"""Logical-axis partition rules → jax.sharding specs (MaxText-style).

Every parameter / activation dimension in the model code is tagged with a
*logical* axis name ("embed", "heads", "vocab", ...).  A rule table maps each
logical name to zero or more *mesh* axes.  The same model code therefore runs
under any mesh by swapping the rule table — this is what makes the 40
(arch × shape) dry-run cells and the elastic re-mesh path share one model
definition.

Mesh axes (launch/mesh.py):
  pod    — data parallelism across pods (crosses DCI)
  data   — data parallelism / FSDP within a pod
  model  — tensor / expert parallelism within a pod

Rules may map a logical axis to an axis that does not exist in the current
mesh (e.g. "pod" on the single-pod mesh) — such entries are silently dropped,
and a logical dim whose mesh-axis product does not divide the actual dim size
falls back to replication (GQA KV heads with kv < model-axis size).

Model code calls ``constrain(x, ("batch", None, "heads", None))``; the ambient
shard context (set by the step builders in launch/) supplies (rules, mesh).
With no ambient context ``constrain`` is a no-op, so smoke tests run unsharded
on one device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> tuple of mesh axis names (in order)."""

    rules: Mapping[str, tuple[str, ...]]

    def get(self, name: str | None) -> tuple[str, ...]:
        if name is None:
            return ()
        return tuple(self.rules.get(name, ()))

    def replace(self, **kw: tuple[str, ...]) -> "AxisRules":
        d = dict(self.rules)
        d.update(kw)
        return AxisRules(d)


# Training: FSDP over ("data",) on the embed dim of weights, tensor parallel
# over ("model",) on heads / ff / vocab / experts; batch over (pod, data).
TRAIN_RULES = AxisRules({
    "batch": ("pod", "data"),
    "seq": (),
    "embed": ("data",),          # FSDP shard dim of weight matrices
    "embed_act": (),             # activations keep d_model replicated
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "qkv": ("model",),           # fused qkv output dim
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ff": (),
    "layers": (),                # scan-stacked leading layer dim
    "d_inner": ("model",),       # mamba inner channels
    "d_state": (),
    "conv_kernel": (),
    "cache_seq": (),             # decode KV cache sequence dim
    "enc_seq": (),
})

# Serving: pure tensor parallelism — weights sharded over "model" only and
# REPLICATED over "data"/"pod" (weights are served in bf16, so the biggest
# assigned arch fits: qwen2-72b = 144 GB bf16 / 16 model-ranks = 9 GB/chip).
# FSDP-style "embed" sharding would all-gather every weight on every decoded
# token (~250 MB/layer measured on qwen2-72b decode_32k — EXPERIMENTS.md
# §Perf); with TP-only layout the per-token collectives are the attention
# split-K psums and FFN output psums (~KBs).  The decode KV cache shards its
# sequence dim over "model" (split-K decode).
SERVE_RULES = TRAIN_RULES.replace(cache_seq=("model",), embed=())

# Weight-distributed serving for tiny batches (long_500k: global_batch=1):
# with nothing to amortize weight reads over, reading w/256 per step +
# cheap activation psums beats TP-only's w/16 per step (measured 34× on
# falcon-mamba long_500k — EXPERIMENTS.md §Perf).
SERVE_RULES_SMALL_BATCH = SERVE_RULES.replace(embed=("data",))


def serve_rules(global_batch: int) -> AxisRules:
    """Layout choice is batch-dependent: big-batch decode amortizes local
    weight reads (TP-only); tiny-batch decode wants weights spread over
    every chip (weight-distributed)."""
    return SERVE_RULES if global_batch >= 16 else SERVE_RULES_SMALL_BATCH


def pod_decode_rules(mesh, base: AxisRules = SERVE_RULES) -> AxisRules:
    """SERVE_RULES specialized for a replica's shard_map decode tick on
    ``mesh`` (ShardedReplica, single-host or a multi-process pod).

    The decode body is run under shard_map and is collective-free — purely
    batch-parallel — so the slot/batch axis must absorb EVERY mesh axis.
    Mapping "batch" to all of them does two things at once: the pod's full
    device set (the "model" axis included, even when it spans hosts)
    jointly serves one replica's S slots, and ``spec_for``'s first-use-wins
    rule then DROPS the base table's model-axis mappings (cache_seq,
    kv_heads, vocab) on every cache/logits leaf — batch is the leading
    sharded dim of every decode-state leaf, so no leaf can demand a
    collective the body doesn't perform.  The spec derivation itself is the
    same rules machinery the multi-host launcher shards by.

    "cache_blocks" (the physical-block axis of a paged KV pool) maps to the
    same axes as "batch": a shard owns a contiguous range of blocks exactly
    as it owns a contiguous range of slots, and the paged allocator pins a
    slot's blocks to its own partition, so the decode body stays
    collective-free in the paged layout too."""
    axes = tuple(mesh.axis_names)
    return base.replace(batch=axes, cache_blocks=axes)


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(logical_axes: Sequence[str | None], rules: AxisRules,
             mesh: Mesh | None = None,
             dim_sizes: Sequence[int] | None = None) -> P:
    """PartitionSpec for one array whose dims are named by ``logical_axes``.

    If ``mesh``/``dim_sizes`` are given, any mapping that would not divide the
    dim size (or names a mesh axis that doesn't exist) is dropped → replicate.
    Also guarantees no mesh axis is used twice across dims (first wins).
    """
    sizes = _mesh_axis_sizes(mesh) if mesh is not None else None
    used: set[str] = set()
    out: list = []
    for i, name in enumerate(logical_axes):
        axes = [a for a in rules.get(name) if (sizes is None or a in sizes)]
        axes = [a for a in axes if a not in used]
        if sizes is not None and dim_sizes is not None and axes:
            total = int(np.prod([sizes[a] for a in axes]))
            if dim_sizes[i] % total != 0:
                # keep the largest divisible prefix of the axis list
                keep: list[str] = []
                prod = 1
                for a in axes:
                    if dim_sizes[i] % (prod * sizes[a]) == 0:
                        keep.append(a)
                        prod *= sizes[a]
                    else:
                        break
                axes = keep
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    while out and out[-1] is None:   # canonical form
        out.pop()
    return P(*out)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def tree_specs(axes_tree, rules: AxisRules, mesh: Mesh | None = None,
               shapes_tree=None):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs.

    ``axes_tree`` mirrors the params pytree with tuples of logical names as
    leaves.  ``shapes_tree`` (optional, same structure, tuples of ints — use
    jax.eval_shape output) enables the divisibility fallback.
    """
    if shapes_tree is None:
        return jax.tree.map(lambda ax: spec_for(ax, rules, mesh), axes_tree,
                            is_leaf=_is_axes_leaf)
    shapes = jax.tree.map(lambda s: tuple(s.shape) if hasattr(s, "shape") else tuple(s),
                          shapes_tree,
                          is_leaf=lambda x: hasattr(x, "shape") or _is_axes_leaf(x))
    return jax.tree.map(
        lambda ax, shp: spec_for(ax, rules, mesh, shp), axes_tree, shapes,
        is_leaf=_is_axes_leaf)


def tree_shardings(axes_tree, rules: AxisRules, mesh: Mesh, shapes_tree=None):
    specs = tree_specs(axes_tree, rules, mesh, shapes_tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Ambient shard context: model code calls constrain() without knowing the mesh.
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def shard_ctx(rules: AxisRules, mesh: Mesh):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (rules, mesh)
    try:
        yield
    finally:
        _TLS.ctx = prev


@contextlib.contextmanager
def no_shard_ctx():
    """Suspend the ambient context — used inside shard_map bodies, where
    per-array with_sharding_constraint no longer applies (the body already
    works on explicit per-device blocks)."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = None
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_ctx():
    return getattr(_TLS, "ctx", None)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with this repo's keyword defaults."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def constrain(x, logical_axes: Sequence[str | None]):
    """with_sharding_constraint through the ambient logical-axis table.

    No-op when no shard context is active (single-device smoke tests)."""
    ctx = current_ctx()
    if ctx is None:
        return x
    rules, mesh = ctx
    spec = spec_for(logical_axes, rules, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class logical:
    """Helper namespace: shorthand constructors for axis tuples."""

    @staticmethod
    def act(*names: str | None):
        return tuple(names)
