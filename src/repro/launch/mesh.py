"""Production mesh construction — single-host and multi-process.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (smoke tests see 1 device; only dryrun.py forces
512 host devices).

Axes:
  pod   — data parallelism across pods; gradient all-reduce crosses DCI,
          which is why it is the *last* axis collectives are scheduled on
          (launch/train.py hierarchical all-reduce).
  data  — within-pod data parallelism / FSDP.
  model — tensor / expert parallelism (highest-bandwidth ICI dimension).

Multi-process (one serving pod spanning hosts):

  ``init_distributed`` wraps ``jax.distributed.initialize`` idempotently —
  coordinator address, process count, and rank are plumbed from config
  (``worker.py --pod-rank/--coordinator``), never discovered ambiently.
  After it runs, ``jax.devices()`` is the GLOBAL device list and
  ``make_pod_mesh`` lays a ("data", "model") mesh over it with the "model"
  axis varying across processes — one logical replica whose weights and KV
  cache span hosts.

  Not every backend can place one program across processes (the CPU
  backend forms the cluster but raises at dispatch); ``spmd_across_
  processes`` probes this ONCE with a tiny cross-process computation so
  callers can degrade deterministically (every rank reaches the same
  verdict — same backend everywhere) instead of dying mid-serve.
"""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic variant: any (shape, axes) pair — used by launch/elastic.py to
    re-mesh after node loss/gain and by tests for small device counts.

    Axes are Auto: ``constrain`` and ``shard_map`` place arrays through
    sharding constraints, which Explicit axes (``jax.make_mesh``'s default
    since JAX 0.7) reject."""
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)


# ---------------------------------------------------------------------------
# multi-process pods (jax.distributed)
# ---------------------------------------------------------------------------

_DIST = {"initialized": False}


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     *, timeout_s: int = 120) -> int:
    """Join (or form) a jax.distributed cluster; returns this process's
    rank.  Idempotent: a pod worker re-initialized by a second router
    attach must not crash on "already initialized" — the cluster outlives
    any one control connection."""
    if _DIST["initialized"]:
        return int(jax.process_index())
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_processes),
            process_id=int(process_id),
            initialization_timeout=int(timeout_s))
    except RuntimeError as e:
        # another caller on this process won the race — that is fine; any
        # other failure (coordinator unreachable, rank clash) is not
        if "already initialized" not in str(e).lower():
            raise
    _DIST["initialized"] = True
    return int(jax.process_index())


def shutdown_distributed():
    if not _DIST["initialized"]:
        return
    _DIST["initialized"] = False
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass


def make_pod_mesh(*, data: int = 1, devices=None):
    """The serving-pod mesh: ("data", "model") over every visible device —
    after ``init_distributed`` that is the whole cluster, and the device
    list is process-major, so with ``data=1`` the "model" axis runs across
    process boundaries (the multi-host tensor-parallel dimension).  Built
    with an explicit device arrangement, NOT ``jax.make_mesh`` — the
    performance-driven reordering there could fold the model axis back
    inside one host."""
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if data < 1 or n % data != 0:
        raise ValueError(f"{n} devices do not divide over data={data}")
    arr = np.array(devices, dtype=object).reshape(data, n // data)
    return Mesh(arr, ("data", "model"))


def local_pod_mesh(*, axis: str = "model"):
    """This process's share of a pod as a one-axis mesh over its LOCAL
    devices — the degraded (mirror) layout used when the backend cannot
    place one program across processes: every rank runs the full replica
    in lockstep on its own devices (see worker.py pod mode)."""
    from jax.sharding import Mesh

    arr = np.array(jax.local_devices(), dtype=object)
    return Mesh(arr, (axis,))


_SPMD_PROBE = {}


def spmd_across_processes() -> bool:
    """Can one jitted computation span every process of the cluster?

    True trivially for a single-process cluster.  Otherwise probe with a
    tiny addition over the global mesh: backends without cross-process
    dispatch (CPU as of jax 0.4.x) raise at compile/dispatch time, on
    every rank, deterministically — which is exactly the property that
    lets each rank pick the same pod mode without a vote."""
    if jax.process_count() == 1:
        return True
    if "ok" in _SPMD_PROBE:
        return _SPMD_PROBE["ok"]
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        mesh = make_pod_mesh()
        n = mesh.devices.size
        sh = NamedSharding(mesh, P(None, "model"))
        x = jax.make_array_from_callback(
            (1, n), sh, lambda idx: np.ones((1, 1), np.float32))
        jax.jit(lambda v: v + 1, out_shardings=sh)(x)
        _SPMD_PROBE["ok"] = True
    except Exception:
        _SPMD_PROBE["ok"] = False
    return _SPMD_PROBE["ok"]
