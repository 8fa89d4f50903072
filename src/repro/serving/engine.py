"""Single-replica continuous-batching engine.

Every tick decodes one fixed-shape (slots, 1) token batch — the TPU-friendly
form (real multi-host serving shards the same cache via SERVE_RULES; this
engine exercises the logic end to end on CPU).  Three things distinguish it
from a naive batched decoder:

* **Chunked prefill.**  Admission prefills only the first ``prefill_chunk``
  prompt tokens one-shot; the rest of the prompt *streams through the shared
  decode tick* one token per step (the slot is in PREFILL phase and feeds
  prompt tokens instead of sampled ones).  A long prompt therefore never
  stalls the other slots' decode progress — admission cost per tick is
  bounded by the chunk.

* **Per-slot ring positions.**  The pool cache's "index" leaf is a (slots,)
  vector, so every slot gets its own RoPE angles, ring-buffer write slot and
  validity mask (see slots.py for why the seed's shared scalar was wrong).

* **Sampling layer.**  Greedy argmax is just the default SamplingParams;
  temperature sampling is seeded per request (scheduler.Request).  The
  device draw is the sampler: it is FUSED into the decode tail
  (steps.make_fused_decode_step), Gumbel-max from stateless (seed, rid,
  output position) counters, and greedy rows and temperature rows over the
  full vocabulary emit its token — a tick pulls (B,) int32s instead of
  (B, 1, V) logits, an admission one int32, and a request replays the same
  stream after a preemption.  Only a temperature row with ``top_k > 0``
  (which the kernel's one static k per call cannot serve) pulls its logits
  row and draws with its stateful per-request host RNG.

* **Speculative decoding** (``spec_k > 0``).  A model-free prompt-lookup
  draft (serving/draft.py) proposes up to k tokens per decode slot from
  the slot's own prompt+generated history; the target model verifies the
  whole window in ONE jitted multi-position decode (steps.make_verify_step)
  and the engine accepts the longest exact-match prefix — emitting a+1
  tokens per tick where the plain path emits 1.  Each lane draws from the
  counters of the output position it would emit.  Rejected tails rewind via
  the pool index vector (the same mechanism preemption uses), so rejected
  K/V is simply re-covered.  PREFILL rows ride the same window: up to W
  upcoming prompt tokens stream per tick.  Acceptance is exact-match on
  sampled tokens, so streams are bit-identical to the plain path for ANY
  sampling mode; families whose state can't rewind (SSM/hybrid recurrence,
  sliding-window rings that wrap) silently serve the plain path.

The low-level admit()/tick() surface is kept compatible with the seed's
launch/serve.py engine; submit()/step() add the queued-request lifecycle.

The host loop records named spans (serving/spans.py) at each of its steps
and counts, in ``EngineStats``, the tokens it emits and the bytes it pulls
from the device, so a profiler trace shows where the host was while the
device sat idle.
"""
from __future__ import annotations

import hashlib
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LM
from repro.models.attention import Attention
from repro.models.steps import (
    make_decode_step, make_fused_decode_step, make_prefill_step,
    make_sample_step, make_verify_step,
)
from repro.serving.draft import ngram_propose
from repro.serving.scheduler import FCFSScheduler, Request
from repro.serving.slots import make_pool
from repro.serving.spans import span

PHASE_FREE, PHASE_PREFILL, PHASE_DECODE = 0, 1, 2


def _reading(now):
    """One reading of the caller's clock: ``now()`` where the caller passed
    the clock itself, else ``now``."""
    return now() if callable(now) else now


def _rid(req) -> int:
    """The request id a span carries (-1 for a slot with no Request)."""
    return req.rid if isinstance(req, Request) else -1


def _host_draws(req) -> bool:
    """A temperature row with ``top_k > 0`` draws on the host: the device
    sampler takes one static k per call, not a per-row one.  Every other
    row takes the device's draw."""
    return (isinstance(req, Request) and req.sampling.temperature > 0.0
            and req.sampling.top_k > 0)


class EngineCore:
    """Model params + jitted step functions, shared by all replicas of one
    deployment — N engines reuse one compile and one weight copy."""

    def __init__(self, cfg, max_seq: int, *, seed: int = 0, params=None):
        """``params`` reuses weights already on the device (e.g. one weight
        copy behind both the Pallas and the jnp-reference configuration);
        by default they are generated from ``seed``."""
        self.cfg = cfg
        self.max_seq = max_seq
        if params is None:
            params, _ = LM.init(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self.prefill = jax.jit(make_prefill_step(cfg, max_seq))
        self.decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))
        # compiled lazily on first use: a fused-sampling decode tick and the
        # multi-position verify step (one retrace per distinct window width)
        self.fused_decode = jax.jit(make_fused_decode_step(cfg),
                                    donate_argnums=(2,))
        self.verify = jax.jit(make_verify_step(cfg), donate_argnums=(2,))
        # the same draw over given logits: an admission's first token, a
        # replaced decode step's tokens
        self.sample = jax.jit(make_sample_step(cfg))


class EngineStats:
    """Per-replica accumulators: a drainable window (one monitoring tick) on
    top of lifetime totals."""

    def __init__(self):
        self.total_completed = 0
        self.total_tokens = 0
        self.total_ticks = 0
        self.total_busy = 0.0
        self.total_spec_proposed = 0
        self.total_spec_accepted = 0
        # host traffic: tokens appended to requests' outputs, and the bytes
        # (and logits rows) the engine materialized from the device; of the
        # temperature tokens, those taken from the device's draw
        self.total_emitted = 0
        self.total_pulled_bytes = 0
        self.total_rows_pulled = 0
        self.total_device_draws = 0
        self.completed_by_tier: dict[str, int] = {}
        self.latencies_ms = deque(maxlen=4096)
        self.queue_depth = 0
        self._reset_window()

    def _reset_window(self):
        self._win_lat: list[float] = []
        self._win_lat_tiers: dict[str, list[float]] = {}
        self._win_completed = 0
        self._win_tokens = 0
        self._win_ticks = 0
        self._win_busy = 0.0
        self._win_spec_prop = 0
        self._win_spec_acc = 0
        self._win_emitted = 0
        self._win_pulled_bytes = 0
        self._win_rows_pulled = 0
        self._win_device_draws = 0

    def on_tick(self, busy_slots: int, slots: int, queue_depth: int):
        self.total_ticks += 1
        self.total_busy += busy_slots / max(slots, 1)
        self._win_ticks += 1
        self._win_busy += busy_slots / max(slots, 1)
        self.queue_depth = queue_depth

    def on_complete(self, request: Request):
        tier = getattr(request, "tier", "interactive")
        lat = request.latency_s
        if lat is not None:
            self.latencies_ms.append(lat * 1e3)
            self._win_lat.append(lat * 1e3)
            self._win_lat_tiers.setdefault(tier, []).append(lat * 1e3)
        self.total_completed += 1
        self.completed_by_tier[tier] = self.completed_by_tier.get(tier, 0) + 1
        self.total_tokens += len(request.tokens_out)
        self._win_completed += 1
        self._win_tokens += len(request.tokens_out)

    def on_speculate(self, proposed: int, accepted: int):
        self.total_spec_proposed += proposed
        self.total_spec_accepted += accepted
        self._win_spec_prop += proposed
        self._win_spec_acc += accepted

    def on_emit(self, device_draw: bool = False):
        """One token appended to a request's output; ``device_draw``: a
        temperature token the device drew."""
        self.total_emitted += 1
        self._win_emitted += 1
        self.total_device_draws += device_draw
        self._win_device_draws += device_draw

    def on_pull(self, nbytes: int, rows: int = 0):
        """One device array materialized on the host: its device bytes
        (before any host cast), and how many of them are logits rows."""
        self.total_pulled_bytes += nbytes
        self._win_pulled_bytes += nbytes
        self.total_rows_pulled += rows
        self._win_rows_pulled += rows

    @property
    def slot_utilization(self) -> float:
        return self.total_busy / max(self.total_ticks, 1)

    def drain_window(self) -> dict:
        """Window metrics since the last drain (one ReplicaReport's worth)."""
        out = {
            "latency_ms_samples": list(self._win_lat),
            # the same samples keyed by tier — the collector's per-tier SLO
            # channels (latency_p95_interactive / _batch) fold these
            "lat_tiers": {t: list(v)
                          for t, v in self._win_lat_tiers.items() if v},
            "n_requests": self._win_completed,
            "n_tokens": self._win_tokens,
            "slot_util": self._win_busy / max(self._win_ticks, 1),
            "queue_depth": self.queue_depth,
            "spec_proposed": self._win_spec_prop,
            "spec_accepted": self._win_spec_acc,
            "emitted_tokens": self._win_emitted,
            "pulled_bytes": self._win_pulled_bytes,
            "rows_pulled": self._win_rows_pulled,
            "device_draws": self._win_device_draws,
        }
        self._reset_window()
        return out


def validate_request(cfg, max_seq: int, prompt: np.ndarray, frames=None):
    """Shape/length validation for one request against (cfg, max_seq).

    Module-level because TWO parties run it: the engine at submit (a
    malformed request must bounce back typed, not abort a batch step
    mid-tick), and a remote replica's parent-side stub — batched submits
    ride the step RPC, so without a local check a bad request would only
    surface a round later, on the wrong side of the wire."""
    P = len(prompt)
    if P < 1:
        raise ValueError("empty prompt")
    if (not cfg.attn_free and cfg.sliding_window is None
            and P >= max_seq):
        raise ValueError(f"prompt ({P}) must fit below max_seq "
                         f"({max_seq}) with room to generate")
    if cfg.family == "vlm" and P <= cfg.n_vision_patches:
        raise ValueError("vlm prompt must extend past the patch prefix")
    if cfg.enc_dec:
        if frames is None:
            raise ValueError("enc-dec request needs encoder frames")
        frames = np.asarray(frames)
        if frames.ndim != 2 or frames.shape[1] != cfg.d_model:
            raise ValueError(f"frames must be (S_enc, d_model="
                             f"{cfg.d_model}), got {frames.shape}")
        if frames.shape[0] < 1 or frames.shape[0] > max_seq:
            raise ValueError(f"encoder length ({frames.shape[0]}) must "
                             f"fit the cross pool (1..{max_seq})")


class ServingEngine:
    """One replica: S decode slots over one shared cache pytree."""

    def __init__(self, cfg, *, slots: int, max_seq: int, seed: int = 0,
                 prefill_chunk: int | None = None,
                 core: EngineCore | None = None, replica_id: int = 0,
                 pool: str = "dense", block_size: int | None = None,
                 num_blocks: int | None = None, partitions: int = 1,
                 spec_k: int = 0, spec_ngram: int = 3):
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.replica_id = replica_id
        self.core = core if core is not None else EngineCore(
            cfg, max_seq, seed=seed)
        self.params = self.core.params
        self.prefill = self.core.prefill
        self.decode = self.core.decode
        self.pool = make_pool(cfg, slots, max_seq, pool=pool,
                              block_size=block_size, num_blocks=num_blocks,
                              partitions=partitions)
        # "paged" on a family with no pageable leaves (pure SSM, short
        # sliding windows) degenerates to the dense pool — same cache tree,
        # so the engine's dense code paths apply unchanged
        self._paged = getattr(self.pool, "is_paged", False)
        self.prefill_tokens = 0      # prompt tokens actually computed
        self.prompt_tokens = 0       # prompt tokens admitted (incl. shared)
        self.tokens = jnp.zeros((slots, 1), jnp.int32)
        self._tokens_host = np.zeros(slots, np.int32)
        # host-side token truth may run ahead of the staged device copy:
        # verify ticks build their window from _tokens_host directly, so
        # they defer the (slots, 1) device put until a fused tick
        # (or admission) actually needs self.tokens
        self._tokens_dirty = False
        self.pos = np.zeros(slots, np.int64)        # per-slot position
        self.remaining = np.zeros(slots, np.int64)  # tokens left to generate
        self.active = np.zeros(slots, bool)
        self.phase = np.zeros(slots, np.int8)
        self.slot_owner: dict[int, Request] = {}    # cleared on release
        chunk = prefill_chunk if prefill_chunk is not None else max_seq
        if cfg.family == "vlm":
            # the patch prefix must land in the one-shot prefill portion
            chunk = max(chunk, cfg.n_vision_patches + 1)
        self.prefill_chunk = max(chunk, 1)
        self._prompt: list[np.ndarray | None] = [None] * slots
        self._fed = np.zeros(slots, np.int64)       # prompt tokens staged
        # vlm prefix KV depends on the vision patches, not just the token
        # ids, so the patch content is digested into every prefix-cache key:
        # two prompts with identical ids but different patches can never
        # alias in the registry.  The engine feeds the same zero patches to
        # every request today (so this is one constant per engine); if
        # patches become request-dependent, digest them per request here.
        self._patch_key = (hashlib.sha1(np.zeros(
            (cfg.n_vision_patches, cfg.d_model), np.float32).tobytes()
        ).digest() if cfg.family == "vlm" else b"")
        self.spec_k = max(int(spec_k), 0)
        self.spec_ngram = max(int(spec_ngram), 1)
        # speculation needs a rewindable cache: recurrent state (SSM towers,
        # hybrid interleaves) can't roll back, and a sliding-window ring
        # shorter than max_seq wraps — speculative writes would clobber live
        # context that rewinding the index cannot restore.  Ineligible
        # families silently serve the plain path; the knob is never an error.
        self._spec_ok = (
            self.spec_k > 0
            and cfg.ssm is None and getattr(cfg, "hybrid", None) is None
            and not cfg.enc_dec and not cfg.attn_free
            and Attention.cache_len(cfg, max_seq) == max_seq)
        self.scheduler = FCFSScheduler()
        self.draining = False
        self.stats = EngineStats()

    # ------------------------------------------------------------- queue API

    def submit(self, request: Request, now: float = 0.0):
        """Enqueue one request.  Validation happens HERE, not at admission:
        a malformed request must bounce back to the submitter, not abort a
        batch step mid-tick with other requests in flight."""
        self._validate(np.asarray(request.prompt).reshape(-1),
                       frames=request.frames)
        if request.t_submit is None:
            request.t_submit = now
        self.scheduler.submit(request)

    def _validate(self, prompt: np.ndarray, frames=None):
        validate_request(self.cfg, self.max_seq, prompt, frames=frames)

    @property
    def idle(self) -> bool:
        return not self.active.any() and not self.scheduler

    @property
    def load(self) -> float:
        """Admitted + queued work relative to slot capacity."""
        return (int(self.active.sum()) + self.scheduler.depth) / max(
            self.slots, 1)

    def step(self, now=None) -> list[Request]:
        """One scheduling round: FCFS admission into free slots, one decode
        tick, completion + slot release.  Returns finished requests.

        ``now`` is the caller's clock: either the clock itself (a callable
        returning seconds), read again when each stamped token is on the
        host, or one reading of it, at which every stamp of the round is
        taken (a virtual clock, on which a round takes no time).  ``None``
        is the engine's own clock, ``time.monotonic``."""
        if now is None:
            now = time.monotonic
        completed: list[Request] = []
        with span("serve.step"):
            t_start = _reading(now)
            if not self.draining:
                free = [s for s in range(self.slots) if not self.active[s]]
                while free and self.scheduler:
                    if self._paged:
                        # head-of-line capacity gate: a paged pool can have
                        # free SLOTS but no free BLOCKS (slots oversubscribe
                        # the pool); admitting anyway would fault
                        # mid-decode, and skipping ahead would break FCFS
                        head = self.scheduler.peek()
                        if not self.pool.can_admit(
                                free[0], np.asarray(head.prompt).reshape(-1),
                                head.gen_len, extra=self._patch_key):
                            break
                    req = self.scheduler.pop()
                    slot = free.pop(0)
                    req.t_admit = t_start
                    req.replica_id = self.replica_id
                    self.admit(slot, req.prompt, req.gen_len, request=req)
                    if self.phase[slot] == PHASE_DECODE:
                        # prompt fit in one chunk: its first token is on
                        # the host now
                        req.t_first_token = _reading(now)
            finished = self.tick(now=now)
            t_done = _reading(now) if finished else None
            for slot in finished:
                req = self.slot_owner.get(slot)
                self.release_slot(slot)
                if isinstance(req, Request):
                    req.t_done = t_done
                    self.stats.on_complete(req)
                    completed.append(req)
            self.stats.on_tick(int(self.active.sum()), self.slots,
                               self.scheduler.depth)
        return completed

    # ------------------------------------------------------------- slot API

    def admit(self, slot: int, prompt: np.ndarray, gen_len: int,
              request: Request | None = None, frames=None):
        """Prefill one slot: one-shot over the first chunk, the remainder of
        the prompt streams through tick() (PREFILL phase).  enc-dec families
        pass ``frames`` (or carry them on the request): the encoder runs
        whole in the one-shot portion — cross K/V cover every frame and the
        decoder prompt tail can still stream through the decode tick."""
        with span("serve.admit", rid=_rid(request), slot=slot):
            self._admit(slot, prompt, gen_len, request, frames)

    def _admit(self, slot, prompt, gen_len, request, frames):
        if self.active[slot]:
            raise ValueError(f"slot {slot} is still active")
        if frames is None and request is not None:
            frames = request.frames
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        P = len(prompt)
        # defense; submit() already rejected malformed requests
        self._validate(prompt, frames=frames)
        if not self.cfg.attn_free and self.cfg.sliding_window is None:
            # full-attention ring wrap would overwrite live context
            gen_len = min(gen_len, self.max_seq - P)
        self.prompt_tokens += P
        if self._paged:
            h_tok = self.pool.admit_slot(slot, prompt, gen_len,
                                         extra=self._patch_key)
            if h_tok > 0:
                # resident prefix: the shared blocks already hold positions
                # 0..h_tok-1, so NO prefill runs at all — the rest of the
                # prompt streams through the decode tick exactly like the
                # chunked-prefill tail, starting at position h_tok
                self.prefill_tokens += P - h_tok
                self.pool.set_slot_index(slot, h_tok)
                self.pos[slot] = h_tok
                self._prompt[slot] = prompt
                self.remaining[slot] = gen_len
                self.active[slot] = True
                if request is not None:
                    self.slot_owner[slot] = request
                self._tokens_host[slot] = int(prompt[h_tok])
                self._fed[slot] = h_tok + 1      # h_tok shared + 1 staged
                self.phase[slot] = PHASE_PREFILL
                self._stage_tokens()
                return
        c = P if self.prefill_chunk >= P else self.prefill_chunk
        self.prefill_tokens += P
        inputs = {"tokens": jnp.asarray(prompt[None, :c])}
        if self.cfg.family == "vlm":
            inputs["patches"] = jnp.zeros(
                (1, self.cfg.n_vision_patches, self.cfg.d_model),
                self.cfg.cdtype)
        if self.cfg.enc_dec:
            inputs["frames"] = jnp.asarray(np.asarray(frames)[None],
                                           self.cfg.cdtype)
        with span("serve.prefill"):
            logits, cache1 = self.prefill(self.params, inputs)
        with span("serve.pool_write"):
            self.pool.write(cache1, slot, index=c)
        if self._paged:
            # blocks fully covered by the one-shot prefill are complete
            # prompt prefixes — publish them for future admissions to share
            for j in range(c // self.pool.block_size):
                self.pool.register_block(slot, j, prompt,
                                         extra=self._patch_key)
        self.pos[slot] = c
        self._prompt[slot] = prompt
        self.remaining[slot] = gen_len
        self.active[slot] = True
        if request is not None:
            self.slot_owner[slot] = request
        if c == P:
            self._tokens_host[slot] = self._first_token(slot, request,
                                                        logits)
            self.phase[slot] = PHASE_DECODE
        else:
            self._tokens_host[slot] = int(prompt[c])
            self._fed[slot] = c + 1              # c cached + 1 staged
            self.phase[slot] = PHASE_PREFILL
        self._stage_tokens()

    def _first_token(self, slot: int, req, logits) -> int:
        """The first generated token, off the prefill's (1, 1, V) logits:
        the device's draw from the request's counters, one int32 pulled;
        a ``top_k > 0`` temperature request pulls the row (its bytes
        counted, not as a tick's pull) and draws on the host."""
        if _host_draws(req):
            row = self._pull_row(logits, (0, -1), slot, rows=0)
            with span("serve.host_draw", rid=req.rid, slot=slot):
                tok = req.sample(row)
            self.stats.on_emit()
            return tok
        toks = self.core.sample(logits, *self._counters([(0, req)], 1))
        tok = int(np.asarray(toks)[0])
        self.stats.on_pull(toks.nbytes)
        if isinstance(req, Request):
            req.tokens_out.append(tok)
            self.stats.on_emit(device_draw=req.sampling.temperature > 0.0)
        return tok

    def tick(self, now: float | None = None) -> list[int]:
        """One decode step for all slots (inactive slots decode garbage that
        is simply ignored).  Returns slots that finished this tick.

        Two paths, one contract (bit-identical token streams):

        * **fused** — sampling runs in the decode tail on device; the
          engine pulls (slots,) int32 tokens, and only a ``top_k > 0``
          temperature row pulls its one (V,) row to draw on the host.  A
          replaced ``self.decode`` (sharded topologies install their own
          compiled step; tests monkeypatch) runs in the fused step's place,
          and the same draw runs over its (slots, 1, V) logits.
        * **verify** — when speculation is on and a draft (or a streamable
          prompt tail) exists, ONE multi-position decode verifies a whole
          (slots, W) window and the engine emits the accepted prefix.

        Both draw from the same (seed, rid, output position) counters, so
        every topology emits the same stream.
        """
        if not self.active.any():
            return []
        with span("serve.tick"):
            # a replaced step is compiled for (slots, 1) decode only
            if self._spec_ok and self.decode is self.core.decode:
                drafts, window_w = self._plan_window()
                if window_w >= 2:
                    return self._tick_verify(drafts, window_w, now)
            return self._tick_fused(now)

    # -------------------------------------------------- shared tick plumbing

    def _pull_row(self, logits, index, slot: int, rows: int = 1):
        """One logits row, ``logits[index]``, to the host as float32; its
        device bytes are counted, and ``rows`` logits pulls."""
        with span("serve.row_pull", rid=_rid(self.slot_owner.get(slot)),
                  slot=slot):
            dev = logits[index]
            row = np.asarray(dev, np.float32)
        self.stats.on_pull(dev.nbytes, rows=rows)
        return row

    def _counters(self, owners, n: int):
        """The sampler's (n,) seed, rid, pos and temperature for rows
        ``owners`` [(row, req)]: pos is the output position the draw
        fills, so a draw is a pure function of (seed, rid, position)."""
        seed = np.zeros(n, np.int32)
        rid = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        temp = np.zeros(n, np.float32)
        for row, req in owners:
            if isinstance(req, Request):
                seed[row] = req.sampling.seed
                rid[row] = req.rid
                pos[row] = len(req.tokens_out)
                temp[row] = req.sampling.temperature
        return seed, rid, pos, temp

    def _stage_tokens(self):
        """Materialize the device copy of every slot's next input token."""
        self.tokens = jnp.asarray(self._tokens_host[:, None])
        self._tokens_dirty = False

    def _emit(self, slot: int, req, tok_dev: int, fetch_row) -> int:
        """One sampled token for a slot: the device's draw (greedy rows
        bit-equal to host argmax, temperature rows Gumbel-max over the full
        vocabulary); a ``top_k > 0`` temperature row instead pulls its one
        logits row and draws with its stateful host RNG."""
        if not isinstance(req, Request):
            return int(tok_dev)
        if _host_draws(req):
            row = fetch_row(slot)
            with span("serve.host_draw", rid=req.rid, slot=slot):
                tok = req.sample(row)
            self.stats.on_emit()
        else:
            tok = int(tok_dev)
            req.tokens_out.append(tok)
            self.stats.on_emit(device_draw=req.sampling.temperature > 0.0)
        return tok

    def _advance(self, toks_host, fetch_row, now) -> list[int]:
        """Single-position tick bookkeeping: per-slot host state advance
        given the device-sampled tokens and a lazy logits-row getter."""
        done: list[int] = []
        for slot in np.nonzero(self.active)[0]:
            slot = int(slot)
            self.pos[slot] += 1
            req = self.slot_owner.get(slot)
            if self.phase[slot] == PHASE_PREFILL:
                prompt = self._prompt[slot]
                pos = int(self.pos[slot])
                if (self._paged and pos % self.pool.block_size == 0
                        and pos <= len(prompt)):
                    # a streamed block just filled with pure prompt tokens —
                    # publish it (positions pos-bk..pos-1 are prompt[:pos])
                    self.pool.register_block(
                        slot, pos // self.pool.block_size - 1, prompt,
                        extra=self._patch_key)
                if self._fed[slot] < len(prompt):
                    self._tokens_host[slot] = int(prompt[self._fed[slot]])
                    self._fed[slot] += 1
                else:
                    # last prompt token just decoded → first generated token
                    self._tokens_host[slot] = self._emit(
                        slot, req, toks_host[slot], fetch_row)
                    self.phase[slot] = PHASE_DECODE
                    if (isinstance(req, Request) and req.t_first_token is None
                            and now is not None):
                        req.t_first_token = _reading(now)
            else:
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0:
                    self.active[slot] = False
                    done.append(slot)
                else:
                    self._tokens_host[slot] = self._emit(
                        slot, req, toks_host[slot], fetch_row)
        self._stage_tokens()
        return done

    def _tick_fused(self, now) -> list[int]:
        """One decode step with sampling fused into the decode tail: the
        kernel draws from stateless (seed, rid, pos) counters per row, and
        the tick pulls (slots,) int32 tokens — no host logits traffic
        unless a ``top_k > 0`` temperature row asks for its row.  A
        replaced decode step takes the fused step's place, and the same
        draw runs over its logits."""
        with span("serve.decode_dispatch"):
            counters = [jnp.asarray(a) for a in self._counters(
                self.slot_owner.items(), self.slots)]
            if self._tokens_dirty:
                self._stage_tokens()
            if self.decode is self.core.decode:
                toks, logits, cache = self.core.fused_decode(
                    self.params, self.tokens, self.pool.cache, *counters)
            else:
                logits, cache = self.decode(self.params, self.tokens,
                                            self.pool.cache)
                toks = self.core.sample(logits, *counters)
            self.pool.cache = cache
        with span("serve.decode_wait"):
            toks_host = np.asarray(toks)                # (slots,) int32
        self.stats.on_pull(toks.nbytes)
        return self._advance(
            toks_host, lambda s: self._pull_row(logits, (s, 0), s), now)

    # ------------------------------------------------------- speculative path

    def _plan_window(self) -> tuple[dict[int, np.ndarray], int]:
        """Collect n-gram drafts and size this tick's verify window.

        Returns (drafts, W).  W is clamped so no ACTIVE row's window writes
        past ``max_seq - 1``: the multi-position decode advances EVERY row's
        index by W, writes wrap modulo the ring, and a wrapped garbage write
        would clobber valid context (or, paged, a shared prefix block) that
        rewinding the index cannot restore.  Inactive rows only ever write
        their own garbage slot, so they don't constrain W.  W < 2 means a
        window buys nothing this tick — caller falls back to the fused tick.
        """
        drafts: dict[int, np.ndarray] = {}
        w_cap = self.spec_k + 1
        streamable = False
        for slot in np.nonzero(self.active)[0]:
            slot = int(slot)
            w_cap = min(w_cap, self.max_seq - int(self.pos[slot]))
            if self.phase[slot] == PHASE_PREFILL:
                if self._fed[slot] < len(self._prompt[slot]):
                    streamable = True
                continue
            req = self.slot_owner.get(slot)
            lim = min(self.spec_k, int(self.remaining[slot]) - 1)
            if not isinstance(req, Request) or lim <= 0:
                continue
            # plain-int history: tokens_out already holds python ints, and
            # the list path through ngram_propose is tick-critical
            hist = np.asarray(req.prompt).ravel().tolist() + \
                list(req.tokens_out)
            d = ngram_propose(hist, k=lim, ngram=self.spec_ngram)
            if d.size:
                drafts[slot] = d
        if not drafts and not streamable:
            return {}, 0
        return drafts, max(w_cap, 0)

    def _tick_verify(self, drafts: dict[int, np.ndarray], W: int,
                     now) -> list[int]:
        """One multi-position decode over a (slots, W) window.

        Lane 0 is every slot's staged token (what the plain tick would have
        fed); decode lanes 1.. carry that slot's draft, prefill lanes carry
        upcoming prompt tokens.  Decode lane j draws at output position
        ``len(tokens_out) + j``, the position the plain tick would fill
        after j accepted tokens; a prefill row draws at its base position
        in every lane, the one it emits at.  After the device pass the
        engine accepts
        the longest exact-match draft prefix per slot and REWINDS the pool
        index vector to the authoritative host positions — unconsumed lanes
        simply get re-covered by later writes, the same mechanism preemption
        relies on.
        """
        B = self.slots
        with span("serve.decode_dispatch"):
            window = np.zeros((B, W), np.int32)
            window[:, 0] = self._tokens_host
            n_extra = np.zeros(B, np.int64)  # prompt tokens fed in lanes 1..
            n_draft = np.zeros(B, np.int64)  # draft tokens staged in lanes 1..
            for slot in np.nonzero(self.active)[0]:
                slot = int(slot)
                if self.phase[slot] == PHASE_PREFILL:
                    prompt = self._prompt[slot]
                    m = min(W - 1, len(prompt) - int(self._fed[slot]))
                    if m > 0:
                        lo = int(self._fed[slot])
                        window[slot, 1:1 + m] = prompt[lo:lo + m]
                        n_extra[slot] = m
                elif slot in drafts:
                    d = drafts[slot][:W - 1]
                    window[slot, 1:1 + len(d)] = d
                    n_draft[slot] = len(d)
            seed, rid, pos, temp = self._counters(self.slot_owner.items(), B)
            lane_pos = np.repeat(pos[:, None], W, axis=1)
            lane_pos[self.phase == PHASE_DECODE] += np.arange(W,
                                                             dtype=np.int32)
            toks, logits, cache = self.core.verify(
                self.params, jnp.asarray(window), self.pool.cache,
                jnp.asarray(seed), jnp.asarray(rid), jnp.asarray(lane_pos),
                jnp.asarray(temp))
            self.pool.cache = cache
        with span("serve.decode_wait"):
            toks_host = np.asarray(toks)                # (slots, W) int32
        self.stats.on_pull(toks.nbytes)

        done: list[int] = []
        for slot in np.nonzero(self.active)[0]:
            slot = int(slot)
            req = self.slot_owner.get(slot)

            def fetch_row(lane, slot=slot):
                return self._pull_row(logits, (slot, lane), slot)

            if self.phase[slot] == PHASE_PREFILL:
                done.extend(self._advance_prefill_window(
                    slot, req, int(n_extra[slot]), toks_host, fetch_row, now))
            else:
                done.extend(self._advance_decode_window(
                    slot, req, window, int(n_draft[slot]), toks_host,
                    fetch_row))
        # authoritative rewind: host positions are truth, rejected (and
        # padding) lanes' device writes fall past the new horizon.  The
        # next-token device copy is NOT re-staged here — the next verify
        # window reads _tokens_host directly, so the put is deferred until
        # a fused tick (or admission) needs it.
        self.pool.set_index(self.pos.astype(np.int32))
        self._tokens_dirty = True
        return done

    def _advance_prefill_window(self, slot, req, m, toks_host, fetch_row,
                                now) -> list[int]:
        """A PREFILL slot consumed lanes 0..m: the staged prompt token plus
        m more.  Publish every prompt block the window crossed, then either
        stage the next prompt token or transition to DECODE off the last
        consumed lane's logits."""
        prompt = self._prompt[slot]
        pos_old = int(self.pos[slot])
        self.pos[slot] += 1 + m
        self._fed[slot] += m
        pos_new = int(self.pos[slot])
        if self._paged:
            bs = self.pool.block_size
            q = (pos_old // bs + 1) * bs
            while q <= min(pos_new, len(prompt)):
                self.pool.register_block(slot, q // bs - 1, prompt,
                                         extra=self._patch_key)
                q += bs
        if self._fed[slot] < len(prompt):
            self._tokens_host[slot] = int(prompt[self._fed[slot]])
            self._fed[slot] += 1
        else:
            self._tokens_host[slot] = self._emit(
                slot, req, toks_host[slot, m], lambda s: fetch_row(m))
            self.phase[slot] = PHASE_DECODE
            if (isinstance(req, Request) and req.t_first_token is None
                    and now is not None):
                req.t_first_token = _reading(now)
        return []

    def _advance_decode_window(self, slot, req, window, m, toks_host,
                               fetch_row) -> list[int]:
        """A DECODE slot with m draft lanes: accept the longest prefix where
        the model's sampled token equals the draft, emit a+1 tokens.  Exact-
        match acceptance keeps streams bit-identical for ANY sampling mode —
        each lane's device draw is the plain tick's draw at the same output
        position, and a ``top_k > 0`` temperature row samples each lane with
        its stateful host RNG (one draw per emitted token, same as the plain
        path); either way a lane is accepted iff its token equals the
        draft."""
        a = 0
        for j in range(m + 1):
            # one simulated plain tick per lane: decrement, maybe complete
            # (the plain path's completing tick samples NOTHING — neither
            # may this one, or host RNG streams would diverge)
            self.pos[slot] += 1
            self.remaining[slot] -= 1
            if self.remaining[slot] <= 0:
                self.stats.on_speculate(m, a)
                self.active[slot] = False
                return [slot]
            tok = self._emit(slot, req, toks_host[slot, j],
                             lambda s, j=j: fetch_row(j))
            self._tokens_host[slot] = tok
            if not (j < m and tok == int(window[slot, j + 1])):
                break
            a += 1
        self.stats.on_speculate(m, a)
        return []

    def release_slot(self, slot: int):
        """Free a finished slot: owner cleared here — a stale owner must
        never survive the slot's release (seed bug)."""
        self.active[slot] = False
        self.phase[slot] = PHASE_FREE
        self._prompt[slot] = None
        self._fed[slot] = 0
        self.slot_owner.pop(slot, None)
        if self._paged:
            # refcount decrement: blocks nobody references (no table row,
            # no registry entry) return to the free list immediately
            self.pool.release(slot)

    def preempt_slot(self, slot: int) -> Request | None:
        """Evict an in-flight request from its slot, rewound for requeue.
        The slot's cache rows are garbage after this, which is safe: an
        inactive slot's decode output is ignored and the next admission
        overwrites the rows."""
        req = self.slot_owner.get(slot)
        self.release_slot(slot)
        if isinstance(req, Request):
            req.reset_generation()
            return req
        return None

    def evacuate(self) -> list[Request]:
        """Empty the whole replica for an immediate park/retire: queued
        requests plus every in-flight one (preempted, rewound).  Nothing is
        left behind — the caller requeues the returned requests elsewhere."""
        out = self.scheduler.drain()
        for slot in np.nonzero(self.active)[0]:
            req = self.preempt_slot(int(slot))
            if req is not None:
                out.append(req)
        if self._paged:
            # with every slot released, dropping the prefix registry's own
            # references drives every block refcount back to zero
            self.pool.release_registry()
        return out

    def lifetime(self) -> dict:
        """Lifetime accumulators for fleet-level metrics — ONE definition,
        shared by the in-process replica wrapper and the subprocess worker,
        so the two transports cannot drift apart field-by-field."""
        out = {
            "latencies_ms": [float(v) for v in self.stats.latencies_ms],
            "total_tokens": int(self.stats.total_tokens),
            "total_completed": int(self.stats.total_completed),
            "completed_interactive": int(
                self.stats.completed_by_tier.get("interactive", 0)),
            "completed_batch": int(
                self.stats.completed_by_tier.get("batch", 0)),
            # served ticks: the weight the router's fleet-mean utilization
            # uses (a two-tick replacement must not weigh like a survivor)
            "total_ticks": int(self.stats.total_ticks),
            "slot_utilization": float(self.stats.slot_utilization),
            "queue_depth": int(self.scheduler.depth),
            "prefill_tokens": int(self.prefill_tokens),
            "prompt_tokens": int(self.prompt_tokens),
            "spec_proposed": int(self.stats.total_spec_proposed),
            "spec_accepted": int(self.stats.total_spec_accepted),
            "logits_pulls": int(self.stats.total_rows_pulled),
            "emitted_tokens": int(self.stats.total_emitted),
            "pulled_bytes": int(self.stats.total_pulled_bytes),
            "device_draws": int(self.stats.total_device_draws),
        }
        if self._paged:
            out["prefix_hits"] = int(self.pool.n_prefix_hits)
            out["prefix_admits"] = int(self.pool.n_admits)
            out["tokens_shared"] = int(self.pool.tokens_shared)
        return out

    # ------------------------------------------------------------- compat

    @property
    def logits_pulls(self) -> int:
        """Logits rows pulled by decode ticks: one per host-drawn token (a
        ``top_k > 0`` temperature row)."""
        return self.stats.total_rows_pulled

    @property
    def cache(self):
        return self.pool.cache

    @cache.setter
    def cache(self, value):
        self.pool.cache = value
