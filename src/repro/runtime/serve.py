"""Serving loops: slot-based continuous batching (the fast path) and the
legacy windowed loop (kept as a measured baseline).

Two servers share one request API (``submit`` / ``step`` / ``flush`` /
``done``), so the serving facade's
:class:`~repro.serving.executor.EngineExecutor` drives either:

* :class:`ContinuousBatchingEngine` — a fixed set of *slots* over a
  shared paged KV pool (``runtime/paging.py``).  A request is admitted
  into any free slot the moment enough KV blocks exist (its whole
  ``prompt_len + max_new`` budget is reserved up front, so a running
  request can never strand mid-decode); it decodes for *exactly* its
  own ``max_new`` steps; the step it finishes, its blocks free and its
  slot is re-admittable — decode proceeds continuously while slots
  churn.  Admission that would overcommit the pool raises
  :class:`~repro.runtime.paging.OutOfBlocksError` internally; the
  request waits in the queue and the deferral is counted (the facade
  surfaces it as backpressure telemetry).  Attention runs the Pallas
  paged-decode kernel (``kernels/paged_attention.py``): the block table
  is walked in-kernel, so per-step HBM traffic is O(blocks touched),
  not O(batch * max_len) gather.  Sampling (greedy by default, or
  per-request temperature/top-k/seed via
  :class:`~repro.runtime.sampling.SamplingParams`) happens *inside* the
  fused decode program — one dispatch per step, ``[B]`` ints on the
  wire.

* :class:`WindowedBaselineServer` — the original *windowed* loop: a
  bounded window of requests prefills together, then every request
  decodes for ``max(max_new)`` steps.  Finished requests keep burning
  decode steps as padding, and newly-arrived requests wait for the
  whole window to drain.  Kept only as the baseline that
  ``benchmarks/decode_bench.py`` and ``benchmarks/router_bench.py``
  measure the continuous engine against.

``BatchingServer`` — the windowed loop's old public name — is now a
deprecated shim: it warns and forwards construction to the engine
(falling back to the windowed loop only for stacks paged decode cannot
serve).  New code should not call either constructor directly; build a
:class:`~repro.serving.FleetSpec` and serve through
:class:`~repro.serving.ServingClient` instead.

Shapes stay bucket-fixed in both servers (``max_batch`` / ``max_slots``
and ``prompt_len``), so every step hits a pre-compiled program — no
compile stalls in the serving path.

Two granularities of progress:
  * ``flush()`` — blocking: run until at least one request completes.
  * ``step()``  — non-blocking building block: advance by ONE unit of
    work and return immediately.  This is what lets several servers —
    the router's accelerator pools — interleave on one host instead of
    each monopolizing it for a full generation.
"""
from __future__ import annotations

import struct
import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.partition import PartitionPlan
from repro.models import transformer as T
from repro.runtime import paging
from repro.runtime.paging import BlockAllocator, OutOfBlocksError
from repro.runtime.sampling import GREEDY, SamplingParams, sample_logits


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new: int = 8
    sampling: Optional[SamplingParams] = None   # None -> greedy
    output: Optional[np.ndarray] = None


@dataclass
class _ActiveWindow:
    """One in-progress bounded window (prefill done, decode underway)."""
    batch: List[Request]
    cache: object
    last: object                       # [b, 1] last sampled token
    gen: List[np.ndarray]
    remaining: int                     # decode steps left
    steps_done: int = 0                # decode steps taken so far


class WindowedBaselineServer:
    """The legacy windowed batching loop (greedy-only).  Baseline for the
    decode benchmarks; serve through ``repro.serving`` instead."""

    def __init__(self, params, cfg: ModelConfig,
                 plan: Optional[PartitionPlan] = None, tp: int = 1,
                 max_batch: int = 8, prompt_len: int = 32,
                 max_len: int = 64):
        self.params, self.cfg, self.plan, self.tp = params, cfg, plan, tp
        self.max_batch, self.prompt_len, self.max_len = (max_batch,
                                                         prompt_len, max_len)
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self._active: Optional[_ActiveWindow] = None
        self._prefill = jax.jit(
            lambda p, toks, cache: T.prefill(p, cfg, toks, cache, plan, tp))
        self._decode = jax.jit(
            lambda p, tok, cache: T.decode_step(p, cfg, tok, cache, plan, tp))
        self.reset_stats()

    def reset_stats(self) -> None:
        self.total_tokens = 0             # real sampled tokens only
        self.decode_steps = 0
        self.decode_tokens = 0            # tokens produced by decode steps
        self.decode_s = 0.0               # wall time inside decode steps
        self.prefill_tokens = 0           # prompt tokens prefilled
        self.deferrals = 0                # windowed loop never defers

    def submit(self, req: Request) -> None:
        _require_prompt(req, "server")
        assert req.prompt.shape[0] <= self.prompt_len
        assert self.prompt_len + req.max_new <= self.max_len, \
            (req.rid, req.max_new, self.max_len)
        if req.sampling is not None and not req.sampling.greedy:
            warnings.warn(
                f"request {req.rid}: the windowed baseline decodes "
                f"greedily and ignores SamplingParams; use an engine-"
                f"backed pool for non-greedy sampling")
        self.queue.append(req)

    @property
    def pending(self) -> int:
        """Requests admitted but not yet completed (queued + in-window)."""
        return len(self.queue) + (len(self._active.batch)
                                  if self._active else 0)

    @property
    def occupancy(self) -> float:
        """Fraction of batch slots doing useful work right now."""
        if self._active is None:
            return 0.0
        return len(self._active.batch) / self.max_batch

    def step(self) -> List[Request]:
        """Advance by one unit of work and return requests it completed.

        No active window: start one (prefill + first token) from the queue.
        Active window: run one decode step.  Returns [] until the window's
        last decode step, at which point the whole batch is finalized.
        """
        if self._active is None:
            if not self.queue:
                return []
            self._start_window()
        else:
            w = self._active
            t0 = time.perf_counter()
            out = self._decode(self.params, w.last.astype(jnp.int32), w.cache)
            w.cache = out.cache
            w.last = jnp.argmax(out.logits[:, -1], axis=-1)[:, None]
            w.gen.append(np.asarray(w.last))
            self.decode_s += time.perf_counter() - t0
            w.remaining -= 1
            w.steps_done += 1
            self.decode_steps += 1
            # padding rows past a request's own max_new are not tokens
            useful = sum(1 for r in w.batch if w.steps_done <= r.max_new - 1)
            self.decode_tokens += useful
            self.total_tokens += useful
        return self._finish_if_done()

    def flush(self) -> List[Request]:
        """Serve one bounded window to completion (blocking form of step)."""
        if self._active is None and not self.queue:
            return []
        while True:
            batch = self.step()
            if batch:
                return batch

    def stats(self) -> Dict[str, float]:
        return {"total_tokens": self.total_tokens,
                "decode_steps": self.decode_steps,
                "decode_tokens": self.decode_tokens,
                "decode_s": self.decode_s,
                "prefill_tokens": self.prefill_tokens,
                "deferrals": self.deferrals}

    def _start_window(self) -> None:
        batch = self.queue[:self.max_batch]
        self.queue = self.queue[self.max_batch:]
        b = self.max_batch                        # fixed bucket: no recompiles
        toks = np.zeros((b, self.prompt_len), np.int32)
        for i, r in enumerate(batch):
            toks[i, -r.prompt.shape[0]:] = r.prompt   # left-pad
        cache = T.init_cache(self.cfg, b, self.max_len, self.tp)
        out = self._prefill(self.params, jnp.asarray(toks), cache)
        last = jnp.argmax(out.logits[:, -1], axis=-1)[:, None]
        max_new = max(r.max_new for r in batch)
        self.prefill_tokens += self.prompt_len * len(batch)
        self.total_tokens += sum(1 for r in batch if r.max_new >= 1)
        self._active = _ActiveWindow(batch, out.cache, last,
                                     [np.asarray(last)], max_new - 1)

    def _finish_if_done(self) -> List[Request]:
        w = self._active
        if w is None or w.remaining > 0:
            return []
        gen = np.concatenate(w.gen, axis=1)       # [b, max_new]
        for i, r in enumerate(w.batch):
            r.output = gen[i, :r.max_new]
            self.done[r.rid] = r
        self._active = None
        return w.batch


def engine_or_windowed(params, cfg: ModelConfig,
                       plan: Optional[PartitionPlan] = None, tp: int = 1,
                       max_slots: int = 8, prompt_len: int = 32,
                       max_len: int = 64, block_size: int = 8,
                       num_blocks: Optional[int] = None,
                       prefill_chunk: Optional[int] = None,
                       harden: bool = False, watchdog_steps: int = 8,
                       scrub_blocks: int = 2,
                       on_fallback=None):
    """The one engine-with-windowed-fallback policy.

    Constructs a :class:`ContinuousBatchingEngine`; stacks paged decode
    cannot serve (hybrid/SSM mixers, sliding windows, int8 KV — the
    engine raises ``ValueError``) fall back to the windowed loop, after
    calling ``on_fallback(exc)`` if given.  Both the serving facade's
    ``make_server`` and the deprecated :func:`BatchingServer` shim come
    through here, so the fallback conditions live in exactly one place.
    """
    if max_len > prompt_len:
        try:
            return ContinuousBatchingEngine(
                params, cfg, plan=plan, tp=tp, max_slots=max_slots,
                prompt_len=prompt_len, max_len=max_len,
                block_size=block_size, num_blocks=num_blocks,
                prefill_chunk=prefill_chunk, harden=harden,
                watchdog_steps=watchdog_steps, scrub_blocks=scrub_blocks)
        except ValueError as e:    # non-pageable: keep the windowed loop
            if on_fallback is not None:
                on_fallback(e)
    return WindowedBaselineServer(params, cfg, plan=plan, tp=tp,
                                  max_batch=max_slots,
                                  prompt_len=prompt_len, max_len=max_len)


def BatchingServer(params, cfg: ModelConfig,
                   plan: Optional[PartitionPlan] = None, tp: int = 1,
                   max_batch: int = 8, prompt_len: int = 32,
                   max_len: int = 64):
    """Deprecated windowed-server entry point.

    Warns and forwards to :class:`ContinuousBatchingEngine` (same
    submit/step/flush/done API, strictly better scheduling), falling
    back to the windowed loop via :func:`engine_or_windowed`.  New code
    should build a :class:`repro.serving.FleetSpec` and go through
    :class:`repro.serving.ServingClient` instead.
    """
    warnings.warn(
        "BatchingServer is deprecated; serve through repro.serving "
        "(FleetSpec -> ServingClient). Forwarding to the continuous-"
        "batching engine.", DeprecationWarning, stacklevel=2)
    return engine_or_windowed(params, cfg, plan=plan, tp=tp,
                              max_slots=max_batch, prompt_len=prompt_len,
                              max_len=max_len)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------
@dataclass
class _Slot:
    """One occupied decode slot."""
    req: Request
    gen: List[int]                     # sampled tokens so far
    remaining: int                     # decode steps left (exact)
    sampled: bool = False              # non-greedy sampling requested


_DEFER = object()        # admission verdict: blocks unavailable, retry later


def _require_prompt(req: Request, who: str) -> None:
    """Every server rejects empty prompts up front: a zero-length prompt
    used to slip into a batch and crash it mid-admission (the -0 slice
    selects the whole row)."""
    if req.prompt.shape[0] == 0:
        raise ValueError(
            f"request {req.rid}: empty prompt — the {who} needs at "
            f"least one prompt token to prefill")


@jax.jit
def _gather_block_rows(caches, rows):
    """Export the KV content of ``rows`` from every sublayer pool —
    one fused device call per handoff (the DPU->VPU DMA analogue)."""
    return {key: (st.k_pool[:, rows], st.v_pool[:, rows])
            for key, st in caches.items()}


@jax.jit
def _paste_block_rows(caches, kv, rows):
    """Import handed-off KV content into ``rows`` of every sublayer
    pool (mirrored geometry); the receiving side of the handoff."""
    out = {}
    for key, st in caches.items():
        k_b, v_b = kv[key]
        out[key] = st._replace(
            k_pool=st.k_pool.at[:, rows].set(k_b.astype(st.k_pool.dtype)),
            v_pool=st.v_pool.at[:, rows].set(v_b.astype(st.v_pool.dtype)))
    return out


class StageSpan:
    """One timed phase of a serving step, reported when it completes to
    a stage hook ``hook(stage, t0, t1, rids, attrs)`` with wall
    ``perf_counter`` endpoints, and annotated in the profiler trace
    (``label``, by default ``engine.<stage>``) while it runs.

    ``t0`` starts the span at a stamp the caller already holds, so that
    consecutive phases tile a step with nothing between them; ``end(t)``
    ends it at a stamp the caller takes for its own counters, so that a
    counter and its span agree exactly.  A span left by an exception
    reports nothing: its phase did not complete."""

    __slots__ = ("hook", "stage", "rids", "attrs", "label", "t0", "t1",
                 "_annotation")

    def __init__(self, hook, stage: str, rids, attrs: Dict,
                 t0: Optional[float], label: Optional[str]):
        self.hook, self.stage, self.rids, self.attrs = hook, stage, rids, attrs
        self.label = label or f"engine.{stage}"
        self.t0, self.t1 = t0, None

    def __enter__(self) -> "StageSpan":
        self._annotation = jax.profiler.TraceAnnotation(self.label)
        self._annotation.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter()
        return self

    def end(self, t: float) -> float:
        self.t1 = t
        return t

    def __exit__(self, kind, value, tb) -> bool:
        if self.t1 is None:
            self.t1 = time.perf_counter()
        self._annotation.__exit__(kind, value, tb)
        if kind is None:
            self.hook(self.stage, self.t0, self.t1, list(self.rids),
                      self.attrs)
        return False


class _NoSpan:
    """The span of an unhooked server: stamps and annotates nothing."""
    t0 = t1 = None

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, kind, value, tb) -> bool:
        return False

    @staticmethod
    def end(t: float) -> float:
        return t


_NO_SPAN = _NoSpan()


def stage_span(hook, stage: str, rids=(), *, t0: Optional[float] = None,
               label: Optional[str] = None, **attrs):
    """``with stage_span(server.on_stage, "decode_sync", step=n):`` times
    the block as a :class:`StageSpan` when ``hook`` is set; with no hook
    it costs the None check and nothing else."""
    if hook is None:
        return _NO_SPAN
    return StageSpan(hook, stage, rids, attrs, t0, label)


class ContinuousBatchingEngine:
    """Slot-based continuous-batching decode over a paged KV pool.

    ``max_slots`` batch slots share a pool of ``num_blocks`` KV blocks
    of ``block_size`` tokens.  Requests admit into free slots as soon as
    the pool can cover their full ``prompt_len + max_new`` budget (the
    reservation is up-front, so admitted work never deadlocks on
    blocks), decode for exactly their own ``max_new`` steps, and free
    their slot + blocks the step they finish.  One ``step()`` =
    admissions (each a batch-1 prefill pasted into the pool) + one
    batched decode step for every occupied slot.

    Sampling is per-request (``Request.sampling``): greedy when unset,
    otherwise temperature/top-k with a counter-based key
    (``fold_in(seed, token_index)``) so outputs are independent of batch
    composition.  Both the admission prefill and the decode step sample
    inside their fused jitted programs.

    Per-token observability: set ``on_token`` to a callable
    ``(rid, token)``; it fires the step each token is sampled (admission
    first-tokens included) — this is what feeds the serving facade's
    ``ResponseHandle.stream()``.

    The engine keeps the block table and per-slot lengths as host-side
    numpy mirrors (the allocator is host code) and pushes them into the
    per-layer :class:`~repro.runtime.paging.PagedKVState` before each
    device call; device-side length bumps from ``append_tokens`` are
    mirrored by the host bookkeeping, so the push is idempotent.
    """

    def __init__(self, params, cfg: ModelConfig,
                 plan: Optional[PartitionPlan] = None, tp: int = 1,
                 max_slots: int = 8, prompt_len: int = 32,
                 max_len: int = 64, block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 harden: bool = False, watchdog_steps: int = 8,
                 scrub_blocks: int = 2):
        self.params, self.cfg, self.plan, self.tp = params, cfg, plan, tp
        self.max_slots, self.prompt_len = max_slots, prompt_len
        self.max_len, self.block_size = max_len, block_size
        assert max_len > prompt_len, (max_len, prompt_len)
        # chunked paged prefill: prompts longer than the prompt_len
        # bucket admit in block-aligned chunks of this many tokens,
        # written straight into paged blocks (no dense scratch cache
        # bounds them) — the only remaining prompt limit is max_len
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else max(block_size,
                                       prompt_len // block_size
                                       * block_size))
        assert self.prefill_chunk % block_size == 0, \
            (self.prefill_chunk, block_size)
        self.table_width = -(-max_len // block_size)
        if num_blocks is None:
            num_blocks = max_slots * self.table_width
        assert num_blocks >= self.table_width, \
            "pool smaller than one max-length request"
        self.alloc = BlockAllocator(num_blocks)
        self.shared = paging.SharedBlockIndex(self.alloc)
        self.table = -np.ones((max_slots, self.table_width), np.int32)
        self.lengths = np.zeros(max_slots, np.int32)
        self.caches = T.init_paged_decode_cache(
            cfg, max_slots, num_blocks, block_size, tp,
            max_blocks=self.table_width)
        self.slots: List[Optional[_Slot]] = [None] * max_slots
        self.last = np.zeros((max_slots, 1), np.int32)
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self._dirty = True                    # host table/lengths changed
        # --- radiation hardening (SEU detection + recovery) -----------
        # harden=True turns on per-block integrity digests: sealed (full)
        # blocks are checksummed, the decode step recomputes every live
        # row's checksum *inside the fused program* (detection lands the
        # same step a corrupted block is read, before any token escapes),
        # and scrub() gives idle pools a budgeted background pass.  The
        # token path itself is untouched — hardened outputs with no
        # faults are bit-identical to hardening-off.
        self.harden = bool(harden)
        self.watchdog_steps = int(watchdog_steps)
        self.scrub_blocks = int(scrub_blocks)
        self.digests = paging.BlockDigestStore()
        # whoever frees a block (finalize, shared-index refcount drop,
        # eviction) retires its seal with it — a recycled block can never
        # false-positive against stale content
        self.alloc.on_release = self.digests.forget
        self.stalled: set = set()       # slots latched by a stall fault
        self._tripped: set = set()      # stalled slots already evicted
        self._stall_age = np.zeros(max_slots, np.int64)
        self._restore_queue: List[tuple] = []   # (req, gen) to replay
        self._armed_flips: List[int] = []       # kv_bitflip seeds pending
        self.mute_rids: set = set()     # one-shot emission suppression
        self._idle_steps = 0            # livelock guard
        # a disaggregated decode engine must not self-restore: its KV
        # came from a peer prefill engine under a different plan, so the
        # seam owner re-runs the handoff instead (restore_import)
        self.external_restore = False
        self.on_token: Optional[Callable[[int, int], None]] = None
        # stage hook: ``on_stage(stage, t0, t1, rids, attrs)`` with wall
        # perf_counter endpoints, one call per completed phase
        # (``_span``) or point event (``_stage``).  Any subscriber may
        # set it (the executor's tracer relay chains to the one it
        # finds); while it is set every phase is also annotated in the
        # profiler trace and decode steps wait on the device apart from
        # the copy back.  Unset, the hot path pays a None check per phase
        self.on_stage = None
        # per-slot sampling knobs, threaded through the jit boundary.
        # Device copies are refreshed per admission round (the only
        # place they change) and the per-token step counters only when
        # a sampled request is active, so the greedy decode hot path
        # pays no per-step transfers.
        self._temps = np.zeros(max_slots, np.float32)
        self._topks = np.zeros(max_slots, np.int32)
        self._seeds = np.zeros(max_slots, np.int32)
        self._gen_counts = np.zeros(max_slots, np.int32)  # tokens so far
        self._knobs_dev = (jnp.asarray(self._temps),
                           jnp.asarray(self._topks),
                           jnp.asarray(self._seeds))
        self.reset_stats()
        # admissions prefill together at the max_slots bucket (rows for
        # non-admitted slots are dead weight but keep shapes fixed)
        self._prefill_cache = T.init_cache(cfg, max_slots, prompt_len, tp)
        # two compiled variants per program: a pure-argmax one (identical
        # to the pre-sampling program — an all-greedy batch pays zero
        # sampling overhead, which matters on tiny configs where the
        # PRNG work rivals the forward pass) and a sampling one; the
        # host picks per call based on the live slots
        self._admit_step = jax.jit(self._admit_impl, static_argnums=(8,))
        self._chunk_step = jax.jit(self._chunk_impl, static_argnums=(8,))

        def _decode_greedy(p, toks, caches):
            out = T.decode_step(p, cfg, toks, caches, plan, tp)
            # greedy sampling inside the program: one dispatch per step,
            # [B] ints on the wire instead of [B, V] logits
            return jnp.argmax(out.logits[:, -1], axis=-1), out.cache
        self._decode = jax.jit(_decode_greedy)

        def _decode_sampled(p, toks, caches, temps, topks, seeds, steps):
            out = T.decode_step(p, cfg, toks, caches, plan, tp)
            nxt = sample_logits(out.logits[:, -1], temps, topks, seeds,
                                steps)
            return nxt, out.cache
        self._decode_with = jax.jit(_decode_sampled)

        # hardened variants: the same decode, plus per-block integrity
        # checksums of the *whole* pool fused into the dispatch — a
        # straight [NB+1] reduction with no row-index operand, so XLA
        # reads memory it already touches instead of materializing a
        # gather (the gathered variant cost ~30% of decode throughput
        # on small configs).  Checksums are computed *after* the step's
        # KV append, so a just-filled tail block's value doubles as its
        # seal — sealing on the decode hot path costs no extra device
        # call.  The host compares only the blocks it holds seals for.

        def _cache_checksums(caches):
            total = None
            for key in sorted(caches):
                s = paging.pool_checksums(caches[key])
                total = s if total is None else total + s
            return total
        self._checksum = jax.jit(_cache_checksums)

        def _decode_greedy_h(p, toks, caches):
            nxt, caches = _decode_greedy(p, toks, caches)
            return nxt, caches, _cache_checksums(caches)
        self._decode_h = jax.jit(_decode_greedy_h)

        def _decode_sampled_h(p, toks, caches, temps, topks, seeds, steps):
            nxt, caches = _decode_sampled(p, toks, caches, temps, topks,
                                          seeds, steps)
            return nxt, caches, _cache_checksums(caches)
        self._decode_with_h = jax.jit(_decode_sampled_h)

    def reset_stats(self) -> None:
        """Zero the telemetry counters (post-jit-warmup)."""
        self.total_tokens = 0                 # real sampled tokens only
        self.decode_steps = 0
        self.occupancy_sum = 0.0
        self.decode_tokens = 0                # tokens from decode steps only
        self.decode_s = 0.0                   # wall time in decode steps
        self.admit_s = 0.0                    # wall time in admission steps
        self.prefill_tokens = 0               # prompt tokens prefilled
        self.deferrals = 0                    # OutOfBlocks admission deferrals
        self.shared.hits = 0                  # prefix blocks served by index
        self.shared.lookups = 0               # share attempts (hits + misses)
        self.bitflips_detected = 0            # checksum mismatches caught
        self.blocks_quarantined = 0           # blocks pulled from service
        self.watchdog_trips = 0               # stalled slots evicted
        self.replays = 0                      # evicted requests rebuilt
        self.scrubbed_blocks = 0              # blocks verified by scrub()

    # ------------------------------------------------------------------
    # public API (shared with WindowedBaselineServer)
    # ------------------------------------------------------------------
    def padded_prompt_len(self, s: int) -> int:
        """Prompt context a length-``s`` prompt occupies once admitted:
        the ``prompt_len`` bucket when it fits (left-padded, matching
        the windowed baseline), else the next prefill-chunk multiple."""
        if s <= self.prompt_len:
            return self.prompt_len
        c = self.prefill_chunk
        return -(-s // c) * c

    def submit(self, req: Request) -> None:
        _require_prompt(req, "engine")
        padded = self.padded_prompt_len(int(req.prompt.shape[0]))
        assert padded + req.max_new <= self.max_len, \
            (req.rid, req.prompt.shape[0], req.max_new, self.max_len)
        self.queue.append(req)

    def _held_blocks(self) -> List[int]:
        """Per-slot count of blocks currently owned — the baseline
        ``plan_blocks`` needs: growing one row must restate every other
        row's holdings so nothing it already owns is re-planned."""
        return [int((self.table[j] >= 0).sum())
                for j in range(self.max_slots)]

    @property
    def pending(self) -> int:
        """Requests admitted but not yet completed (queued + in-slot +
        evicted-awaiting-replay)."""
        return (len(self.queue) + sum(s is not None for s in self.slots)
                + len(self._restore_queue))

    @property
    def occupancy(self) -> float:
        """Fraction of decode slots doing useful work right now."""
        return sum(s is not None for s in self.slots) / self.max_slots

    @property
    def prefix_hits(self) -> int:
        """Prompt blocks served from the content-hash index."""
        return self.shared.hits

    @property
    def prefix_lookups(self) -> int:
        """Prompt blocks offered to the index (hits + misses)."""
        return self.shared.lookups

    def step(self) -> List[Request]:
        """Admit into free slots, then run one decode step; returns the
        requests completed by either (admission completes ``max_new==1``
        requests outright — their single token comes from prefill).

        Hardening rides the same cadence: armed bit flips land first
        (so detection sees them the very step their block is next
        read), the watchdog ages stalled slots, and evicted requests
        replay into free slots before new admissions (recovery has
        priority over fresh work)."""
        if self._armed_flips:
            self._apply_armed_flips()
        if self.stalled:
            self._watchdog()
        if self._restore_queue and not self.external_restore:
            self._restore_pending()
        completed = self._admit()
        completed += self._decode_once()
        # livelock guard: a permanently-stalled single slot (or a
        # restore that can never fit) must fail loudly, not spin the
        # drive loop forever
        if (completed or any(s is not None for s in self.slots)
                or not (self.queue or self._restore_queue)):
            self._idle_steps = 0
        else:
            self._idle_steps += 1
            if self._idle_steps > max(1000, 10 * self.watchdog_steps):
                raise RuntimeError(
                    f"engine livelock: {len(self.queue)} queued + "
                    f"{len(self._restore_queue)} awaiting replay, but no "
                    f"slot can make progress (stalled={sorted(self.stalled)},"
                    f" quarantined={len(self.alloc.quarantined)} blocks)")
        return completed

    def flush(self) -> List[Request]:
        """Blocking form: run until at least one request completes."""
        if not self.pending:
            return []
        while True:
            done = self.step()
            if done:
                return done

    def stats(self) -> Dict[str, float]:
        steps = max(self.decode_steps, 1)
        return {"total_tokens": self.total_tokens,
                "decode_steps": self.decode_steps,
                "mean_occupancy": self.occupancy_sum / steps,
                "decode_tokens": self.decode_tokens,
                "decode_s": self.decode_s,
                "admit_s": self.admit_s,
                "prefill_tokens": self.prefill_tokens,
                "shared_block_hits": self.shared.hits,
                "shared_block_lookups": self.shared.lookups,
                "deferrals": self.deferrals,
                "bitflips_detected": self.bitflips_detected,
                "blocks_quarantined": self.blocks_quarantined,
                "watchdog_trips": self.watchdog_trips,
                "replays": self.replays,
                "scrubbed_blocks": self.scrubbed_blocks}

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _admit_impl(self, params, toks, prefill_cache, caches, admit,
                    temps, topks, seeds, sampled):
        """One fused device call per admission round: bucket-shaped
        prefill, paste of every admitted row's KV into its paged blocks
        (non-admitted rows scatter to the trash row), and the first
        sampled token per row (token index 0 for the sampling key;
        ``sampled`` is static — all-greedy rounds compile to argmax)."""
        out = T.prefill(params, self.cfg, toks, prefill_cache,
                        self.plan, self.tp)
        new_caches = {}
        for key, st in caches.items():
            dc = out.cache[key]
            new_caches[key] = jax.vmap(
                paging.write_prefill_batch,
                in_axes=(0, 0, 0, None))(st, dc.k, dc.v, admit)
        logits = out.logits[:, -1]
        if sampled:
            firsts = sample_logits(logits, temps, topks, seeds,
                                   jnp.zeros_like(seeds))
        else:
            firsts = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return firsts, new_caches

    def _chunk_impl(self, params, toks, caches, seq, start,
                    temps, topks, seeds, sampled):
        """One chunked-prefill device call: run chunk ``toks`` [1, C] of
        sequence ``seq`` at absolute positions ``start..start+C-1``,
        pasting its KV straight into the sequence's paged blocks
        (``write_prefill_chunk``) and attending against the paged prefix
        written by earlier chunks.  Returns the sampled/argmax token off
        the chunk's last logit — callers keep only the final chunk's
        (token index 0 for the sampling key, matching the fused bucket
        admission) — plus the updated caches."""
        out = T.prefill_paged_chunk(params, self.cfg, toks, caches, seq,
                                    start, self.plan, self.tp)
        logits = out.logits[:, -1]
        if sampled:
            firsts = sample_logits(logits, temps, topks, seeds,
                                   jnp.zeros_like(seeds))
        else:
            firsts = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return firsts, out.cache

    def _push_tables(self, table: Optional[np.ndarray] = None,
                     lengths: Optional[np.ndarray] = None) -> None:
        """Broadcast the host table/length mirrors into every sublayer
        cache.  ``table``/``lengths`` override the mirrors for one push
        — the replay path masks every row but the one being rebuilt, so
        the fixed-shape decode program touches nothing else; the next
        dirty push restores the true mirrors."""
        tbl = jnp.asarray(self.table if table is None else table)
        lens = jnp.asarray(self.lengths if lengths is None else lengths)

        def fix(st: paging.PagedKVState) -> paging.PagedKVState:
            return st._replace(
                block_table=jnp.broadcast_to(tbl, st.block_table.shape),
                lengths=jnp.broadcast_to(lens, st.lengths.shape))
        self.caches = jax.tree_util.tree_map(
            fix, self.caches,
            is_leaf=lambda s: isinstance(s, paging.PagedKVState))

    def _emit(self, rid: int, tok: int) -> None:
        if self.on_token is not None:
            self.on_token(rid, tok)

    def _span(self, stage: str, rids=(), **kw):
        """A timed phase (``with self._span("decode_sync", step=n):``)."""
        return stage_span(self.on_stage, stage, rids, **kw)

    def _stage(self, stage: str, t0: float, t1: float, rids, **attrs):
        """A point event (``t0 == t1``) for the stage hook."""
        if self.on_stage is not None:
            self.on_stage(stage, t0, t1, list(rids), attrs)

    # ------------------------------------------------------------------
    # radiation hardening: injection, detection, recovery
    # ------------------------------------------------------------------
    def arm_bitflip(self, seed: int = 0) -> None:
        """Arm one SEU: at the next step with sealed live KV, flip one
        ``seed``-chosen bit in a live paged block.  Armed (not applied
        immediately) because batches run wall-synchronously between
        virtual ticks — the upset must land while KV is actually live,
        exactly when a real particle strike would matter."""
        self._armed_flips.append(int(seed))

    def stall_slot(self, slot: int) -> None:
        """Latch a slot-stall fault: the next request decoding in this
        slot stops making progress (the scheduler cannot see the latent
        upset, so admission still uses the slot) until the watchdog
        evicts it; after the trip the slot is quarantined from admission
        until :meth:`unstall_slot`."""
        self.stalled.add(int(slot) % self.max_slots)

    def unstall_slot(self, slot: int) -> None:
        i = int(slot) % self.max_slots
        self.stalled.discard(i)
        self._tripped.discard(i)
        self._stall_age[i] = 0

    def scrub(self, budget: Optional[int] = None) -> int:
        """Budgeted background integrity pass: verify up to ``budget``
        sealed blocks (round-robin) against their digests; corrupted
        blocks quarantine and their requests replay.  Costs nothing when
        no blocks are sealed; the decode hot path carries its own fused
        full verify, so this mainly covers blocks held while a pool sits
        idle between batches.  Returns blocks verified."""
        if not self.harden or len(self.digests) == 0:
            return 0
        blocks = self.digests.scrub_batch(
            self.scrub_blocks if budget is None else budget)
        if not blocks:
            return 0
        sums = self._row_checksums(blocks)
        self.scrubbed_blocks += len(blocks)
        bad_slots: set = set()
        for b, s in zip(blocks, sums):
            if self.digests.get(b) != int(s):
                bad_slots |= self._on_corrupt_block(b)
        for i in sorted(bad_slots):
            self._evict_slot(i)
        if bad_slots and not self.external_restore:
            self._restore_pending()
        return len(blocks)

    def _row_checksums(self, blocks) -> np.ndarray:
        """Checksums for ``blocks`` — index the one full-pool reduction,
        so every call shape hits the same compiled program."""
        sums = np.asarray(self._checksum(self.caches))
        return sums[np.asarray(blocks, np.int32)]

    def _seal_rows(self, blocks) -> None:
        """Digest freshly-finalized (full, no-longer-written) blocks."""
        if not self.harden:
            return
        bl = [int(b) for b in blocks if int(b) >= 0]
        if not bl:
            return
        for b, s in zip(bl, self._row_checksums(bl)):
            self.digests.seal(b, int(s))

    def _apply_armed_flips(self) -> None:
        """Land armed SEUs on sealed live blocks (deterministic per
        seed).  Flips that cannot land yet (no sealed KV live) stay
        armed — an upset in empty memory is harmless by definition."""
        held = {int(b) for row in self.table for b in row if b >= 0}
        targets = sorted(b for b in held if b in self.digests)
        still_armed: List[int] = []
        for seed in self._armed_flips:
            if not targets:
                still_armed.append(seed)
                continue
            rng = np.random.default_rng(seed)
            b = int(targets[int(rng.integers(len(targets)))])
            key = sorted(self.caches)[int(rng.integers(len(self.caches)))]
            st = self.caches[key]
            which = int(rng.integers(2))
            pool = st.k_pool if which == 0 else st.v_pool
            sh = pool.shape                  # [S, NB+1, P, KVp, hd]
            coord = (int(rng.integers(sh[0])), b,
                     int(rng.integers(sh[2])), int(rng.integers(sh[3])),
                     int(rng.integers(sh[4])))
            nbits = jnp.dtype(pool.dtype).itemsize * 8
            bit = int(rng.integers(nbits))
            u = jnp.uint16 if nbits == 16 else jnp.uint32
            el = jax.lax.bitcast_convert_type(pool[coord], u)
            el = jax.lax.bitcast_convert_type(el ^ u(1 << bit), pool.dtype)
            pool = pool.at[coord].set(el)
            self.caches[key] = (st._replace(k_pool=pool) if which == 0
                                else st._replace(v_pool=pool))
            t = time.perf_counter()
            self._stage("seu_bitflip", t, t, [], block=b, bit=bit,
                        seed=seed)
        self._armed_flips = still_armed

    def _watchdog(self) -> None:
        """Age occupied stalled slots; past the threshold, evict the
        request for replay and quarantine the slot from admission."""
        for i in sorted(self.stalled):
            s = self.slots[i]
            if s is None or i in self._tripped:
                continue
            self._stall_age[i] += 1
            if self._stall_age[i] < self.watchdog_steps:
                continue
            self.watchdog_trips += 1
            self._tripped.add(i)
            self._stall_age[i] = 0
            t = time.perf_counter()
            self._stage("watchdog_trip", t, t, [s.req.rid], slot=i,
                        tokens=len(s.gen))
            self._evict_slot(i)

    def _evict_slot(self, i: int) -> None:
        """Tear a slot down for replay: free its row exactly (shared
        refcounts honored, quarantined blocks skipped by the allocator)
        and queue (request, tokens-so-far) for restoration."""
        s = self.slots[i]
        self.alloc.release(
            self.shared.release(self.table[i][self.table[i] >= 0]))
        self.table[i] = -1
        self.lengths[i] = 0
        self._gen_counts[i] = 0
        self.slots[i] = None
        self._dirty = True
        self._restore_queue.append((s.req, list(s.gen)))

    def _on_corrupt_block(self, b: int) -> set:
        """Account one detected upset: quarantine the block, purge it
        from the shared index (sharers re-prefill fresh copies), drop
        its seal; returns the occupied slots whose rows hold it."""
        self.bitflips_detected += 1
        if self.alloc.quarantine(b):
            self.blocks_quarantined += 1
        self.shared.purge(b)
        self.digests.forget(b)
        t = time.perf_counter()
        self._stage("bitflip_detected", t, t, [], block=b)
        return {i for i in range(self.max_slots)
                if self.slots[i] is not None and b in self.table[i]}

    def _restore_pending(self) -> None:
        """Replay evicted requests into free, healthy slots (recovery
        runs before new admissions; deferred under block pressure)."""
        while self._restore_queue:
            req, gen = self._restore_queue[0]
            # only watchdog-proven slots are avoided — a latent stall the
            # system has not detected yet can catch a replay too (it will
            # trip and move on, same as fresh work)
            free = [i for i in range(self.max_slots)
                    if self.slots[i] is None and i not in self._tripped]
            if not free:
                break
            try:
                self._restore_slot(free[0], req, gen)
            except OutOfBlocksError:
                self.deferrals += 1
                break
            self._restore_queue.pop(0)
            self.replays += 1

    def _restore_slot(self, i: int, req: Request, gen: List[int]) -> None:
        """Rebuild an evicted in-flight request bit-exactly in slot
        ``i``: re-prefill its prompt (sharing prefix blocks via the
        content-hash index when still live — else replaying from the
        prompt), then replay the recorded generated tokens through the
        decode program.  The same programs that produced the original
        KV produce identical bits, and nothing is re-emitted, so the
        stream continues exactly-once from where it stopped."""
        s = int(req.prompt.shape[0])
        sp = req.sampling or GREEDY
        bs = self.block_size
        if s <= self.prompt_len:
            padded_len = self.prompt_len
            need = self._held_blocks()
            need[i] = -(-(self.prompt_len + req.max_new) // bs)
            self.table = paging.plan_blocks(self.table, self.alloc, need)
            self._push_tables()
            self._dirty = False
            toks = np.zeros((self.max_slots, self.prompt_len), np.int32)
            toks[i, -s:] = req.prompt
            admit = np.zeros(self.max_slots, bool)
            admit[i] = True
            self._temps[i], self._topks[i] = sp.temperature, sp.top_k
            self._seeds[i] = sp.seed
            self._knobs_dev = (jnp.asarray(self._temps),
                               jnp.asarray(self._topks),
                               jnp.asarray(self._seeds))
            t0 = time.perf_counter()
            _, self.caches = self._admit_step(
                self.params, jnp.asarray(toks), self._prefill_cache,
                self.caches, jnp.asarray(admit), *self._knobs_dev,
                not sp.greedy)
            self.admit_s += time.perf_counter() - t0
            self.prefill_tokens += self.prompt_len
            self.lengths[i] = self.prompt_len
        else:
            c = self.prefill_chunk
            length = -(-s // c) * c
            padded = np.zeros(length, np.int32)
            padded[length - s:] = req.prompt
            n_prompt_blocks = length // bs
            per_chunk = c // bs
            digests = []
            d = paging.SharedBlockIndex.ROOT
            for b in range(n_prompt_blocks):
                d = self.shared.chain(d, padded[b * bs:(b + 1) * bs])
                digests.append(d)
            hit = 0
            for b in range(n_prompt_blocks - per_chunk):
                if self.shared.lookup(digests[b]) is None:
                    break
                hit = b + 1
            shared_blocks = (hit // per_chunk) * per_chunk
            acquired = [self.shared.acquire(digests[b])
                        for b in range(shared_blocks)]
            self.table[i, :shared_blocks] = acquired
            need = self._held_blocks()
            need[i] = -(-(length + req.max_new) // bs)
            try:
                self.table = paging.plan_blocks(self.table, self.alloc,
                                                need)
            except OutOfBlocksError:
                self.shared.release(acquired)
                self.shared.hits -= len(acquired)
                self.table[i, :shared_blocks] = -1
                raise
            self._push_tables()
            self._dirty = False
            self._temps[i], self._topks[i] = sp.temperature, sp.top_k
            self._seeds[i] = sp.seed
            self._knobs_dev = (jnp.asarray(self._temps),
                               jnp.asarray(self._topks),
                               jnp.asarray(self._seeds))
            self._run_chunks(i, padded, shared_blocks * bs // c, sp,
                             rid=req.rid)
            for b in range(shared_blocks, n_prompt_blocks):
                self.shared.register(digests[b], int(self.table[i, b]))
            padded_len = length
            self.lengths[i] = length
        self._replay_generation(i, req, gen, padded_len, sp)

    def _replay_generation(self, i: int, req: Request, gen: List[int],
                           padded_len: int, sp: SamplingParams) -> None:
        """Replay recorded tokens ``gen[:-1]`` as decode inputs so the
        KV the lost steps had written is regrown bit-identically; every
        other row is masked off the device tables for the duration.
        Outputs are recomputed and discarded — nothing re-emits."""
        g = len(gen)
        t0 = time.perf_counter()
        with self._span("replay", [req.rid], t0=t0, tokens=g, slot=i):
            if g > 1:
                mask_tbl = -np.ones_like(self.table)
                mask_tbl[i] = self.table[i]
                mask_len = np.zeros_like(self.lengths)
                mask_len[i] = padded_len
                self._push_tables(mask_tbl, mask_len)
                last = np.zeros((self.max_slots, 1), np.int32)
                for j in range(g - 1):
                    last[i, 0] = gen[j]
                    if self.harden:  # reuse the compiled hardened program
                        _, self.caches, _ = self._decode_h(
                            self.params, jnp.asarray(last), self.caches)
                    else:
                        _, self.caches = self._decode(
                            self.params, jnp.asarray(last), self.caches)
            self.admit_s += time.perf_counter() - t0
            self.lengths[i] = padded_len + g - 1
            self._gen_counts[i] = g
            self.slots[i] = _Slot(req, list(gen), req.max_new - g,
                                  sampled=not sp.greedy)
            self.last[i, 0] = gen[-1]
            self._dirty = True
            self._seal_rows(
                self.table[i][:self.lengths[i] // self.block_size])

    def restore_import(self, req: Request, gen: List[int],
                       handoff: "PrefillHandoff") -> None:
        """Disaggregated recovery: rebuild an evicted decode slot from a
        *fresh handoff* (the imported KV must reproduce the prefill
        engine's bits — the decode plan's own prefill might differ),
        then replay the recorded tokens.  Raises ``OutOfBlocksError``
        to defer under pressure; the caller parks the handoff payload so
        prefill compute is never repeated."""
        free = [j for j in range(self.max_slots)
                if self.slots[j] is None and j not in self._tripped]
        if not free:
            raise OutOfBlocksError("decode engine has no healthy free slot")
        i = free[0]
        bs, length = self.block_size, handoff.length
        need = self._held_blocks()
        need[i] = -(-(length + req.max_new) // bs)
        self.table = paging.plan_blocks(self.table, self.alloc, need)
        rows = self.table[i][:length // bs]
        self.caches = _paste_block_rows(self.caches, handoff.kv,
                                        jnp.asarray(rows))
        self._verify_import(i, req, handoff, rows)
        self.lengths[i] = length
        sp = req.sampling or GREEDY
        self._temps[i], self._topks[i] = sp.temperature, sp.top_k
        self._seeds[i] = sp.seed
        self._knobs_dev = (jnp.asarray(self._temps),
                           jnp.asarray(self._topks),
                           jnp.asarray(self._seeds))
        self._replay_generation(i, req, gen, length, sp)

    def _verify_import(self, i: int, req: Request,
                       handoff: "PrefillHandoff", rows) -> None:
        """Always-verify at handoff import: recompute the pasted rows'
        checksums against the digests stamped at gather time.  A
        mismatch (payload upset in transit) frees the planned row and
        raises — the seam re-requests the handoff.  Clean imports seal
        the rows with the already-computed sums."""
        if not self.harden or handoff.digests is None:
            return
        sums = self._row_checksums([int(b) for b in rows])
        if any(int(a) != int(e) for a, e in zip(sums, handoff.digests)):
            self.alloc.release(self.table[i][self.table[i] >= 0])
            self.table[i] = -1
            self._dirty = True
            raise HandoffCorruptError(
                f"handoff for request {req.rid} failed integrity verify")
        for b, s in zip(rows, sums):
            self.digests.seal(int(b), int(s))

    def _admit(self) -> List[Request]:
        admits: List[tuple] = []
        completed: List[Request] = []
        # the bucket round's admit_plan phase starts here, or after the
        # round's last chunked admission, which times its own chunks
        hooked = self.on_stage is not None
        plan_t0 = time.perf_counter() if hooked else None
        for i in range(self.max_slots):
            if not self.queue:
                break
            if self.slots[i] is not None or i in self._tripped:
                continue               # occupied, or watchdog-proven bad
            req = self.queue[0]
            if req.prompt.shape[0] > self.prompt_len:
                # over-bucket prompt: chunked paged prefill, one fused
                # chunk call at a time (shares prefix blocks when the
                # content-hash index has them live)
                res = self._admit_chunked(i, req)
                if hooked:
                    plan_t0 = time.perf_counter()
                if res is _DEFER:
                    self.deferrals += 1
                    break
                self.queue.pop(0)
                if res is not None:
                    completed.append(res)
                continue
            need = self._held_blocks()
            need[i] = -(-(self.prompt_len + req.max_new) // self.block_size)
            try:
                self.table = paging.plan_blocks(self.table, self.alloc, need)
            except OutOfBlocksError:
                self.deferrals += 1    # defer admission; blocks will free
                break
            admits.append((i, self.queue.pop(0)))
        if not admits:
            return completed
        with self._span("admit_plan", t0=plan_t0) as ph:
            self._push_tables()            # freed + freshly-planned rows
            self._dirty = False
            # every admission this round rides one fused prefill+paste
            # call; each admitted request occupies its slot's batch row,
            # dead rows keep the compiled shape fixed
            toks = np.zeros((self.max_slots, self.prompt_len), np.int32)
            admit = np.zeros(self.max_slots, bool)
            any_sampled = False
            for i, req in admits:
                toks[i, -req.prompt.shape[0]:] = req.prompt  # left-pad
                admit[i] = True
                sp = req.sampling or GREEDY
                self._temps[i] = sp.temperature
                self._topks[i] = sp.top_k
                self._seeds[i] = sp.seed
                any_sampled |= not sp.greedy
            self._knobs_dev = (jnp.asarray(self._temps),
                               jnp.asarray(self._topks),
                               jnp.asarray(self._seeds))
            temps_d, topks_d, seeds_d = self._knobs_dev
            t0 = ph.end(time.perf_counter())
        with self._span("admit", [req.rid for _, req in admits], t0=t0,
                        tokens=self.prompt_len * len(admits)) as ph:
            firsts, self.caches = self._admit_step(
                self.params, jnp.asarray(toks), self._prefill_cache,
                self.caches, jnp.asarray(admit), temps_d, topks_d, seeds_d,
                any_sampled)
            firsts = np.asarray(firsts)
            t1 = ph.end(time.perf_counter())
        self.admit_s += t1 - t0
        seal: List[int] = []
        for i, req in admits:
            self.lengths[i] = self.prompt_len
            self._gen_counts[i] = 1
            self.prefill_tokens += self.prompt_len
            if self.harden and req.max_new > 1:   # staying: seal the full
                seal.extend(                      # prompt blocks now
                    int(b) for b in
                    self.table[i][:self.prompt_len // self.block_size])
            tok = int(firsts[i])
            if req.max_new >= 1:
                # the admission token only counts when it is actually
                # emitted: a max_new=0 request produces no tokens, and
                # counting its prefill argmax inflated tokens/s
                self.total_tokens += 1
                self._emit(req.rid, tok)
            if req.max_new <= 1:       # done at admission (0 => empty,
                completed.append(       # matching the windowed baseline)
                    self._finalize(i, req, [tok][:req.max_new]))
            else:
                sp = req.sampling or GREEDY
                self.slots[i] = _Slot(req, [tok], req.max_new - 1,
                                      sampled=not sp.greedy)
                self.last[i, 0] = tok
        self._seal_rows(seal)
        return completed

    def _run_chunks(self, i: int, padded: np.ndarray, first_chunk: int,
                    sp: SamplingParams, rid: Optional[int] = None) -> int:
        """Drive the jitted chunk program over ``padded``'s chunks from
        ``first_chunk`` on; returns the final chunk's sampled token.
        The last chunk's stage and ``admit_s`` end with that token on
        the host, as the bucket ``admit`` does."""
        c = self.prefill_chunk
        temps1 = jnp.asarray([sp.temperature], jnp.float32)
        topks1 = jnp.asarray([sp.top_k], jnp.int32)
        seeds1 = jnp.asarray([sp.seed], jnp.int32)
        last = padded.shape[0] // c - 1
        t0 = ct0 = time.perf_counter()
        for ci in range(first_chunk, last + 1):
            with self._span("prefill_chunk", [rid], t0=ct0, chunk=ci,
                            tokens=c) as ph:
                firsts, self.caches = self._chunk_step(
                    self.params,
                    jnp.asarray(padded[ci * c:(ci + 1) * c][None]),
                    self.caches, np.int32(i), np.int32(ci * c),
                    temps1, topks1, seeds1, not sp.greedy)
                if ci == last:
                    tok = int(np.asarray(firsts)[0])
            ct0 = ph.t1
        self.admit_s += time.perf_counter() - t0
        self.prefill_tokens += padded.shape[0] - first_chunk * c
        return tok

    def _admit_chunked(self, i: int, req: Request):
        """Admit one over-bucket prompt into slot ``i`` via chunked
        paged prefill.

        The prompt is left-padded to a whole number of prefill chunks
        (generalizing the bucket's left-pad), its full block budget
        (padded prompt + max_new) reserved atomically, and each chunk
        runs one fused prefill+paste call — the final chunk's is also
        the admit+sample step, exactly like the bucket path.  Before
        allocating, the content-hashed :class:`~repro.runtime.paging.
        SharedBlockIndex` is consulted: a live identical prompt prefix
        (whole chunks only — the final chunk always recomputes, its
        last-token logits seed sampling) is reference-shared instead of
        re-prefilled.  Returns the completed Request for ``max_new<=1``,
        None when the request now occupies the slot, or ``_DEFER`` when
        the pool cannot cover it yet (nothing leaks; retried later)."""
        bs, c = self.block_size, self.prefill_chunk
        s = int(req.prompt.shape[0])
        length = -(-s // c) * c
        padded = np.zeros(length, np.int32)
        padded[length - s:] = req.prompt
        n_prompt_blocks = length // bs
        per_chunk = c // bs
        digests = []
        d = paging.SharedBlockIndex.ROOT
        for b in range(n_prompt_blocks):
            d = self.shared.chain(d, padded[b * bs:(b + 1) * bs])
            digests.append(d)
        hit = 0
        for b in range(n_prompt_blocks - per_chunk):
            if self.shared.lookup(digests[b]) is None:
                break
            hit = b + 1
        shared_blocks = (hit // per_chunk) * per_chunk
        acquired = [self.shared.acquire(digests[b])
                    for b in range(shared_blocks)]
        self.table[i, :shared_blocks] = acquired
        need = self._held_blocks()
        need[i] = -(-(length + req.max_new) // bs)
        try:
            self.table = paging.plan_blocks(self.table, self.alloc, need)
        except OutOfBlocksError:
            self.shared.release(acquired)   # refs only; owners keep blocks
            self.shared.hits -= len(acquired)   # retry will re-count them
            self.table[i, :shared_blocks] = -1
            return _DEFER
        self._push_tables()
        self._dirty = False
        sp = req.sampling or GREEDY
        self._temps[i], self._topks[i] = sp.temperature, sp.top_k
        self._seeds[i] = sp.seed
        self._knobs_dev = (jnp.asarray(self._temps),
                           jnp.asarray(self._topks),
                           jnp.asarray(self._seeds))
        tok = self._run_chunks(i, padded, shared_blocks * bs // c, sp,
                               rid=req.rid)
        # publish the freshly prefilled prompt blocks for future sharers
        # (all are full, read-only blocks: decode appends start a new
        # block because the padded length is block-aligned)
        for b in range(shared_blocks, n_prompt_blocks):
            self.shared.register(digests[b], int(self.table[i, b]))
        self.lengths[i] = length
        self._gen_counts[i] = 1
        if self.harden and req.max_new > 1:
            # freshly prefilled prompt blocks are full + read-only from
            # here on (shared-index hits were sealed by their writer)
            self._seal_rows(self.table[i][shared_blocks:n_prompt_blocks])
        if req.max_new >= 1:
            self.total_tokens += 1
            self._emit(req.rid, tok)
        if req.max_new <= 1:
            return self._finalize(i, req, [tok][:req.max_new])
        self.slots[i] = _Slot(req, [tok], req.max_new - 1,
                              sampled=not sp.greedy)
        self.last[i, 0] = tok
        return None

    def _decode_once(self) -> List[Request]:
        # stalled slots occupy their row but make no progress — the
        # latched fault is latent until the watchdog trips it
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and i not in self.stalled]
        if not active:
            return []
        n = self.decode_steps
        # phases tile the step, each starting where the last one ended;
        # decode_step runs from the dispatch to the tokens on the host
        with self._span("decode_prepare", step=n) as ph:
            if self._dirty:
                self._push_tables()
                self._dirty = False
            any_sampled = any(s is not None and s.sampled
                              for s in self.slots)
            t0 = ph.end(time.perf_counter())
        with self._span("decode_step",
                        [self.slots[i].req.rid for i in active], t0=t0,
                        step=n, tokens=len(active)) as whole:
            with self._span("decode_dispatch", t0=t0, step=n) as ph:
                nxt, sums = self._dispatch_decode(any_sampled)
            if self.on_stage is not None:
                # traced steps only: the wait for the device, apart from
                # the copy of its tokens back
                with self._span("decode_sync", t0=ph.t1, step=n) as ph:
                    nxt.block_until_ready()
            with self._span("decode_fetch", t0=ph.t1, step=n) as ph:
                sums_np = None if sums is None else np.asarray(sums)
                nxt = np.asarray(nxt)
                t1 = whole.end(ph.end(time.perf_counter()))
        self.decode_s += t1 - t0
        with self._span("decode_bookkeep", t0=t1, step=n):
            return self._bookkeep_decode(active, nxt, sums_np)

    def _dispatch_decode(self, any_sampled: bool):
        """Launch one decode step; returns its device tokens and, when
        hardened, the pool's per-block checksums (else None)."""
        if self.harden:
            # hardened dispatch: the same decode plus a per-block
            # checksum of the whole pool, fused — detection lands the
            # same step a corrupted block is read, before any token of
            # this step escapes to a stream
            if any_sampled:
                temps_d, topks_d, seeds_d = self._knobs_dev
                nxt, self.caches, sums = self._decode_with_h(
                    self.params, jnp.asarray(self.last), self.caches,
                    temps_d, topks_d, seeds_d,
                    jnp.asarray(self._gen_counts))
            else:
                nxt, self.caches, sums = self._decode_h(
                    self.params, jnp.asarray(self.last), self.caches)
            return nxt, sums
        if any_sampled:
            temps_d, topks_d, seeds_d = self._knobs_dev
            nxt, self.caches = self._decode_with(
                self.params, jnp.asarray(self.last), self.caches,
                temps_d, topks_d, seeds_d, jnp.asarray(self._gen_counts))
        else:
            nxt, self.caches = self._decode(
                self.params, jnp.asarray(self.last), self.caches)
        return nxt, None

    def _bookkeep_decode(self, active: List[int], nxt: np.ndarray,
                         sums_np: Optional[np.ndarray]) -> List[Request]:
        """Host work after a decode step's tokens arrive: verify seals,
        emit each token, finalize, evict and count."""
        bad_slots: set = set()
        if sums_np is not None and len(self.digests):
            # every sealed block is verified every step — the full-pool
            # reduction makes idle sealed blocks free to check too
            items = self.digests.items()
            blks = np.fromiter((b for b, _ in items), np.int64, len(items))
            seals = np.fromiter((d for _, d in items), np.int64, len(items))
            for b in blks[sums_np[blks] != seals]:
                bad_slots |= self._on_corrupt_block(int(b))
        completed: List[Request] = []
        emitted = 0
        for i in active:
            if i in bad_slots:
                continue       # computed from corrupted KV: never emits
            self.lengths[i] += 1           # mirror device append_tokens
            self._gen_counts[i] += 1
            s = self.slots[i]
            tok = int(nxt[i])
            s.gen.append(tok)
            s.remaining -= 1
            self.last[i, 0] = nxt[i]
            self._emit(s.req.rid, tok)
            emitted += 1
            if self.harden and self.lengths[i] % self.block_size == 0:
                # this step's append just filled a block: its fused sum
                # is the seal (no extra device call on the hot path)
                b = int(self.table[i,
                                   self.lengths[i] // self.block_size - 1])
                if 0 <= b < self.alloc.num_blocks and sums_np is not None:
                    self.digests.seal(b, int(sums_np[b]))
            if s.remaining <= 0:
                completed.append(self._finalize(i, s.req, s.gen))
                self.slots[i] = None
        for i in sorted(bad_slots):
            self._evict_slot(i)
        self.decode_steps += 1
        self.total_tokens += emitted
        self.decode_tokens += emitted
        self.occupancy_sum += len(active) / self.max_slots
        return completed

    def _finalize(self, i: int, req: Request, gen: List[int]) -> Request:
        req.output = np.asarray(gen, np.int32)
        self.done[req.rid] = req
        # shared prompt blocks are refcounted by the content-hash index
        # (freed with their last referencing sequence); the rest of the
        # row goes straight back to the allocator
        self.alloc.release(
            self.shared.release(self.table[i][self.table[i] >= 0]))
        self.table[i] = -1
        self.lengths[i] = 0
        self._dirty = True        # device sees the freed row at next push
        return req

    # ------------------------------------------------------------------
    # co-processing handoff (prefill-class <-> decode-class engines)
    # ------------------------------------------------------------------
    def prefill_handoff(self, req: Request) -> "PrefillHandoff":
        """Run ``req``'s prompt through chunked paged prefill on THIS
        engine and export the block-level KV for a peer decode engine —
        the MPAI DPU->VPU handoff.  The prompt is left-padded to the
        engine's bucket/chunk grid exactly like a unified admission, the
        final chunk samples the first output token (under this engine's
        precision plan — the prefill stage owns it), and the filled
        blocks are gathered out and freed before returning: the handoff
        carries KV *content*; the importer re-blocks it into its own
        mirrored pool.  Raises :class:`OutOfBlocksError` when the prompt
        cannot be covered right now (atomic — callers defer and retry)."""
        _require_prompt(req, "engine")
        bs, c = self.block_size, self.prefill_chunk
        length = -(-max(int(req.prompt.shape[0]), self.prompt_len) // c) * c
        free = [j for j, sl in enumerate(self.slots)
                if sl is None and j not in self._tripped]
        if not free:
            raise OutOfBlocksError("prefill engine has no free slot")
        i = free[0]
        padded = np.zeros(length, np.int32)
        padded[length - req.prompt.shape[0]:] = req.prompt
        need = self._held_blocks()
        need[i] = length // bs            # prompt only: no decode budget
        self.table = paging.plan_blocks(self.table, self.alloc, need)
        self._push_tables()
        self._dirty = False
        sp = req.sampling or GREEDY
        tok = self._run_chunks(i, padded, 0, sp, rid=req.rid)
        if req.rid in self.mute_rids:
            # replayed handoff (the original was lost/corrupted after its
            # first token already streamed): recompute deterministically,
            # emit nothing — exactly-once delivery across the seam
            self.mute_rids.discard(req.rid)
        elif req.max_new >= 1:
            self.total_tokens += 1
            self._emit(req.rid, tok)
        rows = self.table[i][:length // bs].copy()
        with self._span("handoff", [req.rid], blocks=len(rows),
                        tokens=length):
            kv = _gather_block_rows(self.caches, jnp.asarray(rows))
            digests = None
            if self.harden:
                # stamp the payload's per-block checksums before the
                # blocks free — the importer verifies the paste against
                # them
                digests = tuple(int(s) for s in
                                self._row_checksums([int(b) for b in rows]))
        self.alloc.release(self.shared.release(rows))
        self.table[i] = -1
        self.lengths[i] = 0
        self._dirty = True
        return PrefillHandoff(req.rid, tok, length, self.block_size, kv,
                              digests)

    def import_prefill(self, req: Request,
                       handoff: "PrefillHandoff") -> Optional[Request]:
        """Admit a request whose prompt KV a co-processing peer already
        prefilled: reserve the full block budget, paste the handed-off
        blocks into this engine's mirrored pool, and resume at decode
        with the peer's first sampled token (emitted there — importing
        never double-counts or double-streams it).  Returns the
        completed Request for ``max_new<=1``, else None; raises
        :class:`OutOfBlocksError` when blocks are short (callers defer)."""
        assert handoff.block_size == self.block_size, \
            (f"mirrored pools must share block geometry: handoff wrote "
             f"{handoff.block_size}-token blocks, this pool holds "
             f"{self.block_size}-token blocks")
        free = [j for j, sl in enumerate(self.slots)
                if sl is None and j not in self._tripped]
        if not free:
            raise OutOfBlocksError("decode engine has no free slot")
        i = free[0]
        bs, length = self.block_size, handoff.length
        assert length + req.max_new <= self.table_width * bs, \
            (req.rid, length, req.max_new, self.max_len)
        need = self._held_blocks()
        need[i] = -(-(length + req.max_new) // bs)
        self.table = paging.plan_blocks(self.table, self.alloc, need)
        rows = self.table[i][:length // bs]
        with self._span("import", [req.rid], blocks=len(rows),
                        tokens=length):
            self.caches = _paste_block_rows(self.caches, handoff.kv,
                                            jnp.asarray(rows))
        self._verify_import(i, req, handoff, rows)
        self.lengths[i] = length
        self._gen_counts[i] = 1
        self._dirty = True                # table + lengths push next step
        sp = req.sampling or GREEDY
        self._temps[i], self._topks[i] = sp.temperature, sp.top_k
        self._seeds[i] = sp.seed
        self._knobs_dev = (jnp.asarray(self._temps),
                           jnp.asarray(self._topks),
                           jnp.asarray(self._seeds))
        tok = handoff.first_token
        if req.max_new <= 1:
            return self._finalize(i, req, [tok][:req.max_new])
        self.slots[i] = _Slot(req, [tok], req.max_new - 1,
                              sampled=not sp.greedy)
        self.last[i, 0] = tok
        return None


# ---------------------------------------------------------------------------
# Prefill/decode disaggregation (MPAI co-processing)
# ---------------------------------------------------------------------------
class HandoffCorruptError(RuntimeError):
    """A PrefillHandoff payload failed its integrity verify at import —
    the seam re-requests the handoff (prefill is deterministic, so the
    replacement carries identical bits and the already-streamed first
    token stays valid)."""


class HandoffWireError(ValueError):
    """A PrefillHandoff wire buffer is structurally unusable — wrong
    magic, unknown schema version, or a truncated/overlong frame.
    Distinct from :class:`HandoffCorruptError` (checksum mismatch on an
    intact frame): corruption is re-requested by the seam; a wire error
    means the two ends do not speak the same format and retrying the
    same bytes cannot help."""


#: PrefillHandoff wire schema version.  Bump on ANY layout change to
#: ``to_bytes`` — field order, widths, the array encoding, or the
#: checksum construction — and keep ``from_bytes`` rejecting everything
#: it does not speak: a decode pod on an older image must fail loudly,
#: never misparse.  Versioning rules: ``repro/serving/WIRE_FORMAT.md``.
WIRE_VERSION = 1
_WIRE_MAGIC = b"MPAI"
_WIRE_HEADER = "<HQI"                  # version, payload length, checksum


def _wire_dtype(name: str) -> np.dtype:
    """Resolve a serialized dtype name, including the ml_dtypes extras
    (bfloat16, float8_*) that numpy cannot name on its own."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        try:
            return np.dtype(getattr(ml_dtypes, name))
        except AttributeError:
            raise HandoffWireError(
                f"handoff wire carries unknown dtype {name!r}") from None


def _pack_array(a: np.ndarray) -> bytes:
    name = a.dtype.name.encode()
    raw = np.ascontiguousarray(a).tobytes()
    return b"".join([
        struct.pack("<i", len(name)), name,
        struct.pack("<i", a.ndim),
        struct.pack(f"<{a.ndim}q", *a.shape),
        struct.pack("<q", len(raw)), raw])


@dataclass
class PrefillHandoff:
    """One prefilled prompt crossing the co-processing seam.

    Produced by :meth:`ContinuousBatchingEngine.prefill_handoff` on the
    prefill-class engine, consumed by
    :meth:`ContinuousBatchingEngine.import_prefill` on the decode-class
    engine.  Carries the first sampled token (the prefill stage owns
    admission sampling), the padded prompt length, and the block-level
    KV per sublayer — ``kv[key] = (k, v)`` with shape
    ``[n_super, n_blocks, P, KVp, hd]`` — in the shared block geometry
    both mirrored pools were built with.

    The handoff is also a *wire format*: ``to_bytes``/``from_bytes``
    serialize it losslessly (bit-exact KV round-trip) under a schema
    version and a whole-frame integrity checksum, so the seam behaves
    identically whether the importer shares the exporter's address
    space or sits across a process/host boundary.  Every
    :class:`CoProcServer` handoff crosses the seam in wire form.
    """
    rid: int
    first_token: int
    length: int                        # padded prompt length (tokens)
    block_size: int
    kv: Dict[str, tuple]
    # per-block integrity checksums stamped at gather time (hardened
    # prefill engines only): the importer recomputes them after pasting
    # and rejects the handoff on mismatch — an upset on the interconnect
    # never becomes served tokens
    digests: Optional[tuple] = None

    # ------------------------------------------------------------------
    # wire format (versioned, integrity-checked)
    # ------------------------------------------------------------------
    def to_bytes(self) -> bytes:
        """Serialize to the versioned wire frame::

            MAGIC(4) | version(u16) | payload_len(u64) | checksum(u32)
            payload: rid(i64) first_token(i32) length(i32)
                     block_size(i32) digests(i32 count, -1=None, u32*)
                     kv(i32 count; per sorted key: name, K arr, V arr)

        Arrays carry dtype name + shape + raw bytes, so the round-trip
        is bit-exact for every pool dtype (bf16/fp16/fp32 included).
        The checksum is :func:`repro.runtime.paging.wire_checksum` over
        the payload — the PR-7 block-checksum construction applied to
        the frame — so an interconnect upset is caught before the
        importer pastes a single block.
        """
        parts = [struct.pack("<qiii", self.rid, self.first_token,
                             self.length, self.block_size)]
        if self.digests is None:
            parts.append(struct.pack("<i", -1))
        else:
            digs = [int(d) & 0xFFFFFFFF for d in self.digests]
            parts.append(struct.pack(f"<i{len(digs)}I", len(digs), *digs))
        parts.append(struct.pack("<i", len(self.kv)))
        for key in sorted(self.kv):
            k, v = self.kv[key]
            kb = key.encode()
            parts.append(struct.pack("<i", len(kb)) + kb)
            parts.append(_pack_array(np.asarray(k)))
            parts.append(_pack_array(np.asarray(v)))
        payload = b"".join(parts)
        head = _WIRE_MAGIC + struct.pack(
            _WIRE_HEADER, WIRE_VERSION, len(payload),
            paging.wire_checksum(payload))
        return head + payload

    @classmethod
    def from_bytes(cls, buf: bytes) -> "PrefillHandoff":
        """Parse a wire frame back into a handoff.

        Raises :class:`HandoffWireError` on structural problems (bad
        magic, version mismatch, truncation — retrying the same bytes
        cannot help) and :class:`HandoffCorruptError` on a checksum
        mismatch over an intact frame (an in-transit upset — the seam
        re-requests the handoff exactly-once).
        """
        buf = bytes(buf)
        hdr = 4 + struct.calcsize(_WIRE_HEADER)
        if len(buf) < hdr:
            raise HandoffWireError(
                f"handoff frame truncated: {len(buf)} bytes < {hdr}-byte "
                f"header")
        if buf[:4] != _WIRE_MAGIC:
            raise HandoffWireError(
                f"not a PrefillHandoff frame (magic {buf[:4]!r})")
        version, plen, want = struct.unpack_from(_WIRE_HEADER, buf, 4)
        if version != WIRE_VERSION:
            raise HandoffWireError(
                f"handoff wire version {version} != {WIRE_VERSION}; both "
                f"seam ends must run the same schema")
        payload = buf[hdr:]
        if len(payload) != plen:
            raise HandoffWireError(
                f"handoff frame truncated: payload {len(payload)} bytes, "
                f"header declares {plen}")
        if paging.wire_checksum(payload) != want:
            raise HandoffCorruptError(
                "handoff frame failed its wire checksum (payload upset "
                "in transit)")
        off = 0

        def take(fmt):
            nonlocal off
            size = struct.calcsize(fmt)
            if off + size > len(payload):
                raise HandoffWireError(
                    "handoff payload underruns its declared structure")
            vals = struct.unpack_from(fmt, payload, off)
            off += size
            return vals

        def take_array():
            nonlocal off
            (nlen,) = take("<i")
            dtype = _wire_dtype(payload[off:off + nlen].decode())
            off += nlen
            (ndim,) = take("<i")
            shape = take(f"<{ndim}q")
            (rlen,) = take("<q")
            if off + rlen > len(payload):
                raise HandoffWireError(
                    "handoff payload underruns its declared structure")
            a = np.frombuffer(payload, dtype, offset=off,
                              count=int(np.prod(shape, dtype=np.int64))
                              ).reshape(shape)
            off += rlen
            return jnp.asarray(a)

        rid, first_token, length, block_size = take("<qiii")
        (ndig,) = take("<i")
        digests = None if ndig < 0 else tuple(take(f"<{ndig}I"))
        (nkv,) = take("<i")
        kv = {}
        for _ in range(nkv):
            (klen,) = take("<i")
            key = payload[off:off + klen].decode()
            off += klen
            kv[key] = (take_array(), take_array())
        return cls(rid, first_token, length, block_size, kv, digests)


class CoProcServer:
    """Disaggregated serving: a prefill-class engine fanning out to N
    decode-shard engines over mirrored paged pools.

    The MPAI co-processing split as a server: stage 1 (the DPU
    analogue — typically a cheap/int8 precision plan) runs chunked
    paged prefill and samples the first token; stage 2 (the VPU
    analogue) imports the filled blocks into its own pool and decodes.
    Stage 2 may be *sharded*: N identical decode engines, each with its
    own allocator and slots, fed from the single prefill stage.  Every
    handoff crosses the seam in :meth:`PrefillHandoff.to_bytes` wire
    form (versioned + checksummed), so the fan-out behaves identically
    whether the shards share the exporter's process or not.  Importer
    selection is least-loaded per request; seam backpressure is tracked
    per shard (a full shard defers to the next, and only when *every*
    live shard defers does the request park at the seam).  A
    lost/corrupt frame is re-requested exactly-once: prefill replays
    muted (deterministic, bit-identical), so delivered tokens stay
    delivered once.

    Exposes the same ``submit`` / ``step`` / ``flush`` / ``done`` /
    ``stats`` API as the engines, so
    :class:`~repro.serving.executor.EngineExecutor` drives it
    unchanged; per-stage counters (``prefill_tokens`` / ``admit_s`` on
    the prefill engine, decode counters summed over shards) let the
    executor charge each stage to its own pool telemetry, with
    ``imports_by_shard`` splitting the seam traffic per consumer.
    """

    def __init__(self, prefill_engine: ContinuousBatchingEngine,
                 decode_engines: Union[ContinuousBatchingEngine,
                                       Sequence[ContinuousBatchingEngine]]):
        if isinstance(decode_engines, ContinuousBatchingEngine):
            decode_engines = [decode_engines]
        self.decodes: List[ContinuousBatchingEngine] = list(decode_engines)
        assert self.decodes, "need at least one decode shard"
        for eng in self.decodes:
            assert prefill_engine.block_size == eng.block_size, \
                "mirrored pools must share block geometry"
        self.prefill = prefill_engine
        self.max_len = min(e.max_len for e in self.decodes)
        self.prompt_len = self.decodes[0].prompt_len
        self.queue: List[Request] = []
        self._parked: Optional[tuple] = None   # (req, handoff) at the seam
        self.handoff_count = 0
        self._seam_deferrals = 0
        self._on_token: Optional[Callable[[int, int], None]] = None
        self._on_stage = None
        # radiation hardening at the seam: each shard's evictions come
        # back through a *fresh handoff* (its imported KV must carry the
        # prefill engine's bits — replaying prefill under the decode
        # plan would not), so the seam owns every shard's restore queue
        for eng in self.decodes:
            eng.external_restore = True
        self._restore_parked: Optional[tuple] = None  # (shard, req, gen, ho)
        self._lose_handoffs = 0        # armed handoff_loss faults
        self._corrupt_wire = 0         # armed in-transit frame upsets
        self.handoffs_lost = 0
        self.handoffs_replayed = 0
        self._draining: set = set()    # shard indices leaving the rotation
        self.imports_by_shard: Dict[str, int] = {
            f"shard{i}": 0 for i in range(len(self.decodes))}
        self.seam_deferrals_by_shard: Dict[str, int] = {
            f"shard{i}": 0 for i in range(len(self.decodes))}

    # --- back-compat single-shard view --------------------------------
    @property
    def decode(self) -> ContinuousBatchingEngine:
        """The first decode shard (the whole stage when unsharded)."""
        return self.decodes[0]

    @property
    def decode_shards(self) -> int:
        return len(self.decodes)

    # --- token relay: all stages emit through one hook ----------------
    @property
    def on_token(self):
        return self._on_token

    @on_token.setter
    def on_token(self, fn) -> None:
        self._on_token = fn
        self.prefill.on_token = fn         # first token, at the handoff
        for eng in self.decodes:
            eng.on_token = fn              # everything after

    # --- stage relay: engine stage names are disjoint (prefill:
    # admit/prefill_chunk/handoff; decode: import, decode_step and its
    # decode_* phases), and each
    # decode shard tags its spans with its index so the fan-out is
    # visible per consumer -----------------------------------------------
    @property
    def on_stage(self):
        return self._on_stage

    @on_stage.setter
    def on_stage(self, fn) -> None:
        self._on_stage = fn
        self.prefill.on_stage = fn
        for i, eng in enumerate(self.decodes):
            if fn is None:
                eng.on_stage = None
            else:
                def relay(stage, t0, t1, rids, attrs, _i=i, _fn=fn):
                    _fn(stage, t0, t1, rids, {**attrs, "shard": _i})
                eng.on_stage = relay

    # --- mirrored engine API ------------------------------------------
    @property
    def done(self) -> Dict[int, Request]:
        if len(self.decodes) == 1:
            return self.decodes[0].done
        merged: Dict[int, Request] = {}
        for eng in self.decodes:
            merged.update(eng.done)
        return merged

    @property
    def pending(self) -> int:
        return (len(self.queue) + (self._parked is not None)
                + (self._restore_parked is not None)
                + sum(e.pending for e in self.decodes))

    # --- radiation hardening: fault API + counters --------------------
    @property
    def harden(self) -> bool:
        return all(e.harden for e in self.decodes)

    def inject_handoff_loss(self) -> None:
        """Arm one seam SEU: the next handoff payload vanishes between
        gather and import and must be re-requested."""
        self._lose_handoffs += 1

    def inject_handoff_corruption(self) -> None:
        """Arm one in-transit SEU: a byte of the next wire frame flips
        between export and import.  The frame checksum catches it and
        the seam re-requests the handoff exactly-once."""
        self._corrupt_wire += 1

    def arm_bitflip(self, seed: int = 0) -> None:
        # live KV lives in the decode pools (prefill rows free at gather)
        self.decodes[0].arm_bitflip(seed)

    def stall_slot(self, slot: int) -> None:
        self.decodes[0].stall_slot(slot)

    def unstall_slot(self, slot: int) -> None:
        self.decodes[0].unstall_slot(slot)

    def scrub(self, budget: Optional[int] = None) -> int:
        n = self.prefill.scrub(budget)
        for eng in self.decodes:
            n += eng.scrub(budget)
        return n

    @property
    def bitflips_detected(self) -> int:
        return (self.prefill.bitflips_detected
                + sum(e.bitflips_detected for e in self.decodes))

    @property
    def blocks_quarantined(self) -> int:
        return (self.prefill.blocks_quarantined
                + sum(e.blocks_quarantined for e in self.decodes))

    @property
    def watchdog_trips(self) -> int:
        return (self.prefill.watchdog_trips
                + sum(e.watchdog_trips for e in self.decodes))

    @property
    def replays(self) -> int:
        return self.prefill.replays + sum(e.replays for e in self.decodes)

    @property
    def scrubbed_blocks(self) -> int:
        return (self.prefill.scrubbed_blocks
                + sum(e.scrubbed_blocks for e in self.decodes))

    @property
    def occupancy(self) -> float:
        slots = sum(e.max_slots for e in self.decodes)
        busy = sum(e.occupancy * e.max_slots for e in self.decodes)
        return busy / slots

    @property
    def decode_steps(self) -> int:
        return sum(e.decode_steps for e in self.decodes)

    @property
    def decode_tokens(self) -> int:
        return sum(e.decode_tokens for e in self.decodes)

    @property
    def decode_s(self) -> float:
        return sum(e.decode_s for e in self.decodes)

    @property
    def total_tokens(self) -> int:
        return (self.prefill.total_tokens
                + sum(e.total_tokens for e in self.decodes))

    @property
    def prefill_tokens(self) -> int:
        return self.prefill.prefill_tokens

    @property
    def admit_s(self) -> float:
        return self.prefill.admit_s

    @property
    def deferrals(self) -> int:
        return (self.prefill.deferrals
                + sum(e.deferrals for e in self.decodes)
                + self._seam_deferrals)

    @property
    def prefix_hits(self) -> int:
        return (self.prefill.prefix_hits
                + sum(e.prefix_hits for e in self.decodes))

    @property
    def prefix_lookups(self) -> int:
        return (self.prefill.prefix_lookups
                + sum(e.prefix_lookups for e in self.decodes))

    def padded_prompt_len(self, s: int) -> int:
        # the prefill-class engine's chunk grid decides the padded
        # length crossing the seam (its chunks may be wider than the
        # decode engine's bucket — the DPU-analogue is a *wide* engine)
        c = self.prefill.prefill_chunk
        return -(-max(s, self.prefill.prompt_len) // c) * c

    def submit(self, req: Request) -> None:
        _require_prompt(req, "engine")
        padded = self.padded_prompt_len(int(req.prompt.shape[0]))
        budget = min(e.table_width * e.block_size for e in self.decodes)
        assert padded + req.max_new <= budget, \
            (req.rid, req.prompt.shape[0], req.max_new, self.max_len)
        self.queue.append(req)

    # --- shard lifecycle ----------------------------------------------
    def retire_shard(self, idx: int) -> None:
        """Drain decode shard ``idx``: it leaves the import rotation
        immediately but keeps stepping until its in-flight streams
        finish — zero dropped streams, matching pool retirement
        semantics one layer up."""
        if not 0 <= idx < len(self.decodes):
            raise IndexError(f"no decode shard {idx}")
        live = [i for i in range(len(self.decodes))
                if i not in self._draining]
        if live == [idx]:
            raise ValueError("cannot retire the last live decode shard")
        self._draining.add(idx)

    def _import_order(self) -> List[int]:
        """Live shards, least-loaded first (ties broken by index — the
        deterministic routing the bit-identity guarantee relies on)."""
        live = [i for i in range(len(self.decodes))
                if i not in self._draining]
        return sorted(live, key=lambda i: (self.decodes[i].pending, i))

    def _transport(self, handoff: PrefillHandoff) -> PrefillHandoff:
        """Cross the seam: serialize to the wire frame and parse it
        back, exactly what a process/host boundary would do.  An armed
        in-transit upset flips one payload byte; the frame checksum
        turns that into :class:`HandoffCorruptError` before any block
        is pasted."""
        wire = handoff.to_bytes()
        if self._corrupt_wire > 0:
            self._corrupt_wire -= 1
            wire = wire[:-1] + bytes([wire[-1] ^ 0x40])
        return PrefillHandoff.from_bytes(wire)

    def step(self) -> List[Request]:
        """Move work across the handoff seam, then run one decode step
        on every shard.

        Per step: prefill queued requests (stage 1) and import each
        into the least-loaded live decode shard (stage 2) while blocks
        and slots allow; a shard hitting backpressure defers to the
        next, a fully-backed-up stage parks the request without losing
        the other stage's progress, and exactly-once token delivery
        holds across the seam (the first token streams from the prefill
        stage, the importing shard resumes at token index 1)."""
        completed: List[Request] = []
        self._drain_restores()             # recovery before fresh work
        while True:
            if self._parked is None:
                if not self.queue:
                    break
                try:
                    ho = self.prefill.prefill_handoff(self.queue[0])
                except OutOfBlocksError:
                    self._seam_deferrals += 1
                    break
                req = self.queue.pop(0)
                if self._lose_handoffs > 0:
                    # armed seam SEU: the payload vanishes in transit.
                    # Its first token already streamed, so the re-request
                    # is muted — prefill determinism makes the replacement
                    # bit-identical and delivery stays exactly-once.
                    self._lose_handoffs -= 1
                    self.handoffs_lost += 1
                    self.handoffs_replayed += 1
                    self.prefill.mute_rids.add(req.rid)
                    self.queue.insert(0, req)
                    continue
                try:
                    ho = self._transport(ho)
                except HandoffCorruptError:
                    # frame upset caught by the wire checksum: same
                    # exactly-once re-request contract as a loss
                    self.handoffs_replayed += 1
                    self.prefill.mute_rids.add(req.rid)
                    self.queue.insert(0, req)
                    continue
                self._parked = (req, ho)
            req, ho = self._parked
            placed = corrupt = False
            done = None
            for si in self._import_order():
                try:
                    done = self.decodes[si].import_prefill(req, ho)
                except OutOfBlocksError:
                    self.seam_deferrals_by_shard[f"shard{si}"] += 1
                    continue               # next-least-loaded shard
                except HandoffCorruptError:
                    # the payload itself is bad — no other shard can
                    # import it; discard and re-request
                    corrupt = True
                    break
                placed = True
                break
            if corrupt:
                self._parked = None
                self.handoffs_replayed += 1
                self.prefill.mute_rids.add(req.rid)
                self.queue.insert(0, req)
                continue
            if not placed:
                self._seam_deferrals += 1  # every live shard deferred
                break
            self._parked = None
            self.handoff_count += 1
            self.imports_by_shard[f"shard{si}"] += 1
            if done is not None:
                completed.append(done)
        for eng in self.decodes:           # draining shards finish too
            completed += eng.step()
        return completed

    def _drain_restores(self) -> None:
        """Replay decode-side evictions (watchdog trips, quarantined
        blocks) across the seam: re-run the prefill handoff (muted — the
        delivered prefix stays delivered exactly once), import it back
        into a healthy slot *on the same shard*, and replay the recorded
        tokens.  Seam backpressure holds: a restore that cannot place
        yet parks with its handoff and retries next step without
        recomputing prefill."""
        while (self._restore_parked is not None
               or any(e._restore_queue for e in self.decodes)):
            if self._restore_parked is None:
                si = next(i for i, e in enumerate(self.decodes)
                          if e._restore_queue)
                eng = self.decodes[si]
                req, gen = eng._restore_queue[0]
                self.prefill.mute_rids.add(req.rid)
                try:
                    ho = self.prefill.prefill_handoff(req)
                except OutOfBlocksError:
                    self.prefill.mute_rids.discard(req.rid)
                    self._seam_deferrals += 1
                    return
                eng._restore_queue.pop(0)
                try:
                    ho = self._transport(ho)
                except HandoffCorruptError:
                    self.handoffs_replayed += 1
                    eng._restore_queue.insert(0, (req, gen))
                    continue
                self._restore_parked = (si, req, gen, ho)
            si, req, gen, ho = self._restore_parked
            eng = self.decodes[si]
            try:
                eng.restore_import(req, gen, ho)
            except HandoffCorruptError:
                self._restore_parked = None
                self.handoffs_replayed += 1
                eng._restore_queue.insert(0, (req, gen))
                continue
            except OutOfBlocksError:
                self._seam_deferrals += 1
                return
            self._restore_parked = None
            eng.replays += 1

    def flush(self) -> List[Request]:
        """Blocking form: run until at least one request completes."""
        if not self.pending:
            return []
        while True:
            done = self.step()
            if done:
                return done

    def stats(self) -> Dict[str, float]:
        shard_stats = [e.stats() for e in self.decodes]
        p = self.prefill.stats()
        d = dict(shard_stats[0])
        for s in shard_stats[1:]:
            for key, val in s.items():
                d[key] += val
        # mean occupancy does not sum — recompute weighted by steps
        steps = sum(s["decode_steps"] for s in shard_stats)
        d["mean_occupancy"] = (
            sum(s["mean_occupancy"] * s["decode_steps"]
                for s in shard_stats) / steps if steps
            else shard_stats[0]["mean_occupancy"])
        d["total_tokens"] = self.total_tokens
        d["prefill_tokens"] = p["prefill_tokens"]
        d["admit_s"] = p["admit_s"]            # prefill stage wall time
        d["shared_block_hits"] += p["shared_block_hits"]
        d["shared_block_lookups"] += p["shared_block_lookups"]
        d["deferrals"] = self.deferrals
        d["handoffs"] = self.handoff_count
        for key in ("bitflips_detected", "blocks_quarantined",
                    "watchdog_trips", "replays", "scrubbed_blocks"):
            d[key] = getattr(self, key)    # prefill + shard aggregate
        d["handoffs_lost"] = self.handoffs_lost
        d["handoffs_replayed"] = self.handoffs_replayed
        d["decode_shards"] = len(self.decodes)
        d["imports_by_shard"] = dict(self.imports_by_shard)
        d["seam_deferrals_by_shard"] = dict(self.seam_deferrals_by_shard)
        return d

    def reset_stats(self) -> None:
        self.prefill.reset_stats()
        for eng in self.decodes:
            eng.reset_stats()
        self._seam_deferrals = 0
        self.handoff_count = 0
        self.handoffs_lost = 0
        self.handoffs_replayed = 0
        self.imports_by_shard = {
            f"shard{i}": 0 for i in range(len(self.decodes))}
        self.seam_deferrals_by_shard = {
            f"shard{i}": 0 for i in range(len(self.decodes))}
