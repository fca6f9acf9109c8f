"""One run of one cell: set up, drive the window on the wall clock,
reduce, check, report.

The served path is the program's front door: ``FleetSpec.build`` ->
``ServingClient`` -> router -> ``ContinuousBatchingEngine`` -> the Pallas
paged kernel.  A single-threaded loop slaves the client's clock to the
wall clock, submits what is due and pumps the fleet.  Every token is
stamped with ``time.perf_counter()`` as the pool's executor relays it.
The program's pool runs a routed batch to its end inside one ``pump``;
the first token stamped after the window closes ends the run there.
"""
from __future__ import annotations

import gc
import math
import os
import sys
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Dict, List, Optional

from bench import check, mix, stats
from bench.cells import Cell, CellError, load_cell, load_json
from bench.record import Record, Req
from bench.trace import Profiler, breakdown

# profile this stretch of the traced run's window, as shares of it
PROFILE_AT, PROFILE_FOR = 0.4, 0.15
COUNTERS = ("decode_steps", "occupancy_sum", "decode_tokens",
            "prefill_tokens", "admit_s", "decode_s", "deferrals")


class WindowClosed(Exception):
    """Raised from the token relay at the first token past the window."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def process_start() -> float:
    """Wall time (``time.time``) at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def require_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found {devs[0].platform!r}, not a "
                         f"TPU; there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``, a fixed path.  Every program is
    cached, however fast it compiled, so that set-up repeats."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Recorder:
    """Wraps the pool executor's public token relay and the engine's
    stage hook; stamps what happens while the window is open."""

    def __init__(self, executor, engine, seed: int, annotate: bool):
        self.engine, self.seed = engine, seed
        self.relay = executor.on_token
        executor.on_token = self.on_token
        self.requests: Dict[int, Req] = {}
        self.stages: List = []
        self.open = False
        self.close = math.inf
        self.profiler: Optional[Profiler] = None
        self.counters_end: Optional[Dict] = None
        self.annotate = annotate
        if annotate:
            engine.on_stage = self.on_stage

    def counters(self) -> Dict:
        return {k: getattr(self.engine, k) for k in COUNTERS}

    def span(self, name: str):
        if not self.annotate:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def on_token(self, rid: int, tok: int, step: int) -> None:
        now = time.perf_counter()
        if self.open:
            if now > self.close:
                self.counters_end = self.counters()
                self.open = False
                raise WindowClosed
            with self.span("bench.token"):
                req = self.requests.get(rid)
                if req is not None:
                    req.stamps.append(now)
                    req.tokens.append(int(tok))
                if self.profiler is not None:
                    self.profiler.poll(now)
        self.relay(rid, tok, step)

    def on_stage(self, stage, t0, t1, rids, attrs) -> None:
        if self.open:
            self.stages.append((stage, t0, t1, list(rids), dict(attrs)))


def build(cell: Cell, params, mcfg, pool_overrides: Optional[Dict]):
    from repro.runtime.serve import ContinuousBatchingEngine
    from repro.serving import FleetSpec, PoolSpec
    pool = dict(cell.traffic["pool"], **(pool_overrides or {}))
    spec = FleetSpec(pools=[PoolSpec("lm", ("tpu_v5e_bf16",),
                                     backend="engine", **pool)],
                     workload="transformer", seq_len=pool["prompt_len"])
    client = spec.build(model=(mcfg, params), warm=False)
    engine = client.engines["lm"]
    if not isinstance(engine, ContinuousBatchingEngine):
        raise CellError(f"pool built {type(engine).__name__}, not the "
                        f"continuous-batching engine")
    return client, engine, client.router.pools["lm"].executor


def warm_up(client, cell: Cell, vocab: int) -> None:
    """Compile the programs this cell's traffic uses, and no others:
    greedy bucket admission and decode, and chunked prefill where the
    mix has prompts over the bucket."""
    pool = cell.traffic["pool"]
    lens = [min(8, pool["prompt_len"])]
    if cell.traffic["prompt"]["max"] > pool["prompt_len"]:
        lens.append(pool["prompt_len"] + 1)
    for i, n in enumerate(lens):
        client.submit(mix.prompt_tokens(0, 10 ** 6 + i, n, vocab),
                      max_new=2, rid=-1 - i)
    client.drain()


def drive(client, rec: Recorder, cell: Cell, items: List[mix.Item],
          t0: float, close: float, padded) -> Dict:
    """The window.  Open loop: each request is submitted when due.
    Closed loop: each client sends its next request when its last one
    completed.  Returns the handles by request id."""
    traffic = cell.traffic
    vocab = cell.config["vocab_size"]
    seed = rec.seed
    v0 = client.now
    open_loop = traffic["loop"] == "open"
    clients = 0 if open_loop else int(traffic["clients"])
    busy: Dict[int, object] = {}          # client -> handle
    handles: Dict[int, object] = {}
    nxt = 0

    def submit(item: mix.Item, due: float, now: float):
        req = Req(item.index, item.prompt_len, padded(item.prompt_len),
                  item.max_new, due, sent=now)
        rec.requests[item.index] = req
        with rec.span("bench.submit"):
            h = client.submit(mix.prompt_tokens(seed, item.index,
                                                item.prompt_len, vocab),
                              max_new=item.max_new, rid=item.index)
        req.admitted = h.admitted
        handles[item.index] = h
        return h

    while True:
        now = time.perf_counter()
        if now >= close:
            break
        client.advance(dt=max(0.0, v0 + (now - t0) - client.now))
        if rec.profiler is not None:
            rec.profiler.poll(now)
        if open_loop:
            while nxt < len(items) and t0 + items[nxt].due_s <= now:
                submit(items[nxt], t0 + items[nxt].due_s, now)
                nxt += 1
        else:
            for c in range(clients):
                h = busy.get(c)
                if h is None or h.done:
                    if nxt == len(items):
                        items = mix.stream(traffic, 2 * len(items))
                    busy[c] = submit(items[nxt], now, now)
                    nxt += 1
        try:
            with rec.span("bench.pump"):
                client.pump()
        except WindowClosed:
            break
        if open_loop and not client.outstanding:
            wait = (t0 + items[nxt].due_s if nxt < len(items) else close)
            wait = min(wait, close) - time.perf_counter()
            if wait > 0:
                with rec.span("bench.wait_arrival"):
                    time.sleep(wait)
    for rid, h in handles.items():
        rec.requests[rid].dropped = h.dropped
    # due in the window but never sent, because a batch held the loop:
    # they are attempted, and wait until the close
    while open_loop and nxt < len(items) and t0 + items[nxt].due_s <= close:
        it = items[nxt]
        rec.requests[it.index] = Req(it.index, it.prompt_len,
                                     padded(it.prompt_len), it.max_new,
                                     t0 + it.due_s)
        nxt += 1
    return handles


def device_info(devs, trace_summary=None) -> Dict:
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)}
    if trace_summary is not None:
        info["busy_s"] = trace_summary.busy_s
        info["window_s"] = trace_summary.window_s
    return info


def _itl_ms(q: float):
    def reduce(rec: Record, setup_s: float) -> Optional[float]:
        """``q``-th percentile of every gap between consecutive tokens
        of a request, both stamps in the window, over all requests."""
        gaps = [g for r in rec.requests.values()
                for g in stats.gaps_within(r.stamps, rec.t0, rec.close)]
        return None if not gaps else 1e3 * stats.percentile(gaps, q)
    return reduce


def _ttft_p50_s(rec: Record, setup_s: float) -> Optional[float]:
    """Median time to first token over every request due in the window,
    a request with no first token by the close entering with its wait
    so far."""
    due = rec.attempted()
    return stats.percentile(stats.censored_waits(
        [r.due for r in due], [r.first for r in due], rec.close), 50)


def _output_tokens_per_s(rec: Record, setup_s: float) -> float:
    return sum(stats.count_within(r.stamps, rec.t0, rec.close)
               for r in rec.requests.values()) / rec.seconds


END_TO_END = {"setup_s": lambda rec, setup_s: setup_s,
              "itl_p99_ms": _itl_ms(99), "itl_p95_ms": _itl_ms(95),
              "ttft_p50_s": _ttft_p50_s,
              "output_tokens_per_s": _output_tokens_per_s}


def end_to_end(cell: Cell, rec: Record, setup_s: float) -> Dict:
    out = {}
    for m in cell.end_to_end:
        reduce = END_TO_END.get(m.name)
        if reduce is None:
            raise CellError(f"no end-to-end reduction for {m.name!r}")
        v = reduce(rec, setup_s)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, require_chip: bool = True, rate: Optional[float] = None,
        pool_overrides: Optional[Dict] = None,
        keep_trace: Optional[str] = None,
        int8_reference: bool = False) -> Dict:
    """Run ``workload`` once; returns the result line as a dict."""
    started = process_start()
    root = Path(root)
    cell = load_cell(root, workload)
    if rate is not None:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
    devs = require_devices(cell.chips, require_chip)
    peaks_file = load_json(root / "bench" / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks_file["devices"]:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json")
    peaks = peaks_file["devices"][kind]
    log(f"compile cache at {enable_compile_cache(root)}")

    import jax
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T

    from bench import weights
    mcfg = ModelConfig(**cell.reference.program_kwargs(cell.config))
    layout = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0),
                                                 mcfg))
    params = weights.make(layout, cell.config["dtype"], seed)
    client, engine, executor = build(cell, params, mcfg, pool_overrides)
    rec = Recorder(executor, engine, seed, annotate=trace)
    warm_up(client, cell, mcfg.vocab_size)
    items = mix.stream(cell.traffic,
                       mix.stream_length(cell.traffic, seconds))
    padded = engine.padded_prompt_len
    log(f"device {kind} x{len(devs)}; model {mcfg.name}: "
        f"{mcfg.num_layers} layers, d_model {mcfg.d_model}; pool "
        f"{engine.max_slots} slots, max_len {engine.max_len}, "
        f"{engine.alloc.num_blocks} blocks of {engine.block_size}")

    compiles = []

    def on_compile(event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            compiles.append(secs)
    t0 = time.perf_counter()
    setup_s = time.time() - started
    close = t0 + seconds
    if trace:
        rec.profiler = Profiler(t0 + PROFILE_AT * seconds,
                                t0 + (PROFILE_AT + PROFILE_FOR) * seconds)
    counters0 = rec.counters()
    rec.close, rec.open = close, True
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        handles = drive(client, rec, cell, items, t0, close, padded)
    finally:
        rec.open = False
        jax.monitoring.unregister_event_duration_listener(on_compile)
    counters1 = rec.counters_end or rec.counters()
    summary = rec.profiler.summary(keep_trace) if rec.profiler else None
    device = device_info(devs, summary)
    record = Record(t0, close, rec.requests, engine.max_slots, cell.config,
                    peaks, rec.stages, (counters0, counters1), summary,
                    device["memory_peak_bytes"])
    late = [r.sent - r.due for r in record.attempted() if r.sent]
    log(f"window {seconds}s: {len(record.attempted())} requests due, "
        f"{len(record.attempted()) - len(late)} of them never sent, "
        f"{sum(len(r.stamps) for r in rec.requests.values())} tokens; "
        f"generator lateness p50 {stats.percentile(late, 50)} s, max "
        f"{max(late, default=None)} s (the pool runs each routed batch "
        f"to its end inside one pump, so arrivals wait there, not in a "
        f"starved generator); compilations in the window: "
        f"{len(compiles)}")

    due = record.attempted()
    waits = stats.censored_waits([r.due for r in due],
                                 [r.first for r in due], close)
    early = [w for w, r in zip(waits, due) if r.due < t0 + seconds / 2]
    late_half = [w for w, r in zip(waits, due) if r.due >= t0 + seconds / 2]
    log(f"time to first token over all {len(due)} requests due (censored "
        f"at close): p50 {stats.percentile(waits, 50)} s, p90 "
        f"{stats.percentile(waits, 90)} s; {sum(r.first is None for r in due)}"
        f" without a first token at close; median "
        f"{stats.percentile(early, 50)} s in the first half, "
        f"{stats.percentile(late_half, 50)} s in the second")
    log(f"mean time to first token over the same requests: "
        f"{sum(waits) / len(waits) if waits else None} s")
    gaps = sorted((b - a, a - t0) for r in rec.requests.values()
                  for a, b in zip(r.stamps, r.stamps[1:])
                  if t0 <= a and b <= close)
    if gaps:
        mid = gaps[len(gaps) // 2][0]
        log(f"{len(gaps)} gaps between tokens, median {1e3 * mid} ms, "
            f"{sum(g > 2 * mid for g, _ in gaps)} over twice it; longest "
            f"(ms at s into the window): " + ", ".join(
                f"{1e3 * g:.1f}@{at:.1f}" for g, at in gaps[-8:]))
    log("engine counters over the window: " + ", ".join(
        f"{k} {counters1[k] - counters0[k]}" for k in COUNTERS))
    streams = check.streams(rec.requests, handles, engine)
    # the program's state goes before the reference runs, so the
    # reference neither sets the peak nor runs short of memory
    del client, engine, executor, rec
    gc.collect()
    numbers = check.compare(cell, params, record, streams, seed,
                            control=int8_reference)
    del params
    correct = all(n["value"] is not None and n["value"] <= n["limit"]
                  for n in numbers.values())

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = m.reader(record)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
    else:
        metrics = end_to_end(cell, record, setup_s)
    attempted = record.attempted()
    result = {"correct": correct, "attempted": len(attempted),
              "failed": sum(r.failed for r in attempted),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = breakdown(summary)
    result["checks"] = numbers
    for name, n in numbers.items():
        log(f"check {name}={n['value']} limit={n['limit']}")
    return result
