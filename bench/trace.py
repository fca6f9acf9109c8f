"""Profile a stretch of the window and reduce its trace to numbers.

The profiler writes an ``.xplane.pb``; it is read back in-process with
``jax.profiler.ProfileData`` and reduced here, so every PR reads the
trace the same way.  Device operations are the events of each TPU
plane's ``XLA Ops`` line.  The trace's clock is tied to the host's
``time.perf_counter`` by the ``bench.sync`` annotation made right after
the profiler starts, so device operations, the benchmark's host
annotations and the engine's stage stamps all sit on one clock.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench.stats import union_length

SYNC = "bench.sync"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class Summary:
    start: float                      # traced window, perf_counter s
    end: float
    ops: List[Tuple[str, float, float, int]]   # name, start, end, device
    host: List[Tuple[str, float, float]]       # main-thread host events
    busy_s: float = 0.0               # mean over the devices used
    devices: int = 0

    @property
    def window_s(self) -> float:
        return self.end - self.start


def op_name(text: str) -> str:
    """An op's base name from its trace event: the HLO instruction's
    name without ``%`` and without its ``.N`` suffix, e.g.
    ``paged_attention_pallas`` from ``%paged_attention_pallas.6 = ...``."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def reduce(pd, sync_perf: float, end_perf: float) -> Optional[Summary]:
    """``pd``: ``ProfileData``.  None when the trace has no sync mark."""
    sync_ns, host_line = None, None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    sync_ns, host_line = ev.start_ns, line
                    break
            if sync_ns is not None:
                break
        if sync_ns is not None:
            break
    if sync_ns is None:
        return None

    def perf(ns: float) -> float:
        return sync_perf + (ns - sync_ns) * 1e-9

    ops = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):].split()[0]
                  .split(":")[0] or 0)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = perf(ev.start_ns), perf(ev.start_ns + ev.duration_ns)
                if b > sync_perf and a < end_perf:
                    ops.append((op_name(ev.name), a, b, dev))
    host = []
    for ev in host_line.events:
        a, b = perf(ev.start_ns), perf(ev.start_ns + ev.duration_ns)
        if b > sync_perf and a < end_perf:
            host.append((ev.name, a, b))
    s = Summary(sync_perf, end_perf, ops, host)
    by_dev: Dict[int, list] = {}
    for _, a, b, dev in ops:
        by_dev.setdefault(dev, []).append((max(a, sync_perf),
                                           min(b, end_perf)))
    s.devices = len(by_dev)
    if by_dev:
        s.busy_s = (sum(union_length(v) for v in by_dev.values())
                    / len(by_dev))
    return s


def idle_gaps(s: Summary, device: int = 0) -> List[Tuple[float, float]]:
    """Stretches of the traced window in which ``device`` ran nothing."""
    spans = sorted((a, b) for _, a, b, d in s.ops if d == device)
    gaps, t = [], s.start
    for a, b in spans:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if s.end > t:
        gaps.append((t, s.end))
    return gaps


def host_activity(s: Summary, a: float, b: float) -> str:
    """The innermost main-thread host event covering the gap's middle."""
    mid = (a + b) / 2
    best = None
    for name, x, y in s.host:
        if x <= mid <= y and (best is None or y - x < best[1]):
            best = (name, y - x)
    return best[0] if best else "untraced host"


def leaves(ops):
    """Ops that contain no other op of their device: a ``while`` over
    the layers spans its body's ops, which are counted themselves."""
    out = []
    for dev in {d for *_, d in ops}:
        evs = sorted((o for o in ops if o[3] == dev),
                     key=lambda o: (o[1], -o[2]))
        for i, o in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt[1] >= o[2] or nxt[2] > o[2]:
                out.append(o)
    return out


def breakdown(s: Summary, top: int = 10) -> Dict:
    total: Dict[str, float] = {}
    for name, a, b, _ in leaves(s.ops):
        total[name] = total.get(name, 0.0) + (b - a)
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    dev = min((d for *_, d in s.ops), default=0)
    gaps = sorted(idle_gaps(s, dev), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t / max(s.devices, 1)] for n, t in ops],
            "idle_gaps": [[host_activity(s, a, b), b - a] for a, b in gaps]}


class Profiler:
    """Start the profiler at ``start`` and stop it at ``stop``
    (perf_counter seconds), whichever call first sees the time."""

    def __init__(self, start: float, stop: float):
        self.at, self.until = start, stop
        self.dir: Optional[str] = None
        self.sync = self.end = None
        self.done = False

    def poll(self, now: float) -> None:
        if self.done:
            return
        if self.dir is None and now >= self.at:
            self.start()
        elif self.dir is not None and now >= self.until:
            self.stop()

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # tracing every Python call
        opts.enable_hlo_proto = False     # would slow the host it profiles
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC):
            self.sync = time.perf_counter()

    def stop(self) -> None:
        import jax
        if self.dir is None or self.done:
            return
        self.end = time.perf_counter()
        jax.profiler.stop_trace()
        self.done = True

    def summary(self, keep: Optional[str] = None) -> Optional[Summary]:
        """Reduce the trace and delete it (after copying it to ``keep``)."""
        if self.dir is None:
            return None
        self.stop()
        from jax.profiler import ProfileData
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(files[0], keep)
            return reduce(ProfileData.from_file(files[0]), self.sync,
                          self.end)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
