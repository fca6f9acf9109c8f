"""What one run leaves for the metric readers: the window, every
request's stamps, the engine's stage events and counters, the trace."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class Req:
    rid: int
    prompt_len: int               # tokens the caller sent
    padded_len: int               # context the program admitted it at
    max_new: int
    due: float                    # open loop: when it was due; closed: sent
    sent: Optional[float] = None
    admitted: bool = True
    dropped: bool = False
    stamps: List[float] = field(default_factory=list)   # one per token
    tokens: List[int] = field(default_factory=list)

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None

    @property
    def failed(self) -> bool:
        return not self.admitted or self.dropped


@dataclass
class Record:
    t0: float                     # window, perf_counter seconds
    close: float
    requests: Dict[int, Req]
    max_slots: int
    config: Dict                  # the configuration file
    peaks: Dict                   # this device's row of bench/peaks.json
    stages: List[Tuple[str, float, float, List[int], Dict]] = \
        field(default_factory=list)
    counters: Tuple[Dict, Dict] = ({}, {})   # engine, window start / end
    trace: Optional[object] = None           # bench.trace.Summary
    memory_peak_bytes: Optional[int] = None

    @property
    def seconds(self) -> float:
        return self.close - self.t0

    def attempted(self) -> List[Req]:
        """Requests due (open loop) or sent (closed loop) in the window."""
        return [r for r in self.requests.values()
                if self.t0 <= r.due <= self.close]

    def stages_in(self, names, t0: float, t1: float):
        return [s for s in self.stages
                if s[0] in names and t0 <= s[1] and s[2] <= t1]
