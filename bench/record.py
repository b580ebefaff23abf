"""What one run recorded, and the arithmetic the metric readers share.

Every time is a ``time.perf_counter()`` reading on the client's side.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the client saw it."""

    idx: int
    sched: float  # when it was due to be sent
    prompt_len: int
    max_new: int
    prompt: Optional[np.ndarray] = None  # token ids sent
    sent: Optional[float] = None  # when submit() returned
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None  # how it failed, if the engine failed it
    done: bool = False  # the stream ended with every token
    cancelled: bool = False  # cut by the run at the drain deadline

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may use."""

    reqs: List[Req]
    t_open: float
    t_close: float
    deadline: float  # the drain deadline after the close
    setup_s: float
    model: dict  # published config keys
    batch_slots: int
    tp: int  # chips that share each layer
    device_kind: str
    delta: Dict[str, float]  # ServeMetrics change over the window
    trace: Optional[dict] = None  # bench.trace.reduce() of the traced part
    t_trace: Optional[tuple] = None  # (start, stop) of the traced part

    @property
    def peaks(self) -> dict:
        """The device's row of ``bench/peaks.json`` (unknown kind: raises)."""
        from bench import work

        return work.peaks(self.device_kind)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self) -> List[Req]:
        """Requests due to be sent inside the window."""
        return [r for r in self.reqs if self.t_open <= r.sched < self.t_close]

    def ttft(self, r: Req) -> float:
        """First token minus the due time; a request that failed, or had
        no token by the drain deadline, is no faster than the deadline."""
        if r.error is not None or r.first is None:
            return max(self.deadline, r.first or 0.0) - r.sched
        return r.first - r.sched


def p95(xs) -> Optional[float]:
    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, 95)) if xs.size else None
