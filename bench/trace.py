"""Reduction of a JAX profiler trace to device busy time and kernel time.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run on that chip, named by its HLO instruction.
Busy time is the union of those intervals (overlaps count once); idle
share is one minus busy over the traced window.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# Control-flow operations hold other operations of the same line: they
# count for busy time, not as an operation of their own.
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def short_name(hlo: str) -> str:
    """``%paged_gqa_decode_attn.7 = bf16[...] custom-call(...)`` ->
    ``paged_gqa_decode_attn.7``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered_ns(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals: Iterable[Interval]) -> List[Interval]:
    """Idle stretches between the busy intervals, longest first."""
    u = union(intervals)
    g = [(a[1], b[0]) for a, b in zip(u, u[1:])]
    return sorted(g, key=lambda x: x[0] - x[1])


def idle_frac(busy_s: float, window_s: float) -> float:
    return 1.0 - busy_s / window_s


def _stats(ev) -> Dict[str, object]:
    return {str(k): v for k, v in ev.stats}


def load(path: str) -> dict:
    """Events of a ``.xplane.pb``: ``{"devices": {plane: [(name, start_ns,
    end_ns, text)]}, "host": [(name, start_ns, end_ns)]}``. ``name`` is
    the HLO instruction's name; ``text`` joins the whole event name (the
    instruction as the compiler printed it) with its string stats, so a
    kernel can be found by any label attached to the operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = " ".join([ev.name] + [
                        str(v) for v in _stats(ev).values()
                        if isinstance(v, str)])
                    evs.append((short_name(ev.name), float(ev.start_ns),
                                float(ev.end_ns), text))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.end_ns)))
    return {"devices": devices, "host": host}


def kernel_seconds(device_events: Sequence, pattern: str) -> float:
    """Summed device time of operations whose name or labels match
    ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    return sum(e - s for _, s, e, text in device_events
               if rx.search(text)) / 1e9


def _host_label(host: Sequence, gap: Interval) -> str:
    """The host event that overlaps ``gap`` the longest, by name."""
    best, best_ov = "no host event", 0.0
    for name, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce(events: dict, window_s: float, kernels: Dict[str, str],
           top: int = 10) -> dict:
    """Busy time averaged over the device planes, per-kernel time summed
    over them, the operations that took most time, and the longest idle
    gaps with the host event that covered most of each."""
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy = {p: covered_ns((s, e) for _, s, e, _ in evs) / 1e9
            for p, evs in devs.items()}
    ops: Dict[str, float] = {}
    for evs in devs.values():
        for name, s, e, _ in evs:
            if not CONTAINER.match(name):
                ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
    first = sorted(devs)[0]
    idle = gaps((s, e) for _, s, e, _ in devs[first])[:top]
    n = len(devs)
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / n,
        "chips": n,
        "kernel_s": {k: sum(kernel_seconds(evs, rx) for evs in devs.values())
                     / n for k, rx in kernels.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_host_label(events["host"], g), (g[1] - g[0]) / 1e9]
                      for g in idle],
    }
