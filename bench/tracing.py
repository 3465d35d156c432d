"""Capture a profiler trace of the window and reduce it to numbers.

The harness wraps the window in a ``bench.window`` span and each loop stage
in a ``bench.<stage>`` span (``jax.profiler.TraceAnnotation``). The device
planes (``/device:...``) carry one event per operation on their ``XLA Ops``
line. From those this module computes, inside the window span:

- ``busy_s``: the union of the operation intervals, averaged over the
  devices that ran anything;
- the device self seconds of each operation (its time less that of the
  operations nested in it, such as the body of a loop), keyed by its HLO
  instruction name;
- the idle gaps between operations, each labelled with the innermost
  ``bench.*`` host span that was open at the gap's midpoint.

The reduction reads duck-typed planes (``name``, ``lines``; a line has
``name`` and ``events``; an event ``name``, ``start_ns``, ``duration_ns``,
``stats``), so a test can hand it a small recorded trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices used
    devices: int
    op_seconds: Dict[str, float]       # self time, summed over devices
    gaps: List[Tuple[float, str]]      # (seconds, host span), longest first

    def seconds_named(self, name: str) -> float:
        """Self seconds of the ops whose HLO instruction is ``name`` or
        ``name.<n>``: a Pallas kernel's, whose ``pallas_call`` name the
        compiler gives its custom call."""
        own = re.compile(re.escape(name) + r"(\.\d+)?")
        return sum(s for k, s in self.op_seconds.items()
                   if own.fullmatch(k))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, s] for k, s in ops],
                "idle_gaps": [[label, s] for s, label in self.gaps[:n]]}


def capture_dir_trace(log_dir: str):
    """The ``ProfileData`` of the newest trace written under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def op_key(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction name that TPU traces put first in an operation's text."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(events) -> List[Tuple[str, int]]:
    """(event, self ns) for events of one line, where an event that spans
    others (a loop around its body) keeps only the time they leave."""
    out, stack = [], []              # stack: [end, index into out]
    for s, e, ev in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:          # nested in the top
            out[stack[-1][1]][1] -= e - s
        out.append([ev, e - s])
        stack.append((e, len(out) - 1))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def reduce(planes: Iterable) -> Summary:
    planes = list(planes)
    spans = []                                   # (start, end, name)
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    w0, w1 = windows[0]
    spans = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    spans.sort()
    starts = [s for s, _, _ in spans]

    def label(t: float) -> str:
        best = None
        for s, e, n in spans[:bisect.bisect_right(starts, t)]:
            if e >= t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "no span"

    op_ns: Dict[str, int] = {}
    busy_ns, devices, gaps = 0, 0, []
    for p in planes:
        if not p.name.startswith("/device:"):
            continue
        clipped = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    clipped.append((s, e, ev))
        for ev, ns in _self_times(clipped):
            key = op_key(ev.name)
            op_ns[key] = op_ns.get(key, 0) + ns
        intervals = [(s, e) for s, e, _ in clipped]
        if not intervals:
            continue
        devices += 1
        merged = _union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, label((a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_ns * 1e-9 / max(devices, 1), devices=devices,
                   op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
                   gaps=gaps)
