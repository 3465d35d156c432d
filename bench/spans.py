"""The program's own spans and scopes in a profiler trace of the window.

The served step marks its parts on the profiler's host plane as
``serve.*`` spans (``repro/serve/trace.py``: ``serve.step`` around each
engine step, and inside it ``serve.schedule``, ``.prepare``,
``.dispatch``, ``.wait``, ``.repair``, ``.commit``), on the clock the
device ops of the same trace use. From the planes
:func:`bench.tracing.reduce` reads, :func:`reduce` gives:

- the ``serve.*`` spans in the window, each with its arguments and its
  depth in the nesting of spans by interval;
- idle by span: each device-idle interval of the window split over the
  self time of the innermost ``serve.*`` span open then, by interval
  intersection; idle time under no such span goes to ``outside``;
- the device tail: the time from the last device op to the window's close.

The step program's ops carry its ``jax.named_scope`` names (and the paged
kernel's ``pallas_call`` name) in their ``op_name`` metadata, e.g.
``jit(_step_fused_fn)/while/body/closed_call/checkpoint/kv_append/scatter``.
A TPU trace read through ``jax.profiler.ProfileData`` does not carry it:
an ``XLA Ops`` event's stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and its name is the
instruction's HLO text without metadata. The compiled program's text has
both, under the same instruction names as the trace: :func:`op_scopes`
maps each name to the components of its ``op_name`` path, and
:func:`scope_seconds` sums a ``tracing.Summary``'s op self times under the
innermost component of each path that is one of the names its caller
gives. A program scope is read by the metric file that names it
(``bench/metrics/<name>.py``): this module knows no scope's name, so a new
``jax.named_scope`` in the program is measured by a new metric file alone.
A scope is matched as a whole component of the ``op_name`` path, never as
a substring of an op's text, which names operands such as
``%kv_append.12``.

:func:`summarize` builds the whole of a traced run's reading in one
place: the :class:`bench.tracing.Summary` of the planes with the spans and
the ``op_name`` paths beside it (a :class:`Trace`), where
``engine.host_gap_ms`` and the scope readers such as ``model.kv_write_ms``
read them.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from bench import tracing

PREFIX = "serve."
STEP = "serve.step"
OUTSIDE = "outside"
UNSCOPED = "unscoped"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: dict
    depth: int                         # 0: no serve.* span encloses it


@dataclasses.dataclass
class Spans:
    spans: List[Span]                  # in the window, by start
    idle_s: Dict[str, float]           # span name or OUTSIDE -> seconds
    longest_gap: Tuple[float, str]     # (seconds, span with most of it)
    device_tail_s: float               # last device op to window close

    @property
    def steps(self) -> List[Span]:
        return [s for s in self.spans if s.name == STEP]


def _window(planes) -> Tuple[float, float]:
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == tracing.WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"the trace has no {tracing.WINDOW_SPAN} span")


def program_spans(planes, w0: float, w1: float) -> List[Span]:
    """The ``serve.*`` spans that overlap ``w0..w1``, nested by interval."""
    raw = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e > w0 and s < w1:
                        raw.append((s, e, ev.name, dict(ev.stats)))
    out, open_ends = [], []
    for s, e, name, args in sorted(raw, key=lambda t: (t[0], -t[1])):
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        out.append(Span(name, s, e, args, depth=len(open_ends)))
        open_ends.append(e)
    return out


def innermost(spans: List[Span], w0: float, w1: float
              ) -> List[Tuple[float, float, str]]:
    """``w0..w1`` cut into pieces, each under the innermost span open
    then (``OUTSIDE`` under none): the spans' self time on a timeline."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = w0

    def upto(x: float) -> None:
        nonlocal t
        x = min(max(x, w0), w1)
        if x > t:
            pieces.append((t, x, stack[-1].name if stack else OUTSIDE))
            t = x

    for sp in spans:
        while stack and stack[-1].end_ns <= sp.start_ns:
            upto(stack[-1].end_ns)
            stack.pop()
        upto(sp.start_ns)
        stack.append(sp)
    while stack:
        upto(stack[-1].end_ns)
        stack.pop()
    upto(w1)
    return pieces


def _split(gaps, pieces) -> List[Dict[str, float]]:
    """Each gap ``(a, b)`` as ns per piece label, by intersection."""
    out, i = [], 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        share: Dict[str, float] = {}
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, label = pieces[j]
            ns = min(b, e) - max(a, s)
            if ns > 0:
                share[label] = share.get(label, 0.0) + ns
            j += 1
        out.append(share)
    return out


def reduce(planes) -> Spans:
    planes = list(planes)
    w0, w1 = _window(planes)
    spans = program_spans(planes, w0, w1)
    pieces = innermost(spans, w0, w1)
    idle_ns: Dict[str, float] = {}
    longest = (0.0, OUTSIDE)
    devices, last_end = 0, w0
    for p in planes:
        if not p.name.startswith("/device:"):
            continue
        ops = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
               for line in p.lines if line.name == tracing.OPS_LINE
               for ev in line.events]
        ops = [(s, e) for s, e in ops if e > s]
        if not ops:
            continue
        devices += 1
        merged = tracing._union(ops)
        last_end = max(last_end, merged[-1][1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), share in zip(gaps, _split(gaps, pieces)):
            for label, ns in share.items():
                idle_ns[label] = idle_ns.get(label, 0.0) + ns
            if (b - a) * 1e-9 > longest[0]:
                longest = ((b - a) * 1e-9, max(share, key=share.get))
    n = max(devices, 1)
    return Spans(spans=spans,
                 idle_s={k: v * 1e-9 / n for k, v in idle_ns.items()},
                 longest_gap=longest,
                 device_tail_s=(w1 - last_end) * 1e-9 if devices else 0.0)


def op_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> the components of its ``op_name`` path (empty
    where it has none), for every instruction of a compiled program's HLO
    text."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%([\w.\-]+) = (.*)$', hlo_text,
                         re.M):
        op_name = re.search(r'op_name="([^"]*)"', m.group(2))
        out[m.group(1)] = (tuple(op_name.group(1).split("/")) if op_name
                           else ())
    return out


def named_scope(path: Tuple[str, ...], names) -> Optional[str]:
    """The innermost component of ``path`` that is in ``names``, or None."""
    for part in reversed(path):
        if part in names:
            return part
    return None


def scope_seconds(summary, scopes: Dict[str, Tuple[str, ...]],
                  names) -> Dict[str, float]:
    """Device self seconds of a ``tracing.Summary``'s ops by the innermost
    component of each op's path (``scopes`` from :func:`op_scopes`) that
    is in the caller's ``names``; ``UNSCOPED`` for the rest."""
    out: Dict[str, float] = {}
    for key, s in summary.op_seconds.items():
        scope = named_scope(scopes.get(key, ()), names) or UNSCOPED
        out[scope] = out.get(scope, 0.0) + s
    return out


@dataclasses.dataclass
class Trace(tracing.Summary):
    """A traced run's reading: the trace's :class:`bench.tracing.Summary`,
    the window's ``serve.*`` spans, and each instruction's ``op_name``
    path."""
    spans: Spans
    scopes: Dict[str, Tuple[str, ...]]


def summarize(planes, hlo_texts) -> Trace:
    """The :class:`Trace` of one window's ``planes`` and the compiled text
    of each step program. The trace keys ops by instruction name alone: a
    name that several programs use takes the path it has in the last of
    ``hlo_texts``."""
    planes = list(planes)
    return Trace(**vars(tracing.reduce(planes)), spans=reduce(planes),
                 scopes={k: v for text in hlo_texts
                         for k, v in op_scopes(text).items()})
