"""Engine step, host side: device-idle milliseconds per engine step of the
traced window that fall in the self time of the program's ``serve.*``
spans (``serve/paged.py`` ``PagedServeEngine.step``). Notes: the ms per
step under each span and ``outside`` any, the longest idle gap and the
span that holds most of it, the steps that ran the width-1 program (from
the ``serve.step`` argument ``width``), and the time from the last device
op to the window's close.

It reads ``run.trace.spans``, the :class:`bench.spans.Spans` of the
window's planes, and reads nothing where the trace has none."""
from bench import spans


def read(run):
    sp = getattr(run.trace, "spans", None)
    if sp is None or not sp.steps or not run.steps or not run.trace.devices:
        return None
    per_step = 1e3 / len(run.steps)
    notes = {f"{k}_ms": per_step * v for k, v in sp.idle_s.items()}
    gap_s, gap_span = sp.longest_gap
    return {"value": per_step * sum(v for k, v in sp.idle_s.items()
                                    if k != spans.OUTSIDE),
            **notes, "longest_gap_ms": 1e3 * gap_s,
            "longest_gap_span": gap_span,
            "steps_width1": sum(s.args.get("width") == 1
                                for s in sp.steps),
            "device_tail_ms": 1e3 * sp.device_tail_s}
