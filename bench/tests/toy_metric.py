"""A toy metric reader, a test fixture and no benchmark metric:
``test_bench_arch.py`` copies it into a copy of the benchmark as
``bench/metrics/toy.mix_ms.py`` to show that a program scope no harness
file knows is read by a new metric file alone.

Toy mixer: device milliseconds per engine step of the traced window in
the ops under the program scope ``toy_mix`` or ``toy_gate`` (a scope the
mixer opens inside ``toy_mix``), each op under the innermost of the two in
its ``op_name`` path (``run.trace.scopes``). Notes: the ms per step of
each. Reads nothing where the trace has no op under either."""
from bench import spans

MIX = ("toy_mix", "toy_gate")


def read(run):
    scopes = getattr(run.trace, "scopes", None)
    if not scopes or not run.steps:
        return None
    by_scope = spans.scope_seconds(run.trace, scopes, MIX)
    if not any(s in by_scope for s in MIX):
        return None
    per_step = 1e3 / len(run.steps)
    notes = {f"{s}_ms": per_step * by_scope.get(s, 0.0) for s in MIX}
    return {"value": sum(notes.values()), **notes}
