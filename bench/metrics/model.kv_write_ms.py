"""Model step: device milliseconds per engine step of the traced window in
the ops of the KV append and its checksums (``models/attention.py``
``_paged_chunk``): ``kv_guard``, the pre-verify of the first touched
block, and ``kv_append``, the row scatter, ``encode_kv`` and the checksum
scatters. An op counts under the innermost of the scopes this reader
names in its ``op_name`` path, which it reads from ``run.trace.scopes``
(:func:`bench.spans.op_scopes` of the step's compiled programs), and reads
nothing where the trace has none. Notes: the ms per step of each scope of
``NOTED``, and the share of the device's op time under none of them."""
from bench import spans

KV = ("kv_guard", "kv_append")
# the KV append's scopes, the paged kernel's pallas_call name and the
# step's other named scopes (models/transformer.py, serve/paged.py)
NOTED = KV + ("efta_paged", "lm_head", "sample")


def read(run):
    scopes = getattr(run.trace, "scopes", None)
    if not scopes or not run.steps:
        return None
    by_scope = spans.scope_seconds(run.trace, scopes, NOTED)
    if not any(s in by_scope for s in KV):
        return None
    per_step = 1e3 / len(run.steps)
    notes = {f"{s}_ms": per_step * by_scope.get(s, 0.0) for s in NOTED}
    notes["unscoped_share"] = 100.0 * by_scope.get(
        spans.UNSCOPED, 0.0) / sum(by_scope.values())
    return {"value": sum(notes[f"{s}_ms"] for s in KV), **notes}
