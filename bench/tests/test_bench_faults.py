"""The comparison that decides ``correct`` fails what it should.

On the tiny cell, on the CPU: a clean run is correct; the control (the
reference in float8, put in the program's place) is judged by the same
checks and comes out not correct; and a run
with the timed path broken underneath comes out not correct, once for each
fault a serving cell can have on one chip: a step that returns its state
unchanged, and a token altered where it is produced. (Half of a batch left
out and the exchange between chips are faults of training and of several
chips; no cell here has them.)
"""
import time

import pytest

from bench import run, system
from bench.tests import tiny

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(seed, control=None):
    return run.run_cell(tiny.cell(), seed, 1.5, False, device=CPU,
                        peaks=PEAKS, t_start=time.perf_counter(),
                        control=control)


def test_clean_run_is_correct_and_control_is_not():
    res = _run(11, control="float8_e4m3fn")
    assert res["correct"], res["checks"]
    assert res["checks"]["detections"]["value"] == 0
    assert res["checks"]["retries"]["value"] == 0
    assert not res["info"]["control_correct"]
    assert res["info"]["control_gap_max"] > res["checks"]["gap_max"]["limit"]
    assert list(res)[-1] == "checks"


def _planted(monkeypatch, fault):
    build = system.build_engine

    def broken(*a, **kw):
        eng = build(*a, **kw)
        step = eng._step_fused

        def faulty(*args):
            return fault(args, step(*args))
        eng._step_fused = faulty
        return eng
    monkeypatch.setattr(system, "build_engine", broken)


def _state_unchanged(args, out):
    logits, next_tokens, rep, bad, _ = out
    return logits, next_tokens, rep, bad, args[2]


def _token_altered(args, out):
    logits, next_tokens, rep, bad, state = out
    return logits, (next_tokens + 1) % tiny.CONFIG["vocab_size"], rep, bad, \
        state


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state-unchanged", "token-altered"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _planted(monkeypatch, fault)
    res = _run(12)
    assert not res["correct"]
    assert res["checks"]["gap_max"]["value"] > res["checks"]["gap_max"][
        "limit"]


def test_a_detection_on_clean_traffic_is_not_correct(monkeypatch):
    """A bit flipped in the kernel's scores at one step: the EFTA layer
    detects it, and a detection on clean traffic fails ``correct`` even
    where the corrected tokens pass the gap."""
    from repro.core.fault import FaultSpec, Site

    build = system.build_engine

    def flipping(*a, **kw):
        eng = build(*a, **kw)
        step, n = eng.step, [0]

        def one_flip(faults=None):
            n[0] += 1
            if n[0] == 8:
                faults = FaultSpec.single(Site.GEMM1, bit=27)
            return step(faults)
        eng.step = one_flip
        return eng
    monkeypatch.setattr(system, "build_engine", flipping)
    res = _run(13)
    assert not res["correct"]
    assert res["checks"]["detections"]["value"] > 0
