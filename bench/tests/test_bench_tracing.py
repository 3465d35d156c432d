"""The trace reduction on a small recorded trace with known answers."""
import types

import pytest

from bench import tracing


def _ev(name, start_us, dur_us, **stats):
    return types.SimpleNamespace(name=name, start_ns=int(start_us * 1000),
                                 duration_ns=int(dur_us * 1000),
                                 stats=list(stats.items()))


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


KERNEL = ('%custom-call.1 = (bf16[8,12,32,64]) custom-call(bf16[8,12,32,64] '
          '%q), custom_call_target="tpu_custom_call"')


def _trace():
    # window 0..1000 us; host steps 0..400 and 500..900; on the device two
    # runs of a layer loop (100..350, 600..800), each around the kernel
    # and a fusion; one more fusion after the window closed
    host = _plane("/host:CPU", python=[
        _ev("bench.window", 0, 1000),
        _ev("bench.step", 0, 400), _ev("bench.record", 400, 100),
        _ev("bench.step", 500, 400), _ev("bench.submit", 900, 100),
        _ev("other", 0, 5)])
    dev = _plane("/device:TPU:0", **{
        "XLA Ops": [
            _ev("%while.1 = (s32[]) while(s32[] %a)", 100, 250),
            _ev(KERNEL, 100, 200),
            _ev("%fusion.2 = bf16[8] fusion(bf16[8] %b)", 300, 50),
            _ev("%while.1 = (s32[]) while(s32[] %a)", 600, 200),
            _ev(KERNEL, 600, 100),
            _ev("%fusion.2 = bf16[8] fusion(bf16[8] %b)", 700, 100),
            _ev("%fusion.9 = bf16[8] fusion(bf16[8] %c)", 1200, 50)],
        "XLA Modules": [_ev("jit_step", 100, 700)]})
    return [host, dev, _plane("/device:TPU:1")]


def test_busy_idle_and_kernel_time():
    s = tracing.reduce(_trace())
    assert s.window_s == pytest.approx(1e-3)
    assert s.devices == 1
    # union: 100..350 and 600..800 -> 450 us busy
    assert s.busy_s == pytest.approx(450e-6)
    assert s.seconds_matching("tpu_custom_call") == pytest.approx(300e-6)
    # self time: the loop keeps nothing its body covers
    assert s.op_seconds["fusion.2"] == pytest.approx(150e-6)
    assert s.op_seconds["while.1"] == pytest.approx(0)
    gaps = {round(g * 1e6): label for g, label in s.gaps}
    # 0..100 in a step, 350..600 mid at 475 in the record span, 800..1000
    # mid at 900: the step ends at 900 and the submit begins there
    assert gaps[100] == "bench.step" and gaps[250] == "bench.record"
    assert set(gaps) == {100, 250, 200}


def test_breakdown_lists_ops_and_gaps_longest_first():
    b = tracing.reduce(_trace()).breakdown()
    assert [k for k, _ in b["device_ops"]][:2] == ["custom-call.1",
                                                    "fusion.2"]
    assert [round(s * 1e6) for _, s in b["idle_gaps"]] == [250, 200, 100]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce([_plane("/host:CPU", python=[_ev("x", 0, 1)])])
