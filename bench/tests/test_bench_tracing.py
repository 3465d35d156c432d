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


KERNEL = ('%efta_paged.1 = (bf16[8,12,32,64]) custom-call(bf16[8,12,32,64] '
          '%q), custom_call_target="tpu_custom_call"')
# a second Pallas kernel in the step, as an expert layer's grouped matmul
OTHER = ('%gmm.3 = (bf16[64,768]) custom-call(bf16[64,768] %x), '
         'custom_call_target="tpu_custom_call"')


def _trace(other=False):
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
            _ev("%fusion.9 = bf16[8] fusion(bf16[8] %c)", 1200, 50)]
        + ([_ev(OTHER, 360, 40), _ev(OTHER, 810, 60)] if other else []),
        "XLA Modules": [_ev("jit_step", 100, 700)]})
    return [host, dev, _plane("/device:TPU:1")]


def test_busy_idle_and_kernel_time():
    s = tracing.reduce(_trace())
    assert s.window_s == pytest.approx(1e-3)
    assert s.devices == 1
    # union: 100..350 and 600..800 -> 450 us busy
    assert s.busy_s == pytest.approx(450e-6)
    assert s.seconds_named("efta_paged") == pytest.approx(300e-6)
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
    assert [k for k, _ in b["device_ops"]][:2] == ["efta_paged.1",
                                                    "fusion.2"]
    assert [round(s * 1e6) for _, s in b["idle_gaps"]] == [250, 200, 100]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce([_plane("/host:CPU", python=[_ev("x", 0, 1)])])


def test_kernel_metrics_count_the_paged_kernel_by_name_alone():
    """A second Pallas kernel in the step (another ``tpu_custom_call``)
    stays out of both kernel metrics."""
    from bench import catalog
    ms = catalog.load_reader("kernel.efta_paged.ms")
    roof = catalog.load_reader("kernel.efta_paged_roofline")
    layers = catalog.load_arch("gpt2").layers(
        {"n_layer": 2, "n_embd": 64, "n_head": 4, "dtype": "bfloat16"})
    reads = []
    for other in (False, True):
        s = tracing.reduce(_trace(other))
        assert s.seconds_named("gmm") == pytest.approx(100e-6 * other)
        run = types.SimpleNamespace(
            trace=s, steps=[types.SimpleNamespace(live=[(1, 40)])] * 2,
            exact=True, layers=layers,
            geometry={"block_size": 16, "check_stride": 8},
            peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        reads.append((ms(run), roof(run)))
    assert reads[0] == reads[1]
    # 300 us of the kernel over two steps
    assert reads[0][0] == pytest.approx(0.15)
