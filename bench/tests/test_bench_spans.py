"""The program-span reduction and its metric readers on a small recorded
trace with known answers."""
import dataclasses
import time
import types

import pytest

from bench import catalog, spans, tracing
from bench.tests.test_bench_tracing import _ev, _plane

STEP_FN = "jit(_step_fused_fn)/while/body/closed_call/checkpoint"
# the scopes model.kv_write_ms names
NAMES = ("kv_guard", "kv_append", "efta_paged", "lm_head", "sample")
KERNEL = ('%efta_paged.1 = (f32[8,12,32,64]) custom-call(f32[8,12,32,64] '
          '%q, /*index=1*/f32[8] %kv_append.12), '
          'custom_call_target="tpu_custom_call"')


def _op(text, start_us, dur_us):
    # a TPU trace's op: its name is the instruction's HLO text, without
    # the op_name metadata
    return _ev(text, start_us, dur_us, device_duration_ps=dur_us * 1e6)


def _host(with_serve=True):
    # two engine steps, 0..400 and 420..900, with the harness's record
    # span between them; times in us
    evs = [_ev("bench.window", 0, 1000), _ev("bench.step", 0, 400),
           _ev("bench.record", 400, 20), _ev("bench.step", 420, 480)]
    if with_serve:
        evs += [
            _ev("serve.step", 0, 400, width=32, running=2, rows=40,
                attempts=1),
            _ev("serve.schedule", 0, 50), _ev("serve.prepare", 50, 50),
            _ev("serve.dispatch", 100, 10), _ev("serve.wait", 110, 190),
            _ev("serve.commit", 300, 80),
            _ev("serve.step", 420, 480, width=1, running=2, rows=2,
                attempts=1),
            _ev("serve.schedule", 420, 50), _ev("serve.prepare", 470, 50),
            _ev("serve.dispatch", 520, 10), _ev("serve.wait", 530, 350),
            _ev("serve.commit", 880, 20)]
    return _plane("/host:CPU", python=evs)


def _device():
    return _plane("/device:TPU:0", **{"XLA Ops": [
        _op(KERNEL, 105, 145),
        _op("%fusion.12 = f32[8] scatter(f32[8] %a)", 250, 40),
        _op("%fusion.3 = pred[8] fusion(f32[8] %b)", 525, 20),
        _op(KERNEL, 545, 255),
        _op("%fusion.126 = f32[8] fusion(f32[8] %c)", 800, 50),
        _op("%copy.5 = f32[8] copy(f32[8] %d)", 850, 20)]})


def _hlo(scoped=True):
    """The compiled program's text for the ops of :func:`_device`."""
    def meta(scope, prim):
        path = f"{STEP_FN}/{scope}/{prim}" if scoped else f"{STEP_FN}/{prim}"
        return f'metadata={{op_name="{path}" stack_frame_id=1}}'
    return "\n".join([
        f"  {KERNEL}, {meta('efta_paged', 'pallas_call')}",
        f"  %fusion.12 = f32[8] fusion(f32[8] %a), kind=kLoop, "
        f"{meta('kv_append', 'scatter')}",
        f"  %fusion.3 = pred[8] fusion(f32[8] %b), {meta('kv_guard', 'max')}",
        f"  ROOT %fusion.126 = f32[8] fusion(f32[8] %c), "
        f"{meta('lm_head', 'dot_general')}",
        "  %copy.5 = f32[8] copy(f32[8] %d)",
        # a fused computation's own instructions never appear in a trace
        f"  %kv_append.12 = f32[8] scatter(f32[8] %p), "
        f"{meta('kv_append', 'scatter')}"])


def _trace(with_serve=True):
    return [_host(with_serve), _device()]


def test_idle_is_split_by_the_self_time_of_the_innermost_span():
    sp = spans.reduce(_trace())
    idle = {k: round(v * 1e6) for k, v in sp.idle_s.items()}
    # idle: 0..105, 290..525 and 870..1000 us. The middle gap runs through
    # the end of wait, commit, the first step's own tail, the record span
    # (outside every serve span), schedule, prepare and dispatch
    assert idle == {"serve.schedule": 100, "serve.prepare": 100,
                    "serve.dispatch": 10, "serve.wait": 20,
                    "serve.commit": 100, "serve.step": 20, "outside": 120}
    s = tracing.reduce(_trace())
    assert sum(sp.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert sp.longest_gap[0] == pytest.approx(235e-6)
    assert sp.longest_gap[1] == "serve.commit"
    assert sp.device_tail_s == pytest.approx(130e-6)


def test_nested_wait_keeps_its_own_share():
    sp = spans.reduce(_trace())
    depth = {(s.name, round(s.start_ns / 1e3)): s.depth for s in sp.spans}
    assert depth[("serve.step", 0)] == 0 and depth[("serve.wait", 110)] == 1
    # the gap from 290 us: 10 us left of the first wait, not the step's
    assert sp.idle_s["serve.wait"] == pytest.approx(20e-6)
    assert sp.idle_s["serve.step"] == pytest.approx(20e-6)
    assert [s.args["width"] for s in sp.steps] == [32, 1]


def test_ops_are_attributed_by_the_op_name_of_their_instruction():
    scopes = spans.op_scopes(_hlo())
    assert scopes["efta_paged.1"] == (*STEP_FN.split("/"), "efta_paged",
                                      "pallas_call")
    assert scopes["copy.5"] == ()
    # the kernel's text names the operand %kv_append.12 (after a /*index*/
    # comment); its time stays the kernel's
    by_scope = spans.scope_seconds(tracing.reduce(_trace()), scopes, NAMES)
    assert {k: round(v * 1e6) for k, v in by_scope.items()} == {
        "efta_paged": 400, "kv_append": 40, "kv_guard": 20, "lm_head": 50,
        "unscoped": 20}


@pytest.mark.parametrize("names,want", [
    # a name the caller leaves out counts for nothing
    (("kv_append",), {"kv_append": 40, "unscoped": 490}),
    # an enclosing component takes every op under it; the innermost named
    # component wins
    (("checkpoint",), {"checkpoint": 510, "unscoped": 20}),
    (("checkpoint", "lm_head"), {"checkpoint": 460, "lm_head": 50,
                                 "unscoped": 20}),
    # a component is matched whole, never as a substring
    (("kv", "append", "efta"), {"unscoped": 530}),
    ((), {"unscoped": 530}),
])
def test_scope_seconds_reads_the_names_its_caller_gives(names, want):
    by_scope = spans.scope_seconds(tracing.reduce(_trace()),
                                   spans.op_scopes(_hlo()), names)
    assert {k: round(v * 1e6) for k, v in by_scope.items()} == want


def test_summary_reads_the_same_with_and_without_program_spans():
    a = tracing.reduce(_trace(with_serve=True))
    b = tracing.reduce(_trace(with_serve=False))
    assert a == b
    assert a.breakdown() == b.breakdown()


def _run(trace, steps=2):
    return types.SimpleNamespace(trace=trace, steps=[object()] * steps)


def test_summarize_keeps_the_summary_the_spans_and_the_scopes():
    got = spans.summarize(_trace(), [_hlo(scoped=False), _hlo()])
    assert isinstance(got, tracing.Summary)
    assert tracing.Summary(**{f.name: getattr(got, f.name) for f in
                              dataclasses.fields(tracing.Summary)}) \
        == tracing.reduce(_trace())
    assert got.spans == spans.reduce(_trace())
    # a name both programs use takes the last program's scope
    assert got.scopes == spans.op_scopes(_hlo())


def test_kv_write_reads_the_scoped_ops():
    read = catalog.load_reader("model.kv_write_ms")
    trace = spans.summarize(_trace(), [_hlo()])
    got = read(_run(trace))
    # (40 + 20) us over two steps
    assert got["value"] == pytest.approx(0.03)
    assert got["kv_append_ms"] == pytest.approx(0.02)
    assert got["efta_paged_ms"] == pytest.approx(0.2)
    assert got["unscoped_share"] == pytest.approx(100 * 20 / 530)
    # a program without the scopes reads nothing
    assert read(_run(spans.summarize(_trace(), [_hlo(scoped=False)]))) is None


def test_host_gap_reads_the_span_split():
    trace = spans.summarize(_trace(), [_hlo()])
    read = catalog.load_reader("engine.host_gap_ms")
    got = read(_run(trace))
    # 470 us idle, 120 of them outside every serve span, over two steps
    assert got["value"] == pytest.approx(0.175)
    assert got["outside_ms"] == pytest.approx(0.06)
    assert got["serve.commit_ms"] == pytest.approx(0.05)
    assert got["longest_gap_span"] == "serve.commit"
    assert got["steps_width1"] == 1
    assert got["device_tail_ms"] == pytest.approx(0.13)
    # a trace without program spans reads nothing
    assert read(_run(tracing.reduce(_trace()))) is None


@pytest.mark.parametrize("name", ["model.kv_write_ms", "engine.host_gap_ms"])
def test_new_readers_read_nothing_without_a_trace(name):
    assert catalog.load_reader(name)(_run(None)) is None


def test_traced_run_gives_the_summary_spans_and_scopes():
    # on the CPU the trace has no device plane: the spans and the scopes
    # are there, and the metrics that need device ops read nothing
    from bench import run
    from bench.tests import tiny
    seen = {}
    result = run.run_cell(
        tiny.cell(), 12, 1.5, True, device={"platform": "cpu", "kind": "cpu",
                                            "count": 1},
        peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        t_start=time.perf_counter(), observe=seen)
    summary = seen["summary"]
    assert result["correct"]
    widths = {s.args["width"] for s in summary.spans.steps
              if "width" in s.args}
    assert widths and widths <= {1, tiny.CONFIG["engine"]["chunk_size"]}
    assert {"kv_guard", "kv_append", "lm_head", "sample"} <= {
        spans.named_scope(path, NAMES) for path in summary.scopes.values()}
    names = {m.name for m in tiny.cell().per_layer}
    assert {"engine.host_gap_ms", "model.kv_write_ms"} <= names
    assert not names & {"engine.host_gap_ms", "model.kv_write_ms"} & set(
        result["metrics"])
