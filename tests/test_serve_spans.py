"""The served step's host spans and device scope names.

``PagedServeEngine.step`` marks its parts as ``serve.*`` profiler spans
(``repro.serve.trace``) and its step program names its parts with
``jax.named_scope``. Here, on the tiny fused engine: the spans' order and
nesting, the ``serve.step`` arguments, the repair and retry spans, the
spans in a real profiler trace, and the scope names in the compiled
program's ``op_name`` metadata.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.kernels.efta_paged import pages_per_block
from repro.serve import paged

PROMPT = 20          # under the chunk width: one chunk prefills a prompt


class _Recorder:
    """Stands in for ``repro.serve.trace.span``: keeps each span as
    ``[name, args, depth]``, in order of entry."""

    def __init__(self):
        self.spans = []
        self._depth = 0

    def __call__(self, name, **args):
        rec = self

        class _Span:
            def __enter__(self):
                self.entry = [name, dict(args), rec._depth]
                rec.spans.append(self.entry)
                rec._depth += 1
                return self

            def __exit__(self, *exc):
                rec._depth -= 1
                return False

            def set_metadata(self, **more):
                self.entry[1].update(more)

        return _Span()

    def steps(self):
        """Each ``step`` span with the spans inside it, in order."""
        out = []
        for name, args, depth in self.spans:
            if name == "step":
                out.append((args, []))
            else:
                assert depth >= 1 and out, name
                if depth == 1:
                    out[-1][1].append(name)
        return out


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("gpt2-smoke")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(tiny):
    cfg, model, params = tiny
    return paged.PagedServeEngine(model, params, n_slots=2, cache_len=48,
                                  block_size=16, kernel="fused")


def _prompt(cfg, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (PROMPT,)).astype(np.int32)


@pytest.fixture(scope="module")
def served(tiny):
    """Two requests served to the end with every span recorded, then one
    step that reads a block poisoned by ``inject_kv_fault``."""
    cfg = tiny[0]
    eng = _engine(tiny)
    rec = _Recorder()
    mp = pytest.MonkeyPatch()
    mp.setattr(paged, "span", rec)
    try:
        for seed in (1, 2):
            eng.submit(_prompt(cfg, seed), max_new_tokens=3)
        eng.run()
        clean = rec.steps()
        rec.spans.clear()
        eng.submit(_prompt(cfg, 3), max_new_tokens=4)
        eng.step()                                   # prefill
        req = list(eng.scheduler.active_rows())[0]
        eng.inject_kv_fault(layer=1, block=req.block_ids[0], head=0, row=3,
                            col=5, bit=27, into="v")
        eng.step()                                   # reads the bad block
        faulty = rec.steps()
    finally:
        mp.undo()
    return eng, clean, faulty


def test_step_span_encloses_its_parts_in_order(served):
    _, clean, _ = served
    ran = [inner for _, inner in clean if inner != ["schedule"]]
    assert ran
    for inner in ran:
        assert inner == ["schedule", "prepare", "dispatch", "wait",
                         "commit"]


def test_step_span_arguments(served):
    eng, clean, _ = served
    ran = [args for args, inner in clean if "dispatch" in inner]
    # both prompts prefill in the first step at the chunk width; every
    # later step only decodes, at width 1
    assert ran[0] == {"kv_pages_per_block": pages_per_block(
        eng.block_size, eng.max_blocks), "width": eng.chunk_size,
        "running": 2, "rows": 2 * PROMPT, "attempts": 1}
    assert len(ran) >= 3
    for args in ran[1:]:
        assert args["width"] == 1 and args["attempts"] == 1
        assert args["rows"] == args["running"] <= 2


def test_kv_fault_repairs_and_retries_inside_one_step(served):
    eng, _, faulty = served
    assert eng.paged_stats.kv_detected_blocks == 1
    args, inner = faulty[1]
    assert inner == ["schedule", "prepare", "dispatch", "wait", "repair",
                     "prepare", "dispatch", "wait", "commit"]
    assert args["attempts"] == 2 and args["width"] == 1


def test_spans_land_in_the_profiler_trace(tiny, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(tiny)
    eng.submit(_prompt(tiny[0], 4), max_new_tokens=2)
    eng.step()                                       # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = [ev for p in ProfileData.from_file(path).planes
              for line in p.lines for ev in line.events
              if ev.name.startswith("serve.")]
    names = {ev.name for ev in events}
    assert {"serve.step", "serve.schedule", "serve.prepare",
            "serve.dispatch", "serve.wait", "serve.commit"} <= names
    steps = [dict(ev.stats) for ev in events if ev.name == "serve.step"]
    assert steps and steps[0]["width"] == 1 and steps[0]["running"] == 1
    assert steps[0]["kv_pages_per_block"] == pages_per_block(
        eng.block_size, eng.max_blocks)


def test_step_program_names_its_scopes(tiny):
    eng = _engine(tiny)
    text = eng.lower_step(eng.chunk_size).compile().as_text()
    paths = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("kv_guard", "kv_append", "lm_head", "sample"):
        assert any(scope in p.split("/") for p in paths), scope
