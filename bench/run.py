"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on the machine that holds the cell's chips.
Set-up (counted in ``setup_s``): make the weights on the device from the
seed, build the engine, compile (or load from the compile cache) the step's
two programs, then run the cell's traffic until it is steady. The window
then runs for ``--seconds``. With ``--trace 1`` a profiler trace of the
window gives the per-layer metrics, with the program's ``serve.*`` spans in
it and the program scope of each op, read after the window from the step
programs' compiled text (:mod:`bench.spans`); with ``--trace 0`` the
end-to-end ones. After the window (and a drain that waits for the first
token of every request due in it), the engine is freed and a sample of the
finished requests is checked against the float32 reference of the
configuration's architecture (``bench/arch/<model_type>.py``).

Exits 2 outside a checkout, 3 when JAX finds no TPU or fewer chips than
the cell asks for, and prints no result then.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
DRAIN_S = 60.0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache`` (a fixed path: it is part of the
    cache's key). Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises unless they are TPUs and at
    least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"JAX found platform {devs[0].platform!r}, not a "
                           f"TPU")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips; JAX found "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Counts compilations (traces and backend compiles) as they happen."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event in self.EVENTS:
            self.n += 1


class GcPauses:
    """Times the interpreter's garbage collections as they happen."""

    def __init__(self, clock):
        self.clock = clock
        self.pauses: list = []          # (start, seconds)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = self.clock()
        elif self._t0 is not None:
            self.pauses.append((self._t0, self.clock() - self._t0))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def within(self, t0: float, t1: float) -> list:
        return [s for t, s in self.pauses if t0 <= t < t1]


def run_cell(cell, seed: int, seconds: float, trace: bool, *, device: dict,
             peaks: dict, t_start: float = T_START,
             control: str | None = None, observe: dict | None = None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``control`` (``bench/calibrate.py`` only) also reads, on the same
    sample, the widest gap of the reference computed in that precision;
    ``observe`` receives the requests' records and, traced, the trace's
    ``Summary``."""
    import jax

    from bench import correct, drive, reference, spans, system, tracing
    from bench import weights as weights_mod

    cfg, eng_cfg, arch = cell.config, cell.config["engine"], cell.arch
    # the configuration's matrix-product precision, for every product the
    # program traces (the kernel's included); unset: JAX's default
    jax.config.update("jax_default_matmul_precision",
                      cfg.get("matmul_precision"))
    counter = CompileCounter()
    mcfg = arch.model_config(cfg)
    w = weights_mod.make(arch, cfg, seed)
    params = arch.program_params(w)
    system.check_params(mcfg, params)
    jax.block_until_ready(w)
    eng = system.build_engine(mcfg, params, eng_cfg)
    geometry = {"block_size": eng.block_size,
                "check_stride": eng.check_stride,
                "chunk_size": eng.chunk_size, "n_slots": eng.n_slots,
                "cache_len": eng.cache_len}
    drive.warm_programs(eng, eng.chunk_size, cfg["vocab_size"])
    stream = cell.generator(cell.traffic, seed, vocab=cfg["vocab_size"],
                            max_total=eng.cache_len)
    drv = drive.Driver(eng, stream, cell.traffic, geometry,
                       annotate=_annotate)
    clock = drv.clock
    drv.start()
    if drv.open:
        warm_end = clock() + float(cell.traffic["warm_s"])
        while clock() < warm_end:
            drv.tick(deadline=warm_end)
    else:
        for _ in range(int(cell.traffic["warm_steps"])):
            drv.tick()
    compiles_warm = counter.n

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        # the first step under the profiler stalls the device for up to a
        # few seconds; it runs before the window
        drv.tick()
    # what set-up left behind is collected now and kept out of later
    # collections, so that none of them walks it inside the window
    gc.collect()
    gc.freeze()
    gc_pauses = GcPauses(clock)
    t_open = clock()
    setup_s = t_open - t_start
    with _annotate(tracing.WINDOW_SPAN):
        close_at = t_open + seconds
        while clock() < close_at:
            drv.tick(deadline=close_at)
        t_close = clock()
    gc_pauses.close()
    gc_in_window = gc_pauses.within(t_open, t_close)
    waiting_at_close = len(eng.scheduler.waiting)
    n_window_steps = len(drv.steps)
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = counter.n - compiles_warm

    due = [r for r in drv.records if t_open <= r.due < t_close]
    drain_end = clock() + DRAIN_S
    while any(r.first_t is None for r in due) and clock() < drain_end:
        drv.tick(deadline=drain_end)
    failed = sum(r.first_t is None for r in due)
    memory_peak = _peak_bytes(cell.chips)

    summary, read_s = None, {}
    if trace:
        t = clock()
        planes = list(tracing.capture_dir_trace(log_dir).planes)
        shutil.rmtree(log_dir, ignore_errors=True)
        t_texts = clock()
        # a name both step programs use takes the chunk-width one's scope
        texts = [eng.lower_step(width).compile().as_text()
                 for width in (1, eng.chunk_size)]
        read_s["step_texts_s"] = clock() - t_texts
        summary = spans.summarize(planes, texts)
        read_s["trace_read_s"] = clock() - t

    steps = [s for s in drv.steps[:n_window_steps]
             if t_open < s.t1 <= t_close]
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, arch=arch, layers=arch.layers(cfg),
        geometry=geometry, peaks=peaks, seed=seed,
        records=drv.records, steps=steps, exact=drv.exact, t_open=t_open,
        t_close=t_close, setup_s=setup_s, trace=summary)
    kind = "per_layer" if trace else "end_to_end"
    metrics, notes = {}, {}
    for m in getattr(cell, kind):
        got = m.read(run)
        if got is None:
            continue
        if isinstance(got, dict):
            notes.update({f"{m.name}.{k}": v for k, v in got.items()
                          if k != "value"})
            got = got["value"]
        metrics[m.name] = {"value": got, "unit": m.unit}

    finished = [r for r in drv.records
                if r.done_t is not None and t_open <= r.done_t <= t_close]
    short = sum(len(r.req.generated) != r.max_new for r in drv.records
                if r.done_t is not None)
    counts = system.counters(eng)
    sampled = correct.sample(finished, cell.sample, seed)
    # free the engine (its pool) before the reference runs
    del eng, drv.eng, params
    gc.unfreeze()
    gc.collect()
    served, ctrl = correct.served_gaps(
        reference.forward(arch, cfg), w, sampled,
        pad_to=geometry["cache_len"], low=control)
    tokens = int(sum(len(g) for g in served))
    detections = sum(counts["detected"].values()) + counts[
        "kv_detected_blocks"]

    def verdict(gaps: list, detected: int, retries: int):
        return correct.checks(gap_max=correct.widest(gaps),
                              limits=cell.limits, tokens=tokens, short=short,
                              unserved=failed, detections=detected,
                              retries=retries)
    checks = verdict(served, detections, counts["retries"])
    sigma = reference.bf16_logit_sigma(len(run.layers), arch.lm_head(w))
    info = {"window_s": t_close - t_open, "steps": len(steps),
            "requests_finished": len(finished),
            "sampled_requests": len(sampled),
            "steps_width1": sum(s.width1 for s in steps),
            "longest_step_ms": 1e3 * max((s.t1 - s.t0 for s in steps),
                                         default=0.0),
            "longest_gap_between_steps_ms": 1e3 * max(
                (b.t0 - a.t1 for a, b in zip(steps, steps[1:])),
                default=0.0),
            **_stalls(steps),
            "gc_pauses_in_window": len(gc_in_window),
            "gc_longest_ms": 1e3 * max(gc_in_window, default=0.0),
            "sampled_tokens_width1": sum(r.width1_tokens for r in sampled),
            "compiles_in_window": compiles_in_window,
            "work_counts_exact": drv.exact,
            "generator_late_p99_ms": _late_p99_ms(drv.records),
            "bf16_logit_sigma": sigma,
            **read_s,
            **{f"ft.{k}": v for k, v in counts.items()}, **notes}
    if control is not None:
        # the control stands in the program's place: the same checks, its
        # gaps for the served ones; it has no fault-tolerance layer
        ctrl_checks = verdict(ctrl, 0, 0)
        info["control_gap_max"] = correct.widest(ctrl)
        info["control_correct"] = all(c.ok for c in ctrl_checks)
    if observe is not None:
        observe.update(records=drv.records, t_open=t_open, t_close=t_close,
                       waiting_at_close=waiting_at_close, summary=summary)
    result = {"correct": all(c.ok for c in checks),
              "attempted": len(due), "failed": int(failed),
              "metrics": metrics,
              "device": dict(device, memory_peak_bytes=memory_peak)}
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["info"] = info
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                 "op": c.op} for c in checks}
    for k, v in info.items():
        say(f"[info] {k} {v}")
    for c in checks:
        say(f"[check] {c.line()} {'ok' if c.ok else 'FAILED'}")
    return result


def _stalls(steps) -> dict:
    """Steps that took over 1.5 times the median step, and the time they
    took beyond the median: where a window lost steps to a stall."""
    if not steps:
        return {}
    times = [s.t1 - s.t0 for s in steps]
    med = statistics.median(times)
    slow = [t for t in times if t > 1.5 * med]
    return {"step_median_ms": 1e3 * med, "slow_steps": len(slow),
            "slow_steps_excess_ms": 1e3 * sum(t - med for t in slow)}


def _late_p99_ms(records) -> float:
    late = sorted(r.submitted - r.due for r in records)
    return 1e3 * late[min(len(late) - 1, int(0.99 * len(late)))] \
        if late else 0.0


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        say(f"bench: {ROOT} holds no src/repro: run from the root of a "
            f"checkout of the repository")
        return 2
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import catalog

    cell = catalog.load_cell(args.workload, ROOT)
    enable_compile_cache()
    try:
        device = device_info(cell.chips)
    except RuntimeError as e:
        say(f"bench: {e}")
        return 3
    peaks = catalog.peaks(device["kind"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
