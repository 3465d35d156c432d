"""Work counts against hand reckonings."""
import pytest

from bench import catalog, work

GPT2 = catalog.load_json(catalog.BENCH_DIR / "configs" / "gpt2.json")
XL = catalog.load_json(catalog.BENCH_DIR / "configs" / "gpt2-xl.json")
GEO = {"block_size": 16, "check_stride": 8}


def test_parameters():
    # Hugging Face's counts (124,439,808 and 1,557,611,200) less the
    # linear layers' biases, which the program does not have
    assert work.param_count(GPT2) == 124_439_808 - 12 * (3 * 768 + 768
                                                        + 3072 + 768)
    assert work.param_count(GPT2) == 124_356_864
    assert work.param_count(XL) == 1_557_611_200 - 48 * (3 * 1600 + 1600
                                                        + 6400 + 1600)


def test_flops_per_token():
    # 2 x (12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257)
    assert work.matmul_flops_per_token(GPT2) == 247_064_064
    # 2 x (48 x (4 x 1600^2 + 2 x 1600 x 6400) + 1600 x 50257): 3.1 GFLOP
    assert work.matmul_flops_per_token(XL) == 3_109_942_400


def test_pool_bytes_per_token():
    assert work.pool_bytes_per_token(GPT2, GEO) == 73_728      # 73.7 KB
    assert work.pool_bytes_per_token(XL, GEO) == 614_400       # 614 KB


def test_visible_pairs():
    assert work.visible_pairs(1, 700) == 700
    assert work.visible_pairs(32, 100) == 32 * 68 + 32 * 33 // 2


def test_kernel_call_live_bytes_and_flops():
    reqs = [(32, 100), (1, 700)]
    w = work.kernel_call_work(GPT2, GEO, reqs)
    # 7 + 44 resident blocks of (2 x 16 + 4 x 8) rows x 12 heads x 64 x 2 B,
    # plus 33 query rows read and 33 output rows written
    assert w.bytes == 51 * 64 * 12 * 64 * 2 + 2 * 33 * 768 * 2
    pairs = 32 * 68 + 32 * 33 // 2 + 700
    assert w.flops == 4 * 768 * pairs + 51 * 8 * 16 * 12 * 64
    t, bound = w.least_seconds(catalog.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(w.bytes / 8.19e11)


def _kernel_work_of_steps(cache_len, n_steps=10):
    import jax

    from bench import drive, system, weights
    from bench.tests import tiny

    cfg = dict(tiny.CONFIG, engine=dict(tiny.CONFIG["engine"],
                                        cache_len=cache_len))
    w = weights.make(cfg, 3)
    eng = system.build_engine(system.model_config(cfg),
                              system.program_params(w), cfg["engine"])
    geo = {"block_size": eng.block_size, "check_stride": eng.check_stride,
           "chunk_size": eng.chunk_size, "max_blocks": eng.max_blocks}
    stream = catalog.load_generator("lognormal")(
        tiny.TRAFFIC, 3, vocab=cfg["vocab_size"], max_total=128)
    drv = drive.Driver(eng, stream, tiny.TRAFFIC, geo,
                       annotate=jax.profiler.TraceAnnotation)
    drv.start()
    for _ in range(n_steps):
        drv.tick()
    assert drv.exact
    per_step = [work.kernel_call_work(cfg, geo, s.live) for s in drv.steps]
    return eng.max_blocks, per_step


def test_engine_table_width_does_not_change_the_counted_work():
    narrow, a = _kernel_work_of_steps(128)
    wide, b = _kernel_work_of_steps(256)
    assert (narrow, wide) == (8, 16)
    assert a == b and sum(x.bytes for x in a) > 0
