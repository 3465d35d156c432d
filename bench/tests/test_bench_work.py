"""Work counts against hand reckonings."""
import dataclasses
import math

import pytest

from bench import catalog, work

GPT2 = catalog.load_json(catalog.BENCH_DIR / "configs" / "gpt2.json")
XL = catalog.load_json(catalog.BENCH_DIR / "configs" / "gpt2-xl.json")
ARCH = catalog.load_arch("gpt2")
GEO = {"block_size": 16, "check_stride": 8}


def test_parameters():
    # Hugging Face's counts (124,439,808 and 1,557,611,200) less the
    # linear layers' biases, which the program does not have: the weights
    # the harness makes
    def count(cfg):
        return sum(math.prod(s) for s in ARCH.weight_shapes(cfg).values())
    assert count(GPT2) == 124_439_808 - 12 * (3 * 768 + 768 + 3072 + 768)
    assert count(GPT2) == 124_356_864
    assert count(XL) == 1_557_611_200 - 48 * (3 * 1600 + 1600 + 6400
                                              + 1600)


def test_flops_per_token():
    # 2 x (12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257)
    assert ARCH.matmul_flops_per_token(GPT2) == 247_064_064
    # 2 x (48 x (4 x 1600^2 + 2 x 1600 x 6400) + 1600 x 50257): 3.1 GFLOP
    assert ARCH.matmul_flops_per_token(XL) == 3_109_942_400


def test_pool_bytes_per_token():
    assert work.pool_bytes_per_token(ARCH.layers(GPT2), GEO) == 73_728
    assert work.pool_bytes_per_token(ARCH.layers(XL), GEO) == 614_400
    # grouped heads: the pool holds the KV heads alone
    gqa = [work.Layer(heads=32, kv_heads=4, head_dim=128, window=1024,
                      itemsize=2)] * 3
    assert work.pool_bytes_per_token(gqa, GEO) == 3 * 4 * 4 * 128 * 2


def test_visible_pairs():
    assert work.visible_pairs(1, 700) == 700
    assert work.visible_pairs(32, 100) == 32 * 68 + 32 * 33 // 2


@pytest.mark.parametrize("rows,kv_len,window,want", [
    (1, 700, 16, 16),                 # a decode row sees the window
    (1, 10, 16, 10),                  # ... or all keys, fewer than it
    (32, 100, 16, 32 * 16),           # every row past the window's width
    (32, 40, 16, 84 + 25 * 16),       # rows at 8..14 see 9..15 keys, then
    #                                   rows at 15..39 see 16
    (32, 32, 16, 16 * 17 // 2 + 16 * 16),
    (32, 100, 1024, 32 * 68 + 32 * 33 // 2),   # a window wider than all
])
def test_visible_pairs_in_a_window(rows, kv_len, window, want):
    assert work.visible_pairs(rows, kv_len, window) == want
    # against a count by brute force
    assert want == sum(min(p + 1, window)
                       for p in range(kv_len - rows, kv_len))


def test_resident_blocks_in_a_window():
    # 70 tokens in blocks of 16: 5 blocks; a decode row at 69 sees 54..69,
    # blocks 3 and 4
    assert work.resident_blocks(1, 70, 16) == 5
    assert work.resident_blocks(1, 70, 16, window=16) == 2
    # a chunk of 32 rows from 38: its first row sees 23.., blocks 1..4
    assert work.resident_blocks(32, 70, 16, window=16) == 4
    assert work.resident_blocks(1, 10, 16, window=16) == 1


def test_kernel_call_live_bytes_and_flops():
    reqs = [(32, 100), (1, 700)]
    w = work.kernel_call_work(ARCH.layers(GPT2)[0], GEO, reqs)
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
    arch = catalog.load_arch(cfg["model_type"])
    w = weights.make(arch, cfg, 3)
    eng = system.build_engine(arch.model_config(cfg), arch.program_params(w),
                              cfg["engine"])
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
    layer = arch.layers(cfg)[0]
    per_step = [work.kernel_call_work(layer, geo, s.live) for s in drv.steps]
    return eng.max_blocks, per_step


def test_engine_table_width_does_not_change_the_counted_work():
    narrow, a = _kernel_work_of_steps(128)
    wide, b = _kernel_work_of_steps(256)
    assert (narrow, wide) == (8, 16)
    assert a == b and sum(x.bytes for x in a) > 0


def test_kernel_work_counts_kv_heads_and_the_window():
    # GQA: 8 query heads over 2 KV heads of 64, bf16; one full layer and
    # one with a window of 16
    full = work.Layer(heads=8, kv_heads=2, head_dim=64, window=None,
                      itemsize=2)
    win = dataclasses.replace(full, window=16)
    reqs = [(1, 70), (32, 100)]
    a = work.kernel_call_work(full, GEO, reqs)
    b = work.kernel_call_work(win, GEO, reqs)
    tile = (2 * 16 + 4 * 8) * 2 * 64 * 2       # a block, both KV heads
    q_rows = 2 * 33 * 8 * 64 * 2                # fed rows read, out written
    # full: 5 + 7 blocks; window: 2 + 4 (the chunk's first row, at 68,
    # sees from 53, in block 3)
    assert a.bytes == 12 * tile + q_rows
    assert b.bytes == 6 * tile + q_rows
    pairs_full = 70 + 32 * 68 + 32 * 33 // 2
    assert a.flops == 4 * 8 * 64 * pairs_full + 12 * 8 * 16 * 2 * 64
    assert b.flops == 4 * 8 * 64 * (16 + 32 * 16) + 6 * 8 * 16 * 2 * 64
    # the same layer with one KV head per query head reads 4x the blocks'
    # bytes
    mha = dataclasses.replace(full, kv_heads=8)
    assert work.kernel_call_work(mha, GEO, reqs).bytes == 4 * 12 * tile \
        + q_rows


def test_attention_flops_sum_each_layers_visible_pairs():
    full = work.Layer(heads=4, kv_heads=2, head_dim=16, window=None,
                      itemsize=2)
    win = dataclasses.replace(full, window=16)
    live = [(1, 70), (32, 100)]
    per = 4 * 4 * 16
    assert work.attention_flops([win, win, win, full], live) == per * (
        3 * (16 + 32 * 16) + 70 + 32 * 68 + 32 * 33 // 2)
