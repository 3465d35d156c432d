"""Work counts from shapes: parameters, FLOPs per token, and the live work
of one call of the paged attention kernel.

Everything here is arithmetic on a configuration file's sizes and on what
the requests hold. None of it depends on how the engine pads its tables:
the kernel's live work is the running requests' resident blocks and the
rows they feed, whatever grid the kernel walks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    head_dim: int
    d_ff: int
    vocab: int
    positions: int
    itemsize: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        d = cfg["n_embd"]
        return cls(layers=cfg["n_layer"], d_model=d, heads=cfg["n_head"],
                   head_dim=d // cfg["n_head"], d_ff=cfg["n_inner"] or 4 * d,
                   vocab=cfg["vocab_size"], positions=cfg["n_positions"],
                   itemsize=np.dtype(_np_dtype(cfg["dtype"])).itemsize)


def _np_dtype(name: str):
    import ml_dtypes
    return getattr(ml_dtypes, name, None) or np.dtype(name)


def param_count(cfg: dict) -> int:
    """All parameters: embeddings, per-layer attention and MLP (no
    biases), LayerNorm gains and biases."""
    m = Dims.of(cfg)
    d = m.d_model
    per_layer = 4 * d * d + 2 * d * m.d_ff + 4 * d
    return m.vocab * d + m.positions * d + m.layers * per_layer + 2 * d


def matmul_flops_per_token(cfg: dict) -> int:
    """2 FLOPs per weight of every matrix product one token passes:
    q, k, v, o and the two MLP maps in each layer, and the LM head."""
    m = Dims.of(cfg)
    d = m.d_model
    return 2 * (m.layers * (4 * d * d + 2 * d * m.d_ff) + d * m.vocab)


def attention_flops(cfg: dict, pairs: int) -> int:
    """FLOPs of QK^T and PV over ``pairs`` (query row, visible key) pairs,
    in all layers: 4 FLOPs per pair per head dimension per head."""
    m = Dims.of(cfg)
    return 4 * m.layers * m.heads * m.head_dim * int(pairs)


def visible_pairs(rows: int, kv_len: int) -> int:
    """Query-key pairs of a chunk of ``rows`` causal rows that end at
    ``kv_len`` resident tokens (the chunk's own rows included)."""
    before = kv_len - rows
    return rows * before + rows * (rows + 1) // 2


@dataclasses.dataclass(frozen=True)
class KernelWork:
    flops: int
    bytes: int

    def least_seconds(self, peak: dict) -> Tuple[float, str]:
        """The least time a chip with ``peak`` needs, and what bounds it."""
        t_f = self.flops / peak["flops_per_s"]
        t_b = self.bytes / peak["hbm_bytes_per_s"]
        return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def kernel_call_work(cfg: dict, geometry: dict,
                     requests: Iterable[Tuple[int, int]]) -> KernelWork:
    """Live work of one call of the paged kernel (one layer, one step).

    ``requests``: ``(rows fed, resident tokens after the append)`` of each
    running request that feeds rows. ``geometry`` gives ``block_size`` and
    ``check_stride``; any other key, such as the table width, is ignored.

    Bytes: every resident block's K and V tiles and its four checksum tiles
    (``check_stride`` rows each), read once; the fed query rows read and
    the output rows written. FLOPs: QK^T and PV over the visible pairs,
    and the re-fold of each streamed block's two checksums for K and for V
    (2 FLOPs per element per checksum).
    """
    m = Dims.of(cfg)
    bs, cs = geometry["block_size"], geometry["check_stride"]
    hkv = m.heads                   # GPT-2: one KV head per query head
    tile = hkv * m.head_dim * m.itemsize
    flops = nbytes = 0
    for rows, kv_len in requests:
        blocks = math.ceil(kv_len / bs)
        nbytes += blocks * (2 * bs + 4 * cs) * tile
        nbytes += 2 * rows * m.heads * m.head_dim * m.itemsize
        flops += 4 * m.heads * m.head_dim * visible_pairs(rows, kv_len)
        flops += blocks * 2 * 2 * 2 * bs * hkv * m.head_dim
    return KernelWork(flops=flops, bytes=nbytes)


def pool_bytes_per_token(cfg: dict, geometry: dict) -> int:
    """KV and checksum bytes the pool holds per token, all layers."""
    m = Dims.of(cfg)
    per_layer = (2 + 4 * geometry["check_stride"] / geometry["block_size"]) \
        * m.heads * m.head_dim * m.itemsize
    return int(m.layers * per_layer)
