"""Work counts from shapes: the attention of each layer, and the live work
of one call of the paged attention kernel.

Everything here is arithmetic on the layers an architecture module gives
(``bench/arch/<model_type>.py`` ``layers(cfg)``) and on what the requests
hold. None of it depends on how the engine pads its tables: the kernel's
live work is the running requests' resident blocks and the rows they feed,
whatever grid the kernel walks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Layer:
    """The attention of one layer, as the paged kernel serves it."""

    heads: int                  # query heads
    kv_heads: int               # heads of the K and V the pool holds
    head_dim: int
    window: Optional[int]       # keys a query sees, itself included;
    #                             None: every earlier key
    itemsize: int               # bytes of one element of q, K and V


def itemsize(dtype: str) -> int:
    """Bytes of one element of the dtype named ``dtype``."""
    import ml_dtypes
    return np.dtype(getattr(ml_dtypes, dtype, None) or dtype).itemsize


def visible_pairs(rows: int, kv_len: int,
                  window: Optional[int] = None) -> int:
    """Query-key pairs of a chunk of ``rows`` causal rows that end at
    ``kv_len`` resident tokens (the chunk's own rows included). With a
    ``window``, a row sees at most ``window`` keys, its own included."""
    def tri(n):                  # 1 + 2 + ... + n
        return n * (n + 1) // 2

    lo = kv_len - rows           # the rows see 1 + lo, ..., kv_len keys
    if window is None or window >= kv_len:
        return tri(kv_len) - tri(lo)
    cap = max(lo, window)
    return tri(cap) - tri(lo) + window * (kv_len - cap)


def resident_blocks(rows: int, kv_len: int, block_size: int,
                    window: Optional[int] = None) -> int:
    """Blocks of ``kv_len`` resident tokens that hold a key some row of the
    chunk sees: all of them, or with a ``window`` those from the block of
    the earliest row's first visible key."""
    first = 0 if window is None else max(
        0, (kv_len - rows - window + 1) // block_size)
    return max(0, math.ceil(kv_len / block_size) - first)


def attention_flops(layers: Sequence[Layer],
                    live: Iterable[Tuple[int, int]]) -> int:
    """FLOPs of QK^T and PV over the visible pairs of ``live`` ``(rows
    fed, resident tokens)`` in every layer: 4 FLOPs per pair per head
    dimension per query head."""
    live = list(live)
    return sum(4 * lay.heads * lay.head_dim
               * sum(visible_pairs(r, k, lay.window) for r, k in live)
               for lay in layers)


@dataclasses.dataclass(frozen=True)
class KernelWork:
    flops: int
    bytes: int

    def least_seconds(self, peak: dict) -> Tuple[float, str]:
        """The least time a chip with ``peak`` needs, and what bounds it."""
        t_f = self.flops / peak["flops_per_s"]
        t_b = self.bytes / peak["hbm_bytes_per_s"]
        return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def kernel_call_work(layer: Layer, geometry: dict,
                     requests: Iterable[Tuple[int, int]]) -> KernelWork:
    """Live work of one call of the paged kernel (one layer, one step).

    ``requests``: ``(rows fed, resident tokens after the append)`` of each
    running request that feeds rows. ``geometry`` gives ``block_size`` and
    ``check_stride``; any other key, such as the table width, is ignored.

    Bytes: every resident block inside the layer's window
    (:func:`resident_blocks`), its K and V tiles and its four checksum
    tiles (``check_stride`` rows each) for each KV head, read once; the fed
    query rows read and the output rows written, for each query head.
    FLOPs: QK^T and PV over the visible pairs, and the re-fold of each
    streamed block's two checksums for K and for V (2 FLOPs per element per
    checksum).
    """
    bs, cs = geometry["block_size"], geometry["check_stride"]
    q_row = layer.heads * layer.head_dim
    kv_row = layer.kv_heads * layer.head_dim
    flops = nbytes = 0
    for rows, kv_len in requests:
        blocks = resident_blocks(rows, kv_len, bs, layer.window)
        nbytes += blocks * (2 * bs + 4 * cs) * kv_row * layer.itemsize
        nbytes += 2 * rows * q_row * layer.itemsize
        flops += 4 * q_row * visible_pairs(rows, kv_len, layer.window)
        flops += blocks * 2 * 2 * 2 * bs * kv_row
    return KernelWork(flops=flops, bytes=nbytes)


def pool_bytes_per_token(layers: Sequence[Layer], geometry: dict) -> int:
    """KV and checksum bytes the pool holds per token, all layers."""
    return int(sum(
        (2 + 4 * geometry["check_stride"] / geometry["block_size"])
        * lay.kv_heads * lay.head_dim * lay.itemsize for lay in layers))
