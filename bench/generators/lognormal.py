"""Log-normal request lengths, in a closed or an open loop.

A traffic file (``bench/traffic/<name>.json``) that names
``"generator": "lognormal"`` gives:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when the last one finished) or ``"open"`` (Poisson arrivals at
  ``rate_per_s``);
- ``prompt`` and ``output``: log-normal lengths, ``median``, ``sigma``,
  clipped to ``[min, max]``;
- ``initial_s`` (open loop): at the start, ``rate_per_s * initial_s``
  requests are already in flight, as a closed loop's clients are.

Every seed gets the same work in the same order: lengths and arrival gaps
are stratified quantiles of their distribution, ``BLOCK`` at a time, in an
order that does not depend on the seed. Only the token ids come from the
seed. So runs with different seeds differ by the text of the requests, not
by the amount or the timing of the work.
"""
from __future__ import annotations

import statistics
from typing import Tuple

import numpy as np

BLOCK = 64          # draws per stratified block
ORDER_SEED = 0      # the one order of lengths and gaps, for every seed
_NORMAL = statistics.NormalDist()


def quantiles(n: int) -> np.ndarray:
    """The midpoints of ``n`` equal strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Clipped log-normal lengths at the probabilities ``u``."""
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


class Stream:
    """The requests of one traffic mix and seed, in order, without end.

    ``next_request()`` gives ``(prompt tokens, max_new_tokens)``;
    ``next_arrival()`` (open loop) the offset in seconds of the next
    arrival from the start of the traffic. ``n_initial`` requests are in
    flight at the start (a closed loop's clients; an open loop's
    ``rate_per_s * initial_s``): ``first_request(i)`` gives the ``i``-th, cut
    to a stratified share of its lengths, so that they stand at spread
    phases of a request as in a steady state.
    ``max_total`` caps prompt plus output (the engine's context).
    """

    def __init__(self, traffic: dict, seed: int, *, vocab: int,
                 max_total: int):
        self.traffic = traffic
        self.vocab = vocab
        self.max_total = max_total
        self._len_rng = _rng(ORDER_SEED, 1)
        self._arr_rng = _rng(ORDER_SEED, 3)
        self._tok_rng = _rng(seed, 2)
        self._lengths: list = []
        self._gaps: list = []
        self._t_unit = 0.0          # cumulative unit-rate arrival time
        if traffic["loop"] == "closed":
            self.n_initial = int(traffic["clients"])
        else:
            self.n_initial = int(round(float(traffic["rate_per_s"])
                                       * float(traffic.get("initial_s", 0))))
        self._first_share = _rng(ORDER_SEED, 4).permutation(
            quantiles(self.n_initial))

    def _refill_lengths(self) -> None:
        u = quantiles(BLOCK)
        p = self._len_rng.permutation(lognormal_lengths(self.traffic["prompt"],
                                                        u))
        o = self._len_rng.permutation(lognormal_lengths(self.traffic["output"],
                                                        u))
        self._lengths = list(zip(p.tolist(), o.tolist()))[::-1]

    def _make(self, n_prompt: int, n_out: int) -> Tuple[np.ndarray, int]:
        n_prompt = max(1, min(n_prompt, self.max_total - 1))
        n_out = max(1, min(n_out, self.max_total - n_prompt))
        toks = self._tok_rng.integers(0, self.vocab, size=n_prompt,
                                      dtype=np.int32)
        return toks, n_out

    def next_request(self) -> Tuple[np.ndarray, int]:
        if not self._lengths:
            self._refill_lengths()
        return self._make(*self._lengths.pop())

    def first_request(self, i: int) -> Tuple[np.ndarray, int]:
        if not self._lengths:
            self._refill_lengths()
        n_prompt, n_out = self._lengths.pop()
        share = float(self._first_share[i])
        return self._make(int(round(share * n_prompt)),
                          int(round(share * n_out)))

    def next_arrival(self) -> float:
        if not self._gaps:
            gaps = -np.log1p(-quantiles(BLOCK))        # unit-mean Exp
            self._gaps = self._arr_rng.permutation(gaps).tolist()
        self._t_unit += self._gaps.pop()
        return self._t_unit / float(self.traffic["rate_per_s"])
