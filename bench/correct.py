"""Decide ``correct``: the served tokens against the float32 reference.

Once the window has closed, a sample of the requests the window finished
(drawn from the seed, the longest always in it) is run through the
reference once each, over the prompt and the tokens the engine served.
Every request is greedy, so each served token should be the reference's
best up to rounding: the number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
that position. Beside it: the sample's size, every finished request's
token count (each must have all the tokens it asked for), the requests
due in the window that got no first token within a minute of its close,
and the fault-tolerance layer on this clean traffic: no detection at any
EFTA site or resident KV block, and no step retried, since every one of
them would be a false alarm that rewrites a right value or repeats a step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float
    op: str                    # "<=" or ">="

    @property
    def ok(self) -> bool:
        if self.op == "<=":
            return bool(self.value <= self.limit)
        return bool(self.value >= self.limit)

    def line(self) -> str:
        return f"{self.name} {self.value!r} {self.op} {self.limit!r}"


def sample(records: list, spec: dict, seed: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until ``spec['tokens']`` served tokens or ``spec['requests']``
    requests are in the sample."""
    if not records:
        return []
    size = [r.prompt_len + len(r.req.generated) for r in records]
    longest = int(np.argmax(size))
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    order = [longest] + [i for i in rng.permutation(len(records))
                         if i != longest]
    out, tokens = [], 0
    for i in order:
        if len(out) >= spec["requests"] or tokens >= spec["tokens"]:
            break
        out.append(records[i])
        tokens += len(records[i].req.generated)
    return out


def served_gaps(logits, weights: dict, records: list, *, pad_to: int,
                low: Optional[str] = None):
    """Per sampled request, the reference gap of every served token, and
    with ``low`` the gap of the token the lower precision puts first at
    every position of the prompt and the served tokens. ``logits(w, seq,
    low=)`` is the architecture's reference forward for the cell's
    configuration."""
    import jax.numpy as jnp

    from bench import reference

    served, control = [], []
    for r in records:
        gen = np.asarray(r.req.generated, np.int32)
        seq = np.zeros((pad_to,), np.int32)
        n = r.prompt_len + len(gen)
        seq[:r.prompt_len] = np.asarray(r.req.prompt, np.int32)
        seq[r.prompt_len:n] = gen
        s, c = reference.gaps(weights, jnp.asarray(seq), logits=logits,
                              low=low)
        served.append(np.asarray(s)[r.prompt_len - 1:n - 1])
        if low is not None:
            control.append(np.asarray(c)[:n - 1])
    return served, control


def widest(gaps: list) -> float:
    """The largest gap over all sampled tokens (infinite with none)."""
    return float(max((g.max() for g in gaps if len(g)), default=math.inf))


def checks(*, gap_max: float, limits: dict, tokens: int, short: int,
           unserved: int, detections: int, retries: int) -> List[Check]:
    return [
        Check("gap_max", gap_max, limits["gap_max"], "<="),
        Check("sampled_tokens", tokens, limits["sampled_tokens"], ">="),
        Check("short_requests", short, 0, "<="),
        Check("unserved", unserved, 0, "<="),
        Check("detections", detections, 0, "<="),
        Check("retries", retries, 0, "<="),
    ]
