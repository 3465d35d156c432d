"""Scheduler: 90th percentile, over the requests due in the window, of the
time from when a request was due to the start of the engine step that gave
it a slot. A request never admitted counts as infinitely late."""
import math

from bench.stats import nearest_rank


def read(run):
    wait = [(r.admit_t - r.due) if r.admit_t is not None else math.inf
            for r in run.records if run.t_open <= r.due < run.t_close]
    return 1e3 * nearest_rank(wait, 0.90) if wait else None
