"""90th percentile, over every request due in the window, of the time from
when it was due to when its first token was committed. A request never
served counts as infinitely late."""
import math

from bench.stats import nearest_rank


def read(run):
    ttft = [(r.first_t - r.due) if r.first_t is not None else math.inf
            for r in run.records if run.t_open <= r.due < run.t_close]
    return 1e3 * nearest_rank(ttft, 0.90) if ttft else None
