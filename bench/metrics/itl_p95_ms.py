"""95th percentile of every gap between two consecutive output tokens of a
request, both committed in the window (host clock at step ends)."""
from bench.stats import in_window, nearest_rank


def read(run):
    gaps = [b - a for r in run.records
            for a, b in zip(r.token_t, r.token_t[1:])
            if in_window(run, a) and in_window(run, b)]
    return 1e3 * nearest_rank(gaps, 0.95) if gaps else None
