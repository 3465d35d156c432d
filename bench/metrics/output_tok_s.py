"""Generated tokens committed in the window over the window's length."""
from bench.stats import in_window


def read(run):
    n = sum(1 for r in run.records for t in r.token_t if in_window(run, t))
    return n / (run.t_close - run.t_open)
