"""Scheduler: requests that took part in an engine step, as a share of the
engine's slots, averaged over the window's steps."""


def read(run):
    if not run.steps:
        return None
    n = run.geometry["n_slots"]
    return 100.0 * sum(s.running for s in run.steps) / (n * len(run.steps))
