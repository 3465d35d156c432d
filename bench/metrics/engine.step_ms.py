"""Engine step: the traced window over the engine steps that ended in it."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    return 1e3 * run.trace.window_s / len(run.steps)
