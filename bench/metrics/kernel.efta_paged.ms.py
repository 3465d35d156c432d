"""Kernel: device milliseconds in the paged kernel's events per engine
step of the traced window (all layers). The kernel is found by its
``pallas_call`` name, so no other custom call of the step is counted."""

KERNEL = "efta_paged"


def read(run):
    if run.trace is None or not run.steps:
        return None
    kernel_s = run.trace.seconds_named(KERNEL)
    return 1e3 * kernel_s / len(run.steps) if kernel_s > 0 else None
