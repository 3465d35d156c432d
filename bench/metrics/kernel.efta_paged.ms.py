"""Kernel: device milliseconds in the paged kernel's events per engine
step of the traced window (all layers)."""

# the served step's one Pallas kernel; the program gives it no name of its
# own, so its events are known by the custom call's target
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or not run.steps:
        return None
    kernel_s = run.trace.seconds_matching(KERNEL)
    return 1e3 * kernel_s / len(run.steps) if kernel_s > 0 else None
