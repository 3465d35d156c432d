"""Kernel: the least time the chip needs for the paged kernel's live work
in the window, over the device time of the kernel's events.

Live work per call (one layer of one step): the running requests' resident
blocks (K, V and four checksum tiles each) and fed query rows
(:func:`bench.work.kernel_call_work`), never the padded table entries the
kernel's grid also walks. Each call's least time is the larger of its
FLOPs over the peak rate and its bytes over the peak bandwidth; ``bound``
says which bound the larger share of that time."""
from bench import work

# the served step's one Pallas kernel; the program gives it no name of its
# own, so its events are known by the custom call's target
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or not run.exact or not run.steps:
        return None
    kernel_s = run.trace.seconds_matching(KERNEL)
    if kernel_s <= 0:
        return None
    layers = run.cfg["n_layer"]
    least = {"compute": 0.0, "memory": 0.0}
    for s in run.steps:
        t, bound = work.kernel_call_work(run.cfg, run.geometry,
                                         s.live).least_seconds(run.peaks)
        least[bound] += layers * t
    return {"value": 100.0 * sum(least.values()) / kernel_s,
            "bound": max(least, key=least.get)}
