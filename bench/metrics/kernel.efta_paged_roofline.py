"""Kernel: the least time the chip needs for the paged kernel's live work
in the window, over the device time of the kernel's events (found by its
``pallas_call`` name, so no other custom call of the step is counted).

Live work per call (one layer of one step): the running requests' resident
blocks inside the layer's window (K, V and four checksum tiles each, per
KV head) and fed query rows (:func:`bench.work.kernel_call_work`), never
the padded table entries the kernel's grid also walks. Each call's least
time is the larger of its FLOPs over the peak rate and its bytes over the
peak bandwidth, summed over the layers; ``bound`` says which bound the
larger share of that time."""
import collections

from bench import work

KERNEL = "efta_paged"


def read(run):
    if run.trace is None or not run.exact or not run.steps:
        return None
    kernel_s = run.trace.seconds_named(KERNEL)
    if kernel_s <= 0:
        return None
    kinds = collections.Counter(run.layers)       # layer -> how many
    least = {"compute": 0.0, "memory": 0.0}
    for s in run.steps:
        for layer, n in kinds.items():
            t, bound = work.kernel_call_work(layer, run.geometry,
                                             s.live).least_seconds(run.peaks)
            least[bound] += n * t
    return {"value": 100.0 * sum(least.values()) / kernel_s,
            "bound": max(least, key=least.get)}
