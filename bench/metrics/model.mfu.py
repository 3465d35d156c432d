"""Model step: useful FLOPs of the window over the chip's peak for the
traced window. Useful FLOPs are each fed row's matrix products (2 per
weight a token passes, LM head included: the architecture module's
``matmul_flops_per_token``) and, in every layer, its attention over the
tokens it can see there; padding rows, idle slots and the grid the kernel
walks do not count."""
from bench import work


def read(run):
    if run.trace is None or not run.exact or not run.steps:
        return None
    rows = sum(s.rows for s in run.steps)
    flops = rows * run.arch.matmul_flops_per_token(run.cfg) \
        + work.attention_flops(run.layers,
                               [p for s in run.steps for p in s.live])
    return 100.0 * flops / (run.trace.window_s * run.peaks["flops_per_s"])
