"""idle_share: the share of the window in which the chips the job held ran
no operation (1 - union of operation intervals over held time, summed over
the chips; the job holds the first ``slices`` chips).  Layer: the device.
Moves ``train_tokens_per_s``.
"""
import trace_reduce


def read(ctx):
    held = trace_reduce.held_intervals(ctx.trace, ctx.slices0)
    if not any(trace_reduce.busy(ctx.trace, d) for d in held):
        return None
    return 100.0 * trace_reduce.idle_share(ctx.trace, held), "%"
