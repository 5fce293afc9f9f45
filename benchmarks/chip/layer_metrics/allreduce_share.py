"""allreduce_share: device time of the all-reduce, reduce-scatter and
all-gather operations XLA put into the sharded train step, over the train
step's device time, both summed over the chips.  Layer: the data-parallel
exchange.  Moves ``train_tokens_per_s``.
"""
import trace_reduce


def read(ctx):
    coll, step = trace_reduce.collective_s(ctx.trace)
    if coll <= 0 or step <= 0:
        return None
    return 100.0 * coll / step, "%"
