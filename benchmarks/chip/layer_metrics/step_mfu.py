"""step_mfu: the whole train step's share of the chips' bf16 peak.

Model FLOPs of the traced steps (the reference's ``flops_per_token``, which
counts no recomputation) over the device time of the train step's
executions, summed over the chips that ran them, times the chip's peak
(``peaks.json``).  Layer: the model step (``runtime/trainer.py`` train
step through ``models/`` and ``optim/adamw.py``).  Moves
``train_tokens_per_s``.
"""
import peaks
import trace_reduce


def read(ctx):
    execs = trace_reduce.step_executions(ctx.trace)
    device_s = sum(e.end - e.start for evs in execs.values()
                   for e in evs) * 1e-9
    steps = len(execs.get(min(execs), [])) if execs else 0
    if not steps or device_s <= 0:
        return None
    flops = steps * ctx.tokens_per_step() * ctx.flops_per_token()
    peak = peaks.lookup(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (device_s * peak), "%"
