"""gmm_roofline: the routed experts' grouped matmuls' share of the chips'
bf16 peak.

The held experts' model FLOPs in the traced steps (the reference's
``routed_flops_per_token``: the held experts a token chooses on average,
forward and backward, no recomputation) over the device time of the
grouped matmul operations, summed over the chips, times the chip's peak
(``peaks.json``).  The operations are XLA's ragged-dot kernels, found by
the name the trace gives them (``ragged-dot-*``, its routing metadata
left out): the three forward products, which the backward pass runs
again, the input gradients and the weight gradients.  Layer: routed
experts (``models/moe.py``).  Moves ``train_tokens_per_s``.
"""
import re

import peaks
import trace_reduce

GMM = re.compile(r"ragged-dot(?!-metadata)")


def gmm_s(trace) -> float:
    """Device seconds of the grouped matmuls, summed over the chips."""
    return sum(t for dev in trace.devices.values()
               for name, t in dev.op_time.items()
               if GMM.match(name)) * 1e-9


def read(ctx):
    routed = getattr(ctx.cell.reference, "routed_flops_per_token", None)
    execs = trace_reduce.step_executions(ctx.trace)
    steps = len(execs.get(min(execs), [])) if execs else 0
    device_s = gmm_s(ctx.trace)
    if routed is None or not steps or device_s <= 0:
        return None
    flops = steps * ctx.tokens_per_step() * routed(ctx.cell.config)
    peak = peaks.lookup(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (device_s * peak), "%"
