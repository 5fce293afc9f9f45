"""attn_roofline: the attention core's share of the chips' bf16 peak,
where the core runs as one fused kernel (multi-head latent attention).

The core's model FLOPs in the traced steps over the device time of the
fused kernel's operations, summed over the chips, times the chip's peak
(``peaks.json``).  The FLOPs are counted from the configuration and the
mix: exact causal, forward and backward, no recomputation, every layer;
per token and layer 3 x 2 x heads x (query-key width + value width) x
(S + 1) / 2.  The operations are JAX's splash-attention kernels, found by
the names the trace gives them (``splash_mha_fwd*``, ``splash_mha_dq*``,
``splash_mha_dkv*``).  Their time holds every forward the step runs (the
recomputations too) and the masked halves of the diagonal blocks, so the
share stays under 100%.  Where no such operation ran (the chunked core of
an older program) it reads nothing.  Layer: attention core (MLA)
(``models/attention.py`` ``fused_causal_attention``).  Moves
``train_tokens_per_s``.
"""
import re

import peaks
import trace_reduce

SPLASH = re.compile(r"splash_m[hq]a_(fwd|dq|dkv)")
WIDTHS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def kernel_s(trace) -> float:
    """Device seconds of the fused attention kernels, over the chips."""
    return sum(t for dev in trace.devices.values()
               for name, t in dev.op_time.items()
               if SPLASH.match(name)) * 1e-9


def core_flops_per_token(config: dict, seq_len: int):
    """The latent attention core's model FLOPs a token, forward and
    backward; None for a configuration without latent attention."""
    if not all(k in config for k in WIDTHS):
        return None
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return (config["num_hidden_layers"] * 3 * 2
            * config["num_attention_heads"] * width * (seq_len + 1) / 2)


def read(ctx):
    per_token = core_flops_per_token(ctx.cell.config,
                                     ctx.cell.mix["seq_len"])
    execs = trace_reduce.step_executions(ctx.trace)
    steps = len(execs.get(min(execs), [])) if execs else 0
    device_s = kernel_s(ctx.trace)
    if per_token is None or not steps or device_s <= 0:
        return None
    flops = steps * ctx.tokens_per_step() * per_token
    peak = peaks.lookup(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (device_s * peak), "%"
