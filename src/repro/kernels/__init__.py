"""Pallas TPU kernels for attention and the two recurrences.

flash_attention -- causal / sliding-window / softcap / GQA attention
ssd             -- Mamba-2 state-space-duality chunked scan
rglru           -- RG-LRU gated linear recurrence

Each has kernel.py (pl.pallas_call + BlockSpec), ops.py (jitted wrapper
with CPU interpret fallback), ref.py (pure-jnp oracle used by the tests).
No model path calls them: the models run their XLA paths (attention,
``models/ssm.py``, ``models/rglru.py``) on every platform, and MLA's causal
core takes JAX's own splash-attention kernel on a TPU.  Only the tests and
``chip_smoke.py`` run these.
"""
