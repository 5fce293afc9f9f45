"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid ``(B, H, S/chunk)`` with the chunk dimension innermost (sequential);
the inter-chunk SSM state (P, N) lives in VMEM scratch across chunk steps.
Each grid step computes the intra-chunk quadratic term (chunk x chunk decay
matrix on the MXU) plus the carried-state contribution, then updates the
state — the exact blocking of the SSD paper adapted to (8,128)-lane VMEM
tiles.

The wrapper moves the head axis ahead of the sequence, so that every block
ends in ``(chunk, P)``, ``(chunk, N)``, ``(chunk, 1)`` or ``(1, chunk)``:
aligned to the (8,128) tiling or equal to the full dimension.  The per-head
log-decay ``dt * a`` is computed outside the kernel and passed twice, as a
column and as a row, so that its within-chunk prefix sums come out in both
orientations from masked reductions (no cumsum, no transpose in-kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dacol_ref, darow_ref, b_ref, c_ref, y_ref,
                state_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)              # (q, P)
    dt = dt_ref[0, 0]                                # (q, 1)
    da_col = dacol_ref[0, 0]                         # (q, 1)
    da_row = darow_ref[0, 0]                         # (1, q)
    b = b_ref[0].astype(jnp.float32)                 # (q, N)
    c = c_ref[0].astype(jnp.float32)                 # (q, N)

    # within-chunk prefix sums of da, as a column and as a row
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iq >= ik
    seg_col = jnp.sum(jnp.where(lower, da_row, 0.0), axis=1,
                      keepdims=True)                 # (q, 1)
    seg_row = jnp.sum(jnp.where(iq <= ik, da_col, 0.0), axis=0,
                      keepdims=True)                 # (1, q)
    total = jnp.sum(da_row, axis=1, keepdims=True)   # (1, 1)
    xdt = x * dt

    # intra-chunk: (C B^T ⊙ decay) X
    decay = jnp.where(lower, jnp.exp(seg_col - seg_row), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(cb * decay, xdt,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: C h_in, with per-position decay from the chunk start
    state = state_scr[...]                           # (P, N)
    y_inter = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(seg_col)

    # state update: h_out = e^total h_in + (X ⊙ rem)^T B
    rem = jnp.exp(total - seg_col)                   # (q, 1)
    bx = jax.lax.dot_general(xdt * rem, b,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(total) + bx

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan(x, dt, a_log, b, c, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); b,c: (B,S,N) -> y (B,S,H,P)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)             # (B, H, S)
    da = dt * -jnp.exp(a_log.astype(jnp.float32))[None, :, None]
    grid = (bsz, h, s // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    col = pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0))
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt[..., None], da[..., None],
      da[:, :, None, :], b, c)
    return y.transpose(0, 2, 1, 3)
