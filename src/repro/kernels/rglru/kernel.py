"""Pallas TPU kernel for the RG-LRU linear recurrence.

Grid ``(B, W/block_w, S/chunk)`` with the chunk dimension innermost
(sequential on TPU); the recurrent state (1, block_w) persists in VMEM
scratch.  Within a chunk the recurrence loads aligned tiles of ``rows``
time steps (8 for 32-bit inputs, 16 for 16-bit ones: one packed sublane
tile), steps through the tile's rows in registers as (1, block_w) vector
ops on the VPU, and writes the tile back through an fp32 staging buffer.
The width axis rides the 128-lane dimension, so a block_w of 512 keeps 4
full vector registers busy per step while HBM traffic stays at exactly 2
reads + 1 write per element (the roofline floor for a gated scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, state_scr, tile_scr, *, chunk: int,
                  rows: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def tile(i, h):
        t0 = pl.multiple_of(i * rows, rows)
        a = a_ref[0, pl.ds(t0, rows), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(t0, rows), :].astype(jnp.float32)
        for r in range(rows):
            h = a[r:r + 1] * h + b[r:r + 1]
            tile_scr[r:r + 1, :] = h
        h_ref[0, pl.ds(t0, rows), :] = tile_scr[...].astype(h_ref.dtype)
        return h

    state_scr[...] = jax.lax.fori_loop(0, chunk // rows, tile,
                                       state_scr[...])


def rglru_scan_pallas(a, b, *, chunk: int = 256, block_w: int = 512,
                      interpret: bool = False):
    """a, b: (B, S, W) -> h: (B, S, W)."""
    bsz, s, w = a.shape
    chunk = min(chunk, s)
    block_w = min(block_w, w)
    rows = 8 * 4 // jnp.dtype(a.dtype).itemsize
    assert s % chunk == 0 and w % block_w == 0 and chunk % rows == 0
    grid = (bsz, w // block_w, s // chunk)
    kernel = functools.partial(_rglru_kernel, chunk=chunk, rows=rows)
    spec = pl.BlockSpec((1, chunk, block_w), lambda ib, iw, ic: (ib, ic, iw))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32),
                        pltpu.VMEM((rows, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b)
