"""Pallas TPU kernel for the RG-LRU gated linear recurrence
(RecurrentGemma / Griffin):

    h_t = a_t ⊙ h_{t-1} + x_t          (all elementwise, width W)

Sequential in t, parallel over (batch, width):

  * grid = (B, W / BLOCK_W, S / BLOCK_T) — time is the LAST (sequential)
    grid axis so the (1, BLOCK_W) hidden state persists in VMEM scratch
    across time tiles;
  * a/x stream in (BLOCK_T, BLOCK_W) tiles; every step is one fused
    multiply-add row — pure VPU elementwise throughput, the TPU analogue
    of the paper's fused GPU scan;
  * width tiles are independent (grid axis 1), so the kernel scales to
    the model-parallel sharded width without code changes.

Oracle: ``repro.models.blocks_rnn.rglru_scan`` (ref.py re-exports).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_W = 256


def _rglru_kernel(a_ref, x_ref, h0_ref, y_ref, h_ref, *, block_t: int):
    t_tile = pl.program_id(2)

    @pl.when(t_tile == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    def step(i, h):
        h = a_ref[0, i, :] * h + x_ref[0, i, :]
        y_ref[0, i, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[0, :])
    h_ref[0, :] = h


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_w", "interpret"))
def rglru(a: jax.Array, x: jax.Array, h0: jax.Array, *,
          block_t: int = DEFAULT_BLOCK_T,
          block_w: int = DEFAULT_BLOCK_W,
          interpret: bool):
    """a, x: (B, S, W) f32; h0: (B, W) f32.
    Returns (h_all (B, S, W) f32, h_final (B, W) f32)."""
    b, s, w = a.shape
    block_t = min(block_t, s)
    block_w = min(block_w, w)
    assert s % block_t == 0 and w % block_w == 0, (s, w)

    def t_map(bb, wb, tt):
        return (bb, tt, wb)

    def h_map(bb, wb, tt):
        return (bb, wb)

    y, h = pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t),
        grid=(b, w // block_w, s // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), t_map),
            pl.BlockSpec((1, block_t, block_w), t_map),
            pl.BlockSpec((1, block_w), h_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), t_map),
            pl.BlockSpec((1, block_w), h_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, w), jnp.float32),
            jax.ShapeDtypeStruct((b, w), jnp.float32),
        ],
        interpret=interpret,
    )(a, x, h0)
    return y, h
