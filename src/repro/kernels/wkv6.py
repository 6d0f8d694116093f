"""Pallas TPU kernel for the RWKV6 (Finch) WKV recurrence.

The recurrence per head (state S in R^{N x N}, N = head size 64):

    y_t = r_t @ (S + u ⊙ (k_t v_t^T))
    S   = diag(w_t) S + k_t v_t^T

It is sequential in t but embarrassingly parallel over (batch x heads),
so:

  * grid = (B * H, S / BLOCK_T)
  * the (N, N) f32 state lives in a VMEM scratch accumulator that
    PERSISTS across the time-tile grid dimension (TPU grid iteration is
    sequential over the last axis, the standard Pallas accumulation
    idiom), so the state never round-trips to HBM between tiles;
  * r/k/v/w stream through VMEM in (BLOCK_T, N) tiles;
  * each step is rank-1 outer-product + matvec on (64, 64) f32 — VPU
    work with the state held on-chip, which is exactly what the CUDA
    kernel in the RWKV repo does with shared memory (DESIGN.md §2).

Oracle: ``repro.models.blocks_rnn.wkv_scan`` (ref.py re-exports).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_T = 128


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_ref, *,
                block_t: int):
    """One (batch*head, time-tile) program; state persists over tiles."""
    t_tile = pl.program_id(1)

    @pl.when(t_tile == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0, :]                       # (N,)

    def step(i, state):
        r = r_ref[0, i, :]                # (N,)
        k = k_ref[0, i, :]
        v = v_ref[0, i, :]
        w = w_ref[0, i, :]
        kv = k[:, None] * v[None, :]      # (N, N) outer product
        y = ((state + u[:, None] * kv) * r[:, None]).sum(axis=0)  # (N,)
        y_ref[0, i, :] = y.astype(y_ref.dtype)
        return w[:, None] * state + kv

    state = jax.lax.fori_loop(0, block_t, step, state_ref[0, :, :])
    state_ref[0, :, :] = state


@functools.partial(jax.jit,
                   static_argnames=("block_t", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, block_t: int = DEFAULT_BLOCK_T,
         interpret: bool):
    """r/k/v/w: (B, S, H, N) f32; u: (H, N) f32.
    Returns (y (B, S, H, N) f32, final state (B, H, N, N) f32)."""
    b, s, h, n = r.shape
    block_t = min(block_t, s)
    assert s % block_t == 0, (s, block_t)

    def bh(x):  # (B, S, H, N) -> (B*H, S, N)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, n)

    rf, kf, vf, wf = bh(r), bh(k), bh(v), bh(w)
    uf = jnp.broadcast_to(u[None, :, :], (b, h, n)).reshape(b * h, n)

    def t_map(g, tt):
        return (g, tt, 0)

    y, state = pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=block_t),
        grid=(b * h, s // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, n), t_map),
            pl.BlockSpec((1, block_t, n), t_map),
            pl.BlockSpec((1, block_t, n), t_map),
            pl.BlockSpec((1, block_t, n), t_map),
            pl.BlockSpec((1, n), lambda g, tt: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, n), t_map),
            pl.BlockSpec((1, n, n), lambda g, tt: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, n), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n, n), jnp.float32),
        ],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)

    y = y.reshape(b, h, s, n).transpose(0, 2, 1, 3)
    return y, state.reshape(b, h, n, n)
