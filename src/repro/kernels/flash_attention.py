"""Pallas TPU flash attention (train/prefill hot spot).

Online-softmax attention with explicit VMEM tiling:

  * grid = (batch * q_heads, Sq / BLOCK_Q)
  * each program holds one (BLOCK_Q, D) query tile, the (BLOCK_Q,)
    running max/denominator and the (BLOCK_Q, D) output accumulator in
    VMEM scratch, and streams (BLOCK_K, D) key/value tiles through a
    ``fori_loop``;
  * causal masking skips fully-masked KV tiles (the loop upper bound is
    derived from the q tile index), so FLOPs stay at ~S^2/2;
  * GQA reads the kv head ``h // group`` straight from the BlockSpec
    index map — repeated KV heads are never materialized.

Block sizes default to (512, 512): at D=128 a program's working set is
q(512x128x4) + k,v(2x512x128x4) + acc(512x128x4) + stats ~= 1 MB of
VMEM — comfortably under the ~16 MB/core budget with double buffering.
MXU dims (BLOCK x D) are multiples of 128.

Oracle: ``repro.models.attention.full_attention`` (ref.py re-exports).
Validated in interpret mode; ``interpret`` has no default (``ops``
resolves it from the platform).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *,
                  scale: float, causal: bool, block_k: int, s_k: int):
    """One (head, q-tile) program.  q_ref: (1, BQ, D); k/v_ref: full
    (1, Sk, D) rows of this head's KV (streamed in BK tiles below)."""
    q_tile = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]

    q = q_ref[0, :, :].astype(jnp.float32) * scale          # (BQ, D)

    m0 = jnp.full((bq,), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((bq,), dtype=jnp.float32)
    acc0 = jnp.zeros((bq, d), dtype=jnp.float32)

    if causal:
        # last kv tile that any query in this tile may attend to
        hi = ((q_tile + 1) * bq + block_k - 1) // block_k
        n_k = min if False else None  # noqa  (documentation aid)
        num_tiles = jnp.minimum(hi, s_k // block_k)
    else:
        num_tiles = s_k // block_k

    q_pos = q_tile * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(kt, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(kt * block_k, block_k), :]    # (BK, D)
        v = v_ref[0, pl.dslice(kt * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (BQ, BK)
        if causal:
            k_pos = kt * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_tiles, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    scale: float | None = None,
                    interpret: bool) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D), Hq % Hkv == 0.
    Returns (B, Hq, Sq, D) in q.dtype."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)

    def q_map(h, qt):
        return (h, qt, 0)

    def kv_map(h, qt):
        # GQA: query head h -> kv head h // group, batch-major layout
        return ((h // (hq)) * hkv + (h % hq) // group, 0, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_k=block_k, s_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sk, d), kv_map),
            pl.BlockSpec((1, sk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
