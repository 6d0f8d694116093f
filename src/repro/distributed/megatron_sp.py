"""Explicit Megatron-style sequence-parallel transitions.

With activations sequence-sharded between blocks and heads/d_ff
TP-sharded inside them, the mathematically right collective after the
attention-out / MLP-down projections is a **reduce-scatter** over the
sequence axis (bf16, 1/TP of the bytes of a full all-reduce).  The
SPMD partitioner is free to emit an all-reduce + slice instead — and
XLA:CPU always does (this build never creates reduce-scatters; see
EXPERIMENTS.md §Perf H2) — promoting the operand to f32 on the way,
which quadruples the dominant collective term of the dense train
cells.

These helpers make the transition explicit with ``shard_map`` +
``jax.lax.psum_scatter`` so the collective schedule is what a TPU
deployment would run, independent of backend pass availability:

  out_project_rs   y = einsum(h, w)  -> reduce-scatter(seq)
                   (FSDP weight shards are all-gathered inside, which
                   is the ZeRO-3 gather XLA would insert anyway.)

Differentiable: the transpose of psum_scatter is all-gather and vice
versa, so the backward pass gets the mirrored schedule for free.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import ShardingRules


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def sp_enabled(rules: ShardingRules, seq: int,
               batch: Optional[int] = None) -> bool:
    """SP transitions apply when the rules sequence-shard activations
    over a real model axis that divides the sequence length, and (when
    given) the batch divides the DP axes — shard_map requires exact
    divisibility where pjit would pad."""
    mesh = rules.mesh
    if "model" not in mesh.shape or mesh.shape["model"] == 1:
        return False
    if rules.rules.get("seq") != ("model",):
        return False
    if batch is not None:
        dp = _dp_axes(mesh)
        n = 1
        for a in dp:
            n *= mesh.shape[a]
        if dp and batch % n != 0:
            return False
    return seq % mesh.shape["model"] == 0


def out_project_rs(h: jax.Array, w: jax.Array, *, rules: ShardingRules,
                   contract: str, batch_sharded: bool = True) -> jax.Array:
    """TP out-projection with an explicit reduce-scatter over sequence.

    contract="hkd": h (B, S, H, K) head-sharded,  w (H, K, D)
    contract="fd":  h (B, S, F)   d_ff-sharded,   w (F, D)

    Weights may be FSDP-sharded on their d_model axis (ZeRO-3); the
    shard is all-gathered over the DP axes inside, exactly the gather
    XLA inserts for the implicit path.  Returns (B, S/TP, D) sequence-
    sharded bf16 — the inter-block layout.
    """
    mesh = rules.mesh
    dp = _dp_axes(mesh)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if batch_sharded and dp \
        else None

    if contract == "hkd":
        w_spec = rules.spec_for(("heads", "head_dim", "d_model"), w.shape)
        # h's head axis mirrors the weight's (replicated when heads
        # don't divide TP, e.g. recurrentgemma's 10 heads on 16)
        h_spec = P(dp_spec, None, w_spec[0], None)
        eins = "bshk,hkd->bsd"
        w_dm_axis = 2
    elif contract == "fd":
        w_spec = rules.spec_for(("d_ff", "d_model"), w.shape)
        h_spec = P(dp_spec, None, w_spec[0])
        eins = "bsf,fd->bsd"
        w_dm_axis = 1
    else:
        raise ValueError(contract)

    w_dp = w_spec[w_dm_axis]  # how the weight's d_model axis is sharded

    def body(h_loc, w_loc):
        if w_dp is not None:
            w_loc = jax.lax.all_gather(
                w_loc, w_dp, axis=w_dm_axis, tiled=True)  # ZeRO-3 gather
        partial = jnp.einsum(eins, h_loc, w_loc)          # local TP sum
        return jax.lax.psum_scatter(partial, "model",
                                    scatter_dimension=1, tiled=True)

    out_spec = P(dp_spec, "model", None)
    return jax.shard_map(body, mesh=mesh, in_specs=(h_spec, w_spec),
                         out_specs=out_spec, check_vma=False)(h, w)


def in_project_ag(x: jax.Array, weights, *, rules: ShardingRules,
                  kinds, batch_sharded: bool = True):
    """Fused SP->TP input projections: gather the sequence axis once
    and apply every projection inside ONE shard_map.

    Why fused: if the gather and the einsums live in separate SPMD
    regions, the einsums' input-gradient resolves its partial sums with
    a full all-reduce *and then* the gather's transpose scatters it —
    two reductions for one mathematical reduce-scatter.  Inside one
    shard_map, AD emits exactly ``psum_scatter(dout @ w^T)`` (the fused
    reduce-scatter) and nothing else (§Perf H2, iteration 3).

    x: (B, S, D) sequence-sharded.  kinds per weight: "df" ((D, F),
    F TP-sharded) or "dhk" ((D, H, K), H TP-sharded when divisible).
    Weight d_model axes may be FSDP-sharded; gathered inside (ZeRO-3).
    Returns one output per weight, full-seq, TP-sharded on F/H.
    """
    mesh = rules.mesh
    dp = _dp_axes(mesh)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if batch_sharded and dp \
        else None

    w_specs = []
    for w, kind in zip(weights, kinds):
        logical = ("d_model", "d_ff") if kind == "df" \
            else ("d_model", "heads", "head_dim")
        w_specs.append(rules.spec_for(logical, w.shape))

    def body(x_loc, *w_locs):
        x_full = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        outs = []
        for w_loc, spec, kind in zip(w_locs, w_specs, kinds):
            if spec[0] is not None:  # ZeRO-3: gather the FSDP shard
                w_loc = jax.lax.all_gather(w_loc, spec[0], axis=0,
                                           tiled=True)
            eins = "bsd,df->bsf" if kind == "df" else "bsd,dhk->bshk"
            outs.append(jnp.einsum(eins, x_full, w_loc))
        return tuple(outs)

    out_specs = tuple(
        P(dp_spec, None, s[1]) if kind == "df"
        else P(dp_spec, None, s[1], None)
        for s, kind in zip(w_specs, kinds))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(dp_spec, "model", None), *w_specs),
                         out_specs=out_specs,
                         check_vma=False)(x, *weights)


def gather_seq(x: jax.Array, *, rules: ShardingRules,
               batch_sharded: bool = True) -> jax.Array:
    """SP->TP transition: all-gather the sequence axis (bf16).

    Explicit so that (a) the gather happens on the bf16 residual (the
    implicit XLA path hoists an f32 convert through it) and (b) the
    BACKWARD is ``psum_scatter`` — a true reduce-scatter — instead of
    the all-reduce+slice XLA:CPU falls back to (EXPERIMENTS.md §Perf).
    x: (B, S, D) sequence-sharded -> (B, S, D) replicated over model.
    """
    mesh = rules.mesh
    dp = _dp_axes(mesh)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if batch_sharded and dp \
        else None

    def body(x_loc):
        return jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=P(dp_spec, "model", None),
                         out_specs=P(dp_spec, None, None),
                         check_vma=False)(x)
