"""Mixture-of-Experts FFN (OLMoE/DeepSeek) and MLA attention (DeepSeek-V2).

MoE dispatch is the GShard einsum formulation with *token chunking*:
tokens are routed in chunks (``MOE_CHUNK`` tokens) so the dispatch
tensors stay small and the expert all-to-all is naturally pipelined
against expert compute.  Experts are sharded over the mesh `model`
axis (EP); XLA SPMD turns the dispatch/combine einsums into
all-to-alls.

MLA (Multi-head Latent Attention) caches the *compressed* latent
c_kv (kv_lora_rank + rope dims per token) instead of full K/V — the
decode path uses the published weight-absorption trick so the cache
is never decompressed.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention
from repro.models.common import ParamTable, rms_norm, rope

Aux = Dict[str, jax.Array]
Cache = Optional[Dict[str, jax.Array]]

MOE_CHUNK = 2048  # tokens per dispatch chunk


# ----------------------------------------------------------------------
# MoE FFN
# ----------------------------------------------------------------------

def moe_table(cfg: ModelConfig) -> ParamTable:
    d = cfg.d_model
    m = cfg.moe
    f = m.d_ff_expert or cfg.d_ff
    t: ParamTable = {
        "moe.router": ((d, m.n_experts), ("d_model", "experts")),
        "moe.w_gate": ((m.n_experts, d, f), ("experts", "d_model", "d_ff")),
        "moe.w_up": ((m.n_experts, d, f), ("experts", "d_model", "d_ff")),
        "moe.w_down": ((m.n_experts, f, d), ("experts", "d_ff", "d_model")),
        "moe_norm.scale": ((d,), (None,)),
    }
    if m.n_shared:
        fs = f * m.n_shared
        t["moe.shared_gate"] = ((d, fs), ("d_model", "d_ff"))
        t["moe.shared_up"] = ((d, fs), ("d_model", "d_ff"))
        t["moe.shared_down"] = ((fs, d), ("d_ff", "d_model"))
    return t


def _route_chunk(cfg: ModelConfig, rules, params, xc: jax.Array,
                 capacity: int) -> Tuple[jax.Array, Aux]:
    """xc: (T, d) one chunk of tokens -> (T, d) expert mixture."""
    m = cfg.moe
    t, d = xc.shape
    logits = jnp.einsum("td,de->te", xc, params["moe.router"]).astype(
        jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)           # (T, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)    # renormalize top-k

    onehot_e = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # (T,k,E)
    # position of each (token, choice) within its expert, in token order
    flat = onehot_e.reshape(t * m.top_k, m.n_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.einsum("tke,tke->tk", onehot_e,
                     pos_flat.reshape(t, m.top_k, m.n_experts))
    keep = pos < capacity
    onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32) \
        * keep[..., None]                                 # (T, k, C)
    dispatch = jnp.einsum("tke,tkc->tec", onehot_e, onehot_c)
    combine = jnp.einsum("tec,tk->tec", dispatch,
                         gates * keep.astype(gates.dtype))

    xin = jnp.einsum("tec,td->ecd", dispatch.astype(xc.dtype), xc)
    xin = rules.constraint(xin, "act_experts", None, None)
    g = jnp.einsum("ecd,edf->ecf", xin, params["moe.w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xin, params["moe.w_up"])
    h = jax.nn.silu(g) * u
    xout = jnp.einsum("ecf,efd->ecd", h, params["moe.w_down"])
    xout = rules.constraint(xout, "act_experts", None, None)
    y = jnp.einsum("tec,ecd->td", combine.astype(xout.dtype), xout)

    # load-balance + router-z aux losses (train)
    me = jnp.mean(probs, axis=0)                        # mean router prob
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, m.n_experts), axis=1), axis=0)
    aux = {
        "moe_aux": m.n_experts * jnp.sum(me * ce) * m.aux_loss,
        "moe_z": jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))) * m.router_z_loss,
        "moe_dropped": jnp.sum(1.0 - keep.astype(jnp.float32)),
    }
    return y, aux


def _route_chunk_gather(cfg: ModelConfig, rules, params, xc: jax.Array,
                        capacity: int) -> Tuple[jax.Array, Aux]:
    """Gather-based dispatch (§Perf H3) — same math as ``_route_chunk``
    but without the (T, E, C) one-hot dispatch/combine tensors.

    The GShard einsum formulation costs 2*T*E*C*d FLOPs per dispatch
    and combine — MORE than the expert matmuls themselves at top-8/64
    — and materializes (T, E, C) one-hots.  Here the permutation is
    computed on int32 index arrays (a scatter of T*k indices, ~KB) and
    the data movement is two gathers:

      xin[e, c]   = xc[src_token[e, c]]          (token -> expert)
      y[t]       += gate * xout[expert_slot[t]]  (expert -> token)

    so the only O(big) traffic is the tokens themselves, once each
    way.  Expert tensors stay EP-sharded over `model` exactly as
    before (XLA turns the cross-shard gathers into all-to-alls).
    """
    m = cfg.moe
    t, d = xc.shape
    k = m.top_k
    logits = jnp.einsum("td,de->te", xc,
                        params["moe.router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                   # (T, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert (arrival order)
    onehot = jax.nn.one_hot(idx.reshape(-1), m.n_experts,
                            dtype=jnp.int32)               # (T*k, E)
    pos_flat = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos_flat, idx.reshape(-1)[:, None],
                              axis=1)[:, 0]                # (T*k,)
    e_flat = idx.reshape(-1)
    keep = pos < capacity
    slot = e_flat * capacity + pos                          # (T*k,)
    slot = jnp.where(keep, slot, m.n_experts * capacity)    # dropped bin

    # inverse permutation on INDEX arrays only (tiny scatter)
    tok_of_choice = jnp.arange(t * k, dtype=jnp.int32) // k
    src = jnp.full((m.n_experts * capacity + 1,), t,        # t = pad row
                   dtype=jnp.int32)
    src = src.at[slot].set(tok_of_choice)
    src = src[:-1].reshape(m.n_experts, capacity)           # (E, C)

    # token -> expert gather (pad row of zeros for empty slots)
    xpad = jnp.concatenate([xc, jnp.zeros((1, d), xc.dtype)], axis=0)
    xin = xpad[src]                                         # (E, C, d)
    xin = rules.constraint(xin, "act_experts", None, None)
    g = jnp.einsum("ecd,edf->ecf", xin, params["moe.w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xin, params["moe.w_up"])
    h = jax.nn.silu(g) * u
    xout = jnp.einsum("ecf,efd->ecd", h, params["moe.w_down"])
    xout = rules.constraint(xout, "act_experts", None, None)

    # expert -> token gather + gate-weighted combine
    flat_out = xout.reshape(m.n_experts * capacity, d)
    flat_out = jnp.concatenate(
        [flat_out, jnp.zeros((1, d), flat_out.dtype)], axis=0)
    safe_slot = jnp.where(keep, slot, m.n_experts * capacity)
    per_choice = flat_out[safe_slot]                        # (T*k, d)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(xc.dtype)
    y = jnp.sum((per_choice * w[:, None]).reshape(t, k, d), axis=1)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, m.n_experts), axis=1), axis=0)
    aux = {
        "moe_aux": m.n_experts * jnp.sum(me * ce) * m.aux_loss,
        "moe_z": jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))) * m.router_z_loss,
        "moe_dropped": jnp.sum(1.0 - keep.astype(jnp.float32)),
    }
    return y, aux


def _ep_enabled(cfg: ModelConfig, rules, x: jax.Array) -> bool:
    mesh = rules.mesh
    if "model" not in mesh.shape or mesh.shape["model"] == 1:
        return False
    tp = mesh.shape["model"]
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    return (cfg.moe.n_experts % tp == 0 and x.shape[1] % tp == 0
            and x.shape[0] % dp == 0
            and rules.rules.get("seq") == ("model",))


def _moe_apply_ep(cfg: ModelConfig, rules, params, x: jax.Array
                  ) -> Tuple[jax.Array, Aux]:
    """Expert parallelism via shard_map + all_to_all (§Perf H3b).

    Tokens stay sequence-sharded (they already are between blocks);
    experts live E/TP per shard.  Each shard routes its own tokens,
    packs (E, C_src, d) send buffers with local index arithmetic, and
    one tiled ``all_to_all`` delivers every token to its expert's
    shard — the canonical GShard/MaxText EP exchange.  All heavy
    tensors are token-sized; the only cross-shard traffic is the two
    all-to-alls (a few MB each), vs the hundreds of GB of resharding
    the einsum formulation triggers under SPMD (see EXPERIMENTS.md).

    Capacity bookkeeping is per source shard (C_src = C_global / TP),
    so a shard-local burst can drop tokens a global counter would
    admit — same expected drop rate, simpler = faster; on a 1-shard
    mesh it equals the global-capacity reference exactly (tested).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh = rules.mesh
    tp = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if dp else None
    b, s, d = x.shape
    e_loc = m.n_experts // tp
    t_loc = (b // _size(mesh, dp)) * (s // tp) if dp else b * (s // tp)
    cap_src = max(int(m.top_k * t_loc * m.capacity_factor
                      / m.n_experts), 4)

    router_spec = rules.spec_for(("d_model", "experts"),
                                 params["moe.router"].shape)
    w_specs = {
        name: rules.spec_for(("experts", "d_model", "d_ff"),
                             params[name].shape)
        for name in ("moe.w_gate", "moe.w_up", "moe.w_down")}
    # w_down is (E, F, D): logical axes differ
    w_specs["moe.w_down"] = rules.spec_for(
        ("experts", "d_ff", "d_model"), params["moe.w_down"].shape)

    def body(x_loc, router, wg, wu, wd):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xc = x_loc.reshape(t, d)
        # gather replicated views of the small sharded params
        if router_spec[0] is not None:
            router = jax.lax.all_gather(router, router_spec[0], axis=0,
                                        tiled=True)
        router = jax.lax.all_gather(router, "model", axis=1, tiled=True)
        for name, w in (("moe.w_gate", wg), ("moe.w_up", wu),
                        ("moe.w_down", wd)):
            pass  # expert weights stay local (E_loc shard)
        if w_specs["moe.w_gate"][1] is not None:
            wg = jax.lax.all_gather(wg, w_specs["moe.w_gate"][1],
                                    axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, w_specs["moe.w_up"][1],
                                    axis=1, tiled=True)
        if w_specs["moe.w_down"][2] is not None:
            wd = jax.lax.all_gather(wd, w_specs["moe.w_down"][2],
                                    axis=2, tiled=True)

        logits = jnp.einsum("td,de->te", xc, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
        gates = gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(idx.reshape(-1), m.n_experts,
                                dtype=jnp.int32)
        pos_flat = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos_flat, idx.reshape(-1)[:, None],
                                  axis=1)[:, 0]
        e_flat = idx.reshape(-1)
        keep = pos < cap_src
        slot = jnp.where(keep, e_flat * cap_src + pos,
                         m.n_experts * cap_src)

        tok_of_choice = jnp.arange(t * m.top_k, dtype=jnp.int32) \
            // m.top_k
        src = jnp.full((m.n_experts * cap_src + 1,), t, dtype=jnp.int32)
        src = src.at[slot].set(tok_of_choice)
        src = src[:-1].reshape(m.n_experts, cap_src)

        xpad = jnp.concatenate([xc, jnp.zeros((1, d), xc.dtype)], 0)
        xsend = xpad[src]                          # (E, C_src, d) local
        # ---- the EP exchange: tokens -> their expert's shard --------
        xrecv = jax.lax.all_to_all(xsend, "model", split_axis=0,
                                   concat_axis=1, tiled=True)
        # (E_loc, C_src * TP, d)
        g = jnp.einsum("ecd,edf->ecf", xrecv, wg)
        u = jnp.einsum("ecd,edf->ecf", xrecv, wu)
        h = jax.nn.silu(g) * u
        xout = jnp.einsum("ecf,efd->ecd", h, wd)
        # ---- reverse exchange: results back to the token's shard ----
        yback = jax.lax.all_to_all(xout, "model", split_axis=1,
                                   concat_axis=0, tiled=True)
        # (E, C_src, d)
        flat_out = yback.reshape(m.n_experts * cap_src, d)
        flat_out = jnp.concatenate(
            [flat_out, jnp.zeros((1, d), flat_out.dtype)], 0)
        per_choice = flat_out[jnp.where(keep, slot,
                                        m.n_experts * cap_src)]
        wgt = (gates.reshape(-1)
               * keep.astype(jnp.float32)).astype(xc.dtype)
        y = jnp.sum((per_choice * wgt[:, None]).reshape(t, m.top_k, d),
                    axis=1)

        # aux stats: global over the model axis (token partition)
        n_tok = t * tp
        me = jax.lax.psum(jnp.sum(probs, axis=0), "model") / n_tok
        ce = jax.lax.psum(
            jnp.sum(jax.nn.one_hot(idx, m.n_experts), axis=(0, 1)),
            "model") / n_tok
        aux = {
            "moe_aux": m.n_experts * jnp.sum(me * ce) * m.aux_loss,
            "moe_z": jax.lax.psum(jnp.sum(jnp.square(
                jax.nn.logsumexp(logits, axis=-1))), "model") / n_tok
            * m.router_z_loss,
            "moe_dropped": jax.lax.psum(
                jnp.sum(1.0 - keep.astype(jnp.float32)), "model"),
        }
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, "model", None), router_spec,
                  w_specs["moe.w_gate"], w_specs["moe.w_up"],
                  w_specs["moe.w_down"]),
        out_specs=(P(dp_spec, "model", None), P()),
        check_vma=False,
    )(x, params["moe.router"], params["moe.w_gate"],
      params["moe.w_up"], params["moe.w_down"])

    if m.n_shared:
        from repro.distributed import megatron_sp
        if megatron_sp.sp_enabled(rules, x.shape[1], x.shape[0]):
            g, u = megatron_sp.in_project_ag(
                x, [params["moe.shared_gate"], params["moe.shared_up"]],
                rules=rules, kinds=("df", "df"))
            h = jax.nn.silu(g) * u
            y = y + megatron_sp.out_project_rs(
                h, params["moe.shared_down"], rules=rules, contract="fd")
        else:
            g = jnp.einsum("bsd,df->bsf", x, params["moe.shared_gate"])
            u = jnp.einsum("bsd,df->bsf", x, params["moe.shared_up"])
            y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                               params["moe.shared_down"])
    return y, aux


def _size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def moe_apply(cfg: ModelConfig, rules, params, x: jax.Array
              ) -> Tuple[jax.Array, Aux]:
    """x: (B, S, d).  Chunked routing; shared experts added densely."""
    m = cfg.moe
    if m.dispatch == "gather" and _ep_enabled(cfg, rules, x):
        return _moe_apply_ep(cfg, rules, params, x)
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    n_tok = tokens.shape[0]
    chunk = min(MOE_CHUNK, n_tok)
    n_chunks = max(n_tok // chunk, 1)
    capacity = max(int(m.top_k * chunk * m.capacity_factor / m.n_experts), 4)
    route = (_route_chunk_gather if m.dispatch == "gather"
             else _route_chunk)

    if n_chunks * chunk != n_tok:  # ragged tail: single-chunk fallback
        y, aux = route(cfg, rules, params, tokens, capacity=max(
            int(m.top_k * n_tok * m.capacity_factor / m.n_experts), 4))
    else:
        xs = tokens.reshape(n_chunks, chunk, d)

        def body(carry, xc):
            y, aux = route(cfg, rules, params, xc, capacity)
            return carry, (y, aux)

        _, (ys, auxs) = jax.lax.scan(body, (), xs)
        y = ys.reshape(n_tok, d)
        aux = jax.tree.map(lambda a: jnp.sum(a) / n_chunks, auxs)
        aux["moe_dropped"] = aux["moe_dropped"] * n_chunks  # total, not mean

    y = y.reshape(b, s, d)
    if m.n_shared:
        g = jnp.einsum("bsd,df->bsf", x, params["moe.shared_gate"])
        u = jnp.einsum("bsd,df->bsf", x, params["moe.shared_up"])
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           params["moe.shared_down"])
    return y, aux


# ----------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ----------------------------------------------------------------------

def mla_table(cfg: ModelConfig) -> ParamTable:
    d, h = cfg.d_model, cfg.n_heads
    a = cfg.mla
    qk = a.qk_nope_dim + a.qk_rope_dim
    return {
        "mla.wq": ((d, h, qk), ("d_model", "heads", None)),
        "mla.w_dkv": ((d, a.kv_lora_rank + a.qk_rope_dim), ("d_model", None)),
        "mla.kv_norm.scale": ((a.kv_lora_rank,), (None,)),
        "mla.w_uk": ((a.kv_lora_rank, h, a.qk_nope_dim),
                     (None, "heads", None)),
        "mla.w_uv": ((a.kv_lora_rank, h, a.v_head_dim),
                     (None, "heads", None)),
        "mla.wo": ((h, a.v_head_dim, d), ("heads", None, "d_model")),
        "attn_norm.scale": ((d,), (None,)),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int,
                   dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    a = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, seq, a.kv_lora_rank), dtype=dtype),
        "k_pe": jnp.zeros((batch, seq, a.qk_rope_dim), dtype=dtype),
    }


def mla_apply(cfg: ModelConfig, rules, params, x: jax.Array, *,
              mode: str, cache: Cache, positions: jax.Array
              ) -> Tuple[jax.Array, Cache]:
    a = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    scale = (a.qk_nope_dim + a.qk_rope_dim) ** -0.5

    from repro.distributed import megatron_sp
    sp = (mode != "decode"
          and megatron_sp.sp_enabled(rules, s, b)
          and rules.spec_for(("d_model", "heads", "head_dim"),
                             params["mla.wq"].shape)[1] is not None)
    if sp:
        (q,) = megatron_sp.in_project_ag(x, [params["mla.wq"]],
                                         rules=rules, kinds=("dhk",))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["mla.wq"])
    q_nope, q_pe = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_pe = rope(q_pe, positions, cfg.rope_theta)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, params["mla.w_dkv"])
    c_kv = rms_norm(ckv_full[..., :a.kv_lora_rank],
                    params["mla.kv_norm.scale"], cfg.norm_eps)
    k_pe = rope(ckv_full[..., a.kv_lora_rank:], positions, cfg.rope_theta)

    if mode == "decode":
        assert cache is not None
        idx = positions[0, 0]
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), idx, axis=1)
        p_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k_pe"], k_pe.astype(cache["k_pe"].dtype), idx, axis=1)
        c_cache = rules.constraint(c_cache, "batch", "kv_seq", None)
        p_cache = rules.constraint(p_cache, "batch", "kv_seq", None)
        # absorbed decode: scores/context in the compressed space
        q_c = jnp.einsum("bshk,rhk->bshr", q_nope, params["mla.w_uk"])
        scores = (jnp.einsum("bshr,btr->bhst", q_c, c_cache,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshk,btk->bhst", q_pe, p_cache,
                               preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(c_cache.shape[1])[None, :] <= idx
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", probs.astype(c_cache.dtype),
                         c_cache)
        out = jnp.einsum("bshr,rhv->bshv", ctx, params["mla.w_uv"])
        new_cache = {"c_kv": c_cache, "k_pe": p_cache}
    else:
        # train/prefill: decompress K/V (sequence-parallel friendly)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["mla.w_uk"])
        v = jnp.einsum("bsr,rhv->bshv", c_kv, params["mla.w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                      (b, s, h, a.qk_rope_dim))], axis=-1)
        qq = jnp.concatenate([q_nope, q_pe], axis=-1)
        # pad V to qk dim so we can reuse the blocked kernel, then slice
        qt = qq.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        qt = rules.constraint(qt, "batch", "act_heads", None, None)
        out = attention.full_attention(qt, kt, vt, causal=True,
                                       q_block=cfg.q_block, scale=scale)
        out = out.transpose(0, 2, 1, 3)
        new_cache = None
        if mode == "prefill":
            c_cache = rules.constraint(c_kv, "batch", "kv_seq", None)
            p_cache = rules.constraint(k_pe, "batch", "kv_seq", None)
            new_cache = {"c_kv": c_cache.astype(x.dtype),
                         "k_pe": p_cache.astype(x.dtype)}

    if sp:
        y = megatron_sp.out_project_rs(out, params["mla.wo"],
                                       rules=rules, contract="hkd")
    else:
        y = jnp.einsum("bshv,hvd->bsd", out, params["mla.wo"])
    return y, new_cache


# ----------------------------------------------------------------------
# Full MoE decoder blocks
# ----------------------------------------------------------------------

def table(cfg: ModelConfig) -> ParamTable:
    """MoE block: (MLA | GQA) attention + MoE FFN."""
    from repro.models import blocks_attn
    at = mla_table(cfg) if cfg.mla else blocks_attn.attn_table(cfg)
    return {**at, **moe_table(cfg)}


def apply(cfg: ModelConfig, rules, params, x: jax.Array, *,
          mode: str, cache: Cache, positions: jax.Array
          ) -> Tuple[jax.Array, Cache, Aux]:
    from repro.models import blocks_attn
    h = rms_norm(x, params["attn_norm.scale"], cfg.norm_eps)
    if cfg.mla:
        a, new_cache = mla_apply(cfg, rules, params, h, mode=mode,
                                 cache=cache, positions=positions)
    else:
        a, new_cache = blocks_attn.attn_apply(
            cfg, rules, params, h, mode=mode, cache=cache,
            positions=positions)
    x = x + a
    x = rules.constraint(x, "batch", "seq", None)
    hh = rms_norm(x, params["moe_norm.scale"], cfg.norm_eps)
    y, aux = moe_apply(cfg, rules, params, hh)
    x = x + y
    x = rules.constraint(x, "batch", "seq", None)
    return x, new_cache, aux


def init_cache(cfg: ModelConfig, batch: int, seq: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    from repro.models import blocks_attn
    if cfg.mla:
        return init_mla_cache(cfg, batch, seq, dtype)
    return blocks_attn.init_attn_cache(cfg, batch, seq, dtype)


# Dense-FFN + MLA block (DeepSeek first_dense_layers)

def dense_mla_table(cfg: ModelConfig) -> ParamTable:
    from repro.models import blocks_attn
    at = mla_table(cfg) if cfg.mla else blocks_attn.attn_table(cfg)
    return {**at, **blocks_attn.mlp_table(cfg, d_ff=cfg.moe.d_ff_dense)}


def dense_mla_apply(cfg: ModelConfig, rules, params, x: jax.Array, *,
                    mode: str, cache: Cache, positions: jax.Array
                    ) -> Tuple[jax.Array, Cache, Aux]:
    from repro.models import blocks_attn
    h = rms_norm(x, params["attn_norm.scale"], cfg.norm_eps)
    if cfg.mla:
        a, new_cache = mla_apply(cfg, rules, params, h, mode=mode,
                                 cache=cache, positions=positions)
    else:
        a, new_cache = blocks_attn.attn_apply(
            cfg, rules, params, h, mode=mode, cache=cache,
            positions=positions)
    x = x + a
    h = rms_norm(x, params["mlp_norm.scale"], cfg.norm_eps)
    x = x + blocks_attn.mlp_apply(cfg, rules, params, h)
    x = rules.constraint(x, "batch", "seq", None)
    return x, new_cache, {}
