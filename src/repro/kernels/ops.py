"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (every wrapper's default) resolves from the
platform through ``policy_eval.default_interpret``: the compiled
kernels on a TPU, interpret mode (kernel bodies evaluated as plain
JAX, for correctness) everywhere else.  Pass a bool to override.

``twin_schedule_pass`` is the drop-in replacement for the pure-jnp
``core.backfill.schedule_pass`` inside the what-if engine: it takes a
SimState + policy pool and returns the per-policy started masks.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.core.state import SimState
from repro.kernels import flash_attention as _fa
from repro.kernels import policy_eval as _pe
from repro.kernels import rglru as _rg
from repro.kernels import wkv6 as _wkv
from repro.kernels.ref import kernel_inputs_from_state


def _interpret(interpret: Optional[bool]) -> bool:
    return _pe.default_interpret() if interpret is None else interpret


def twin_schedule_pass(state: SimState, pool: jax.Array,
                       interpret: bool | None = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Policy-batched scheduling pass (paper hot spot).

    Returns (started (k, J) i32, free_after (k,) f32)."""
    inp = kernel_inputs_from_state(state, pool)
    return _pe.policy_eval_pass(
        inp["order"], inp["queued"], inp["nodes"], inp["est"],
        inp["run_end"], inp["run_nodes"], inp["free0"], inp["now"],
        interpret=_interpret(interpret))


def flash_attention(q, k, v, *, causal=True, block_q=None, block_k=None,
                    scale=None, interpret=None):
    kwargs = {}
    if block_q is not None:
        kwargs["block_q"] = block_q
    if block_k is not None:
        kwargs["block_k"] = block_k
    return _fa.flash_attention(
        q, k, v, causal=causal, scale=scale,
        interpret=_interpret(interpret), **kwargs)


def wkv6(r, k, v, w, u, *, block_t=None, interpret=None):
    kwargs = {}
    if block_t is not None:
        kwargs["block_t"] = block_t
    return _wkv.wkv6(r, k, v, w, u, interpret=_interpret(interpret),
                     **kwargs)


def rglru(a, x, h0, *, block_t=None, block_w=None, interpret=None):
    kwargs = {}
    if block_t is not None:
        kwargs["block_t"] = block_t
    if block_w is not None:
        kwargs["block_w"] = block_w
    return _rg.rglru(a, x, h0, interpret=_interpret(interpret), **kwargs)
