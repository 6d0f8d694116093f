"""Production mesh builders.

``make_production_mesh`` is a FUNCTION — importing this module never
touches jax device state.  Single pod = (data=16, model=16) over 256
chips (TPU v5e pod); multi-pod adds a leading ``pod`` axis (2 pods =
512 chips).  The ``pod`` axis defaults to extra data parallelism
(FSDP over ('pod','data')); the sharding rules in
``repro/distributed/sharding.py`` treat ('pod','data') as the DP axes
everywhere, so the same model code runs on either mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off (the
    fleet engine's per-device SPMD primitive).  Every fleet output is
    explicitly sharded or reduced by the caller, and the check rejects
    the drain's ``while_loop`` carries, the replay's pass-elision
    ``lax.cond`` and the ``pallas_call`` outputs, whose types it cannot
    infer as varying."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Generic builder (tests / degraded-fleet elastic re-mesh)."""
    return _make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    m = model or 1
    assert n % m == 0
    return make_mesh((n // m, m), ("data", "model"))


def make_fleet_mesh(shards: Optional[int] = None) -> Mesh:
    """A (data=shards, model=1) mesh for the fleet replay engine
    (``whatif.sharded_replay_grid``): scenarios shard over ``data``.
    Defaults to every local device; unlike ``jax.make_mesh`` it accepts
    a PREFIX of the device list, so ``--shard 2`` works on an 8-chip
    host without reshaping the rest of the fleet away."""
    n = len(jax.devices())
    s = n if shards is None else int(shards)
    if not 1 <= s <= n:
        raise ValueError(f"shards={s} outside [1, {n}] local devices")
    return Mesh(np.asarray(jax.devices()[:s]).reshape(s, 1),
                ("data", "model"))


# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link
