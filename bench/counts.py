"""Work of the scheduling-pass kernel, counted from its semantics.

One pass over k forks of J job slots reads six (k, J) inputs (priority
order, queued flag, nodes, estimate, predicted end and nodes of the
running jobs), writes one (k, J) output (started), and reads or writes
three per-fork scalars (free nodes and time in, free nodes out), all
4 bytes wide.  Its arithmetic is the shadow time's pairwise
compare-accumulate, 2·k·J² operations.  The counts are at the logical
(k, J): never the padded tiles, never the rank bound, so they read the
same whatever implements the pass.
"""
from __future__ import annotations

import re
from typing import NamedTuple

from bench.peaks import Peak

#: The pass kernel's device op, as the profiler names it today: the
#: ``tpu_custom_call`` that ``pallas_call`` lowers to takes the name of
#: the jitted function around it, ``policy_eval_pass_batched``.
PASS_KERNEL = re.compile(r"^%policy_eval_pass_batched(\.\d+)?$")


class Work(NamedTuple):
    ops: float
    bytes: float


def pass_work(k: int, j: int) -> Work:
    return Work(ops=2.0 * k * j * j, bytes=4.0 * k * (7 * j + 3))


def least_seconds(work: Work, peak: Peak) -> tuple:
    """(the least time the chip could take, the bound that binds)."""
    t_ops, t_bytes = work.ops / peak.flops, work.bytes / peak.hbm_bytes_s
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
