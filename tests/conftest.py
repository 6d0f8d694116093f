import jax
import numpy as np
import pytest

from repro.core.state import JobTable, add_job, empty_state, start_job
from repro.distributed.sharding import make_rules
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="session")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def rules_train(mesh11):
    return make_rules(mesh11, "fsdp_tp")


@pytest.fixture(scope="session")
def rules_decode(mesh11):
    return make_rules(mesh11, "decode")


def make_cluster_state(max_jobs=64, total_nodes=32, n_queued=12,
                       n_running=4, seed=0, now=500.0):
    """A consistent SimState: running jobs fit, the rest are queued."""
    rng = np.random.default_rng(seed)
    st = empty_state(max_jobs, total_nodes)
    jid = 0
    free = total_nodes
    for _ in range(n_running):
        nodes = int(rng.integers(1, max(2, free // 2 + 1)))
        if nodes > free:
            break
        st = add_job(st, jid, float(jid * 7.0), nodes,
                     float(rng.uniform(60, 600)))
        st = start_job(st, jid, float(jid * 7.0 + rng.uniform(0, 50)))
        free -= nodes
        jid += 1
    for _ in range(n_queued):
        st = add_job(st, jid, float(jid * 7.0),
                     int(rng.integers(1, total_nodes + 1)),
                     float(rng.uniform(30, 900)))
        jid += 1
    import jax.numpy as jnp
    return st._replace(now=jnp.float32(now))


def gathered_mirror(state, slots):
    """What ``sync.pack`` uploads, as a host ``SimState`` and the job id
    of each row, built row by row: the live slots' rows first in job-id
    (submission) order, empty rows after them, the scalars as they are."""
    live = sorted((int(j), s) for s, j in enumerate(slots.ids) if j >= 0)
    capacity = state.jobs.capacity
    out = empty_state(capacity, int(state.total_nodes), xp=np).jobs
    cols = {name: np.array(getattr(out, name)) for name in JobTable._fields}
    for row, (_, s) in enumerate(live):
        for name in JobTable._fields:
            cols[name][row] = getattr(state.jobs, name)[s]
    return (state._replace(jobs=JobTable(**cols),
                           free_nodes=np.int32(state.free_nodes),
                           total_nodes=np.int32(state.total_nodes),
                           now=np.float32(state.now)),
            np.array([j for j, _ in live], np.int64))
