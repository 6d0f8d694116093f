"""A tiny fleet run on four devices, whole or with the exchange between
chips left out: every shard's rows of each block replaced by the first
shard's, as if the others' results never arrived.  Prints the verdict.

    python -m bench.tests.fleet_fault none|exchange_left_out
"""
import sys

from bench import run  # noqa: F401  (puts the program on the path)


def main(fault: str) -> None:
    import jax
    import repro.core.whatif as whatif
    import repro.launch.cache
    from bench.tests.helpers import run_tiny, tiny_cell

    repro.launch.cache.enable_persistent_cache = lambda enabled=True: None
    if fault == "exchange_left_out":
        orig = whatif._replay_block_sharded
        n = 4

        def first_shard_only(*args, **kwargs):
            res, metrics = orig(*args, **kwargs)

            def copy(x):
                if getattr(x, "ndim", 0) == 0 or x.shape[0] % n:
                    return x
                k = x.shape[0] // n
                return jax.numpy.concatenate([x[:k]] * n)
            return (res._replace(state=jax.tree.map(copy, res.state),
                                 events=copy(res.events),
                                 deadlocked=copy(res.deadlocked)),
                    jax.tree.map(copy, metrics))
        whatif._replay_block_sharded = first_shard_only
    cell = tiny_cell("paper32.grid", chips=4, mix_file="fleet_s256_sweep",
                     scenarios=8, block_scenarios=8, check_scenarios=8)
    result = run_tiny(cell)
    print(result["checks"])
    print(f"correct {result['correct']}")


if __name__ == "__main__":
    main(sys.argv[1])
