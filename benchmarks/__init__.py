"""The benchmark of paddle_tpu: the yardstick later PRs are held to.

`run.py` is the one command; everything that belongs to one
configuration, one traffic mix or one metric is a file of its own under
`configs/`, `traffic/`, `limits/`, `metrics/` and `readers/`, found by
the name `BENCHMARK.json` gives. See PERF.md.
"""


def log(*parts):
    """An evidence line on stdout, before the result line."""
    print('[bench]', *parts, flush=True)


def free_arrays(*trees):
    """Free the device memory of every jax array in the trees, whoever
    else still holds a reference (the program's stores keep their jitted
    closures, and so the state, alive). For use once the window has
    closed, so that the reference fits beside nothing."""
    import jax
    n = 0
    for leaf in jax.tree_util.tree_leaves(trees):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            n += leaf.nbytes
            leaf.delete()
    return n
