"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §4).

Must set env before jax initializes its backends, hence module-level.
"""
import atexit
import os
import shutil
import tempfile

# Tier-1 runs on the CPU whatever the machine holds: the numerics the
# tests pin are float32-exact there, the 8-device mesh exists only
# there, and a test process must never take a chip (one client per
# chip). JAX honours JAX_PLATFORMS; nothing else is needed.
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

# One fresh compile cache per test session, placed from outside the way
# an operator would (programs.ensure_compile_cache honours the
# variable): cold-vs-warm tests need a cache no earlier session filled,
# subprocess children inherit it, and the checkout's .jax_cache is
# never written by a test run.
if 'JAX_COMPILATION_CACHE_DIR' not in os.environ:
    os.environ['JAX_COMPILATION_CACHE_DIR'] = tempfile.mkdtemp(
        prefix='paddle_tpu_t1_jax_cache_')
    atexit.register(shutil.rmtree, os.environ['JAX_COMPILATION_CACHE_DIR'],
                    ignore_errors=True)

import jax  # noqa: E402,F401

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu as paddle
    paddle.seed(42)
    np.random.seed(42)
    yield


@pytest.fixture(autouse=True)
def _reset_span_state():
    """Empty this thread's stack of open spans around every test.

    A test that begin()s a Span and never end()s it leaves it open on
    the main thread, so a later test asserting absolute depths or
    parents (test_span_nesting_records_depth_and_order) would fail
    depending on which test ran first. Span state is per-test
    scaffolding, not cross-test truth — reset it on both sides."""
    from paddle_tpu.observability import events as _events
    del _events._span_state.stack[:]
    yield
    del _events._span_state.stack[:]


@pytest.fixture
def fresh_dispatch():
    """The eager dispatch cache keys an op by its code, not by the
    module globals a departure patches: empty it around such a test."""
    from paddle_tpu import _dispatch
    _dispatch.clear()
    yield
    _dispatch.clear()


@pytest.fixture
def fresh_programs():
    """Empty the program store's memory around a test that serves ONE
    model on two paths a trace picks (the expert loop, then the
    interpreted expert kernel): the store keys a program by the model's
    class, configuration and avals, which such a test holds equal."""
    from paddle_tpu import programs
    store = programs.get_store()
    store.clear_memory()
    yield store
    store.clear_memory()


@pytest.fixture
def kv_interpreted(monkeypatch, fresh_programs):
    """Decode attention over K and V held by head through the kernel,
    interpreted, wherever `ops.pallas.kv_decode_kernel`'s conditions
    hold but the backend's — for a model's cached branch and for the
    engine's count alike — at tiles of 16 or 8 rows (toy lengths have
    no whole lanes). -> what the kernel was handed, call by call (at
    trace time: tracers under a jit)."""
    import functools
    from paddle_tpu.ops import pallas, pallas_kernels
    calls = []
    real = pallas_kernels.kv_decode_attention

    def kernel(*args, **kw):
        calls.append(args)
        return real(*args, **kw)
    monkeypatch.setattr(pallas_kernels, 'kv_decode_attention', kernel)
    monkeypatch.setattr(pallas_kernels, '_mla_row_tile', lambda rows: next(
        (n for n in (16, 8) if rows % n == 0), None))
    monkeypatch.setattr(pallas, 'kv_decode_kernel', functools.partial(
        pallas.kv_decode_kernel, interpret=True))
    return calls


@pytest.fixture
def kda_interpreted(monkeypatch, fresh_programs):
    """A KDA layer's one-token recurrence through `kda_decode_step`,
    interpreted, with `ops.pallas.kda_step_kernel` lifted off its
    backend and whole-lane conditions (the toy head of 8 has no whole
    lanes) — for `kda_mix` and for the engine's count alike. -> the
    shapes of the states the dispatch was asked with."""
    import functools
    from paddle_tpu.ops import pallas, pallas_kernels
    asked = []

    def lifted(state, interpret=False):
        asked.append(tuple(state.shape))
        return functools.partial(pallas_kernels.kda_decode_step,
                                 interpret=True)
    monkeypatch.setattr(pallas, 'kda_step_kernel', lifted)
    return asked


@pytest.fixture
def sanitizer_strict():
    """Run the test under the runtime concurrency sanitizer in STRICT
    mode (ISSUE 15): any lock-order cycle, non-reentrant re-entry, or
    guarded-field lockset race raises ConcurrencySanitizerError at the
    offending acquire/access — and even if a violation is swallowed by
    a failover/retry path mid-test, the teardown assertion on the
    violation counter still fails the test. The chaos gauntlets
    (router failover storm, autoscaler thundering herd, hotswap
    kill-mid-swap, pool recovery) all opt in."""
    from paddle_tpu import observability as obs
    from paddle_tpu.analysis import runtime as _rt

    reg = obs.get_registry()

    def _total():
        fam = reg.get('paddle_sanitizer_violations_total')
        return fam.total() if fam is not None else 0.0

    before = _total()
    n_before = len(_rt.violations())
    _rt.enable('strict')
    try:
        yield _rt
    finally:
        _rt.disable()
    new = _rt.violations()[n_before:]
    assert _total() == before and not new, (
        'concurrency sanitizer reported violations during the '
        f'gauntlet: {new}')


@pytest.fixture
def fleet_mesh():
    """Factory for a hybrid fleet mesh over the forced 8-device CPU
    platform: `fleet_mesh(dp=..., mp=..., pp=..., sp=...)` runs
    fleet.init with those degrees and returns the strategy. Tears the
    whole parallel env (mesh, HCG, resize history) down afterwards so
    mesh-shaped tests stay independent — the elastic suite re-meshes
    mid-test and must not leak a shrunken world into the next test."""
    from paddle_tpu.distributed import env, fleet

    def make(dp=1, mp=1, pp=1, sp=1, sharding=False, stage=1):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {'dp_degree': dp, 'mp_degree': mp,
                                   'pp_degree': pp, 'sep_degree': sp}
        if sharding:
            strategy.sharding = True
            strategy.sharding_configs['stage'] = stage
        fleet.init(is_collective=True, strategy=strategy)
        return strategy

    yield make
    env.destroy_process_group()
    fleet._fleet.initialized = False
    fleet._fleet.strategy = None
    fleet._fleet._hcg = None
    fleet._resize_history.clear()
