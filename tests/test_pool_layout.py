"""The slot pool in the layout the decode block reads (PR 33), on the CPU.

On a TPU a K or V leaf whose head size is not whole lanes asks for a
layout of its own, the whole-length decode block is compiled with AUTO
there, the pool holds its leaves as that program takes them and every
other program that takes the pool is compiled to those formats. Here:
the rule; that on the CPU nothing is asked and every serve cell's family
compiles to the program it had; the mechanism itself, driven on the CPU
by asking for a layout it takes (a permuted `major_to_minor`); what the
store keys and persists; the pool's book of it. What the v5e's compiler
makes of it is `tests/test_aot_decode.py`'s (slow)."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.programs import store as store_mod
from paddle_tpu.serving import InferenceEngine, SamplingParams
from paddle_tpu.serving.kv_pool import (SlotPool, format_bytes, layout_name,
                                        wants_own_layout)

_tree = jax.tree_util
NO_EOS = -1


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('what, shape, backend, wanted', [
    ('gpt3-1.3B-en K and V', (12, 1024, 16, 128), 'tpu', False),
    ('internlm2-1_8b K and V', (6, 4096, 8, 128), 'tpu', False),
    ('trinity-mini K and V: four heads, whole lanes', (8, 4096, 4, 128),
     'tpu', False),
    ('lfm2-24b-a2b K and V', (32, 4096, 8, 64), 'tpu', True),
    ('mimo-v2.5 K of a full layer', (32, 4096, 4, 192), 'tpu', True),
    ('mimo-v2.5 V of a full layer', (32, 4096, 4, 128), 'tpu', False),
    ('mimo-v2.5 K of a ring', (32, 128, 8, 192), 'tpu', True),
    ('mimo-v2.5 V of a ring', (32, 128, 8, 128), 'tpu', False),
    ('lfm2-24b-a2b conv state', (32, 3, 2048), 'tpu', False),
    ('lfm2-24b-a2b K and V on the CPU', (32, 4096, 8, 64), 'cpu', False),
    ('lfm2-24b-a2b K and V on a GPU', (32, 4096, 8, 64), 'gpu', False),
])
def test_the_rule_reads_the_leaf_and_the_backend(what, shape, backend,
                                                 wanted):
    assert wants_own_layout(shape, backend) is wanted, what


def test_a_state_leaf_is_never_asked_whatever_its_shape(monkeypatch):
    """The pool applies the rule to (K, V) entries alone: lfm2's conv
    state would not pass it anyway, and a 4-D state must not either."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny()).eval()
    pool = SlotPool(model, num_slots=2, max_length=32)
    assert pool.state_layers
    leaves = _tree.tree_leaves(pool.rows)
    asked = [i for i, f in enumerate(pool.own_layout) if f is not None]
    assert asked and all(leaves[i].ndim == 4 for i in asked)
    assert all(not isinstance(f.layout, Layout)      # AUTO: the compiler's
               for f in pool.own_layout if f is not None)
    state = [i for i, leaf in enumerate(leaves) if leaf.ndim != 4]
    assert state and all(pool.own_layout[i] is None for i in state)
    assert pool.formats == [None] * len(leaves)


# ---------------------------------------------------------------------------
# on the CPU nothing is asked: every family's programs are the parent's
# ---------------------------------------------------------------------------
_FAMILIES = {'gpt': (GPTForCausalLM, GPTConfig),
             'llama': (LlamaForCausalLM, LlamaConfig),
             'afmoe': (AfmoeForCausalLM, AfmoeConfig),
             'lfm2': (Lfm2MoeForCausalLM, Lfm2MoeConfig),
             'mimo': (MiMoV2ForCausalLM, MiMoV2Config)}

# sha256 (first 16 hex digits) of the StableHLO text of the decode
# programs of a tiny engine (2 slots x 64, block 4, bucket 16), one per
# serve cell's family, lowered from the engine's OWN functions on its
# own arguments. Re-taken AT PR 36, whose programs take the slot state
# as one buffer and unpack it (the parents' digests, PR 32's and PR
# 33's, were of programs over nine loose arrays; that the packed
# program returns the loose one's tokens and pool, bit for bit, is
# `tests/test_slot_state.py`'s); re-taken AT PR 45, whose programs also
# take the tokens of the block before and read a `carried` slot's
# pending token off them (ONE `where` outside the scan, spelled in
# `tests/test_serving.py`); jax 0.9.0
_PARENT_DECODE = {
    ('afmoe', 'decode'): '06d4c6cd626f8f1c',
    ('afmoe', 'decode_half'): '9ff2fd3dad3881d1',
    ('gpt', 'decode'): '26d1a9e871109e8a',
    ('gpt', 'decode_half'): '43ad4e7e3a35cce5',
    ('lfm2', 'decode'): '2b23d551b6d5dcf5',
    ('lfm2', 'decode_half'): '0f8bfc49e25b9946',
    ('llama', 'decode'): '0b50d9ed7ed665f2',
    ('llama', 'decode_half'): 'b5a72e2c6a853df8',
    ('mimo', 'decode'): '6ac560b685af1312',
    ('mimo', 'decode_half'): '497fca16f53523e5',
}


def _engine(family, **kw):
    cls, conf = _FAMILIES[family]
    paddle.seed(0)
    kw = dict(dict(num_slots=2, max_length=64, decode_block=4,
                   buckets=[16]), **kw)
    return InferenceEngine(cls(conf.tiny()).eval(), **kw)


@pytest.mark.parametrize('family', sorted(_FAMILIES))
def test_on_the_cpu_nothing_is_asked_and_decode_is_the_parents(family):
    eng = _engine(family)
    pool = eng.pool
    assert pool.own_layout == [None] * len(_tree.tree_leaves(pool.rows))
    assert pool.formats == pool.own_layout and pool.own_layout_leaves == 0
    assert eng._pool_layout_settled
    # no program that takes the pool asks the compile site for anything
    for jit in (eng._decode_jit, eng._decode_half_jit, pool._seat_jit,
                pool._copy_jit, pool._slice_jit):
        assert jit._pool_io and store_mod.pool_formats(jit._pool_io) == ()
    args = eng._decode_args()
    for name, fn in (('decode', eng._decode_block_fn),
                     ('decode_half', eng._decode_block_half_fn)):
        text = jax.jit(fn).lower(*args).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == _PARENT_DECODE[family, name], (family, name)
    assert set(pool.stats()['entry_layouts'].values()) == {'default'}
    assert obs.get_registry().value(
        'paddle_serving_pool_own_layout_leaves') == 0


# ---------------------------------------------------------------------------
# the mechanism, on a layout the CPU takes
# ---------------------------------------------------------------------------
def _permuted(leaf):
    """Heads before rows: not the CPU's default for a 4-D leaf."""
    return Format(Layout((0, 2, 1, 3), ()), leaf.sharding)


def _cpu_takes_a_layout():
    """Asked of the compiler, not of an array: what an array SAYS of its
    layout is wrong once its program came out of jax's compile cache."""
    x = jnp.zeros((2, 4, 2, 8), jnp.float32)
    try:
        took = jax.jit(lambda v: v, out_shardings=_permuted(x)).lower(
            x).compile().output_formats
    except Exception:       # a backend that refuses: the case is skipped
        return False
    return took == _permuted(x)


def _ask(pool, fmt_of):
    """Steer the rule in the test: every (K, V) leaf asks, for the
    format `fmt_of(leaf)` gives."""
    leaves = _tree.tree_leaves(pool.rows)
    pool.own_layout = [fmt_of(leaf) if leaf.ndim == 4 else None
                       for leaf in leaves]
    return leaves


def _held_as_booked(pool):
    for leaf, fmt in zip(_tree.tree_leaves(pool.rows), pool.formats):
        if fmt is not None:
            assert leaf.format == fmt, (leaf.format, fmt)
    return True


def test_a_pool_in_a_format_of_its_own_survives_every_pool_program():
    if not _cpu_takes_a_layout():
        pytest.skip('this backend takes no layout but its default')
    paddle.seed(0)
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny()).eval()
    eng = InferenceEngine(model, num_slots=3, max_length=32, decode_block=2,
                          buckets=[8])
    pool = eng.pool
    _ask(pool, _permuted)
    asked = list(pool.own_layout)
    pool.adopt_formats(asked, asked)
    assert pool.formats == asked and pool.own_layout_leaves == 2
    assert _held_as_booked(pool)
    rng = np.random.default_rng(0)
    row = _tree.tree_map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), s.dtype),
        pool.row_spec)
    want = [np.asarray(v) for v in _tree.tree_leaves(row)]

    def slot(i):
        return [np.asarray(v) for v in _tree.tree_leaves(pool.row(i))]

    pool.set_row(1, row)                        # seat
    assert _held_as_booked(pool)
    for got, ref in zip(slot(1), want):         # slice
        np.testing.assert_array_equal(got, ref)
    for got in slot(0):
        assert not got.any()
    pool.copy_slot(1, 2)                        # copy
    assert _held_as_booked(pool)
    for got, ref in zip(slot(2), want):
        np.testing.assert_array_equal(got, ref)
    # the three programs were compiled to the pool's formats
    for jit in (pool._seat_jit, pool._copy_jit, pool._slice_jit):
        (_, call), = jit._entries.values()
        taken = _tree.tree_leaves(call.input_formats[0][0])
        assert [t if f is not None else None
                for t, f in zip(taken, asked)] == asked
    pool.reset_rows()
    assert pool.formats == asked and _held_as_booked(pool)
    assert not any(v.any() for v in slot(1))
    pool.set_row(0, row)
    eng._recover_pool()
    assert pool.formats == asked and _held_as_booked(pool)
    assert not any(v.any() for v in slot(0))


@pytest.mark.parametrize('family, asks', [('lfm2', 'permuted'),
                                          ('mimo', 'permuted'),
                                          ('gpt', 'auto')])
def test_an_engine_settles_the_layout_before_it_touches_the_pool(
        family, asks, fresh_programs):
    """The whole flow on the CPU: the whole-length decode block is
    compiled to what the leaves ask (a permuted layout the CPU takes, or
    AUTO, which is the default here), the pool adopts what that program
    takes, the half-length block, seat and slice are compiled to it, and
    the tokens are the ones a default pool gives."""
    if asks == 'permuted' and not _cpu_takes_a_layout():
        pytest.skip('this backend takes no layout but its default')
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    sp = SamplingParams(max_new_tokens=9, eos_token_id=NO_EOS)
    ref = [h.tokens for h in _engine(family).generate_many(prompts, sp)]
    fresh_programs.clear_memory()
    eng = _engine(family)
    pool = eng.pool
    leaves = _ask(pool, _permuted if asks == 'permuted' else
                  lambda leaf: Format(Layout.AUTO, leaf.sharding))
    n_asked = sum(f is not None for f in pool.own_layout)
    eng._pool_layout_settled = False
    compiles = dict(eng._trace_counts)
    got = [h.tokens for h in eng.generate_many(prompts, sp)]
    assert got == ref
    assert eng._pool_layout_settled
    assert pool.own_layout_leaves == n_asked > 0
    assert _held_as_booked(pool)
    for leaf, asked, fmt in zip(leaves, pool.own_layout, pool.formats):
        if asked is None:
            assert fmt is None
        elif asks == 'permuted':
            assert fmt == asked
        else:       # AUTO's answer is a layout, here the default one
            assert fmt.layout == jnp.zeros(leaf.shape).format.layout
    # asking costs no trace: each decode program was traced once
    assert eng._trace_counts['decode_step'] \
        == compiles.get('decode_step', 0) + 1
    assert eng._trace_counts['decode_step_half'] == 1
    assert obs.get_registry().value(
        'paddle_serving_pool_own_layout_leaves') == n_asked
    book = pool.stats()
    assert any(v != 'default' for v in book['entry_layouts'].values())
    if family == 'lfm2':
        assert book['entry_layouts']['state'] == 'default'


# ---------------------------------------------------------------------------
# the store: key, manifest, load
# ---------------------------------------------------------------------------
@pytest.fixture
def open_store(monkeypatch):
    """`open_store(directory)`: a NEW, empty process-wide store, as a
    fresh process would hold (`tests/test_programs.py`'s)."""
    opened = []

    def _open(directory):
        store = programs.ProgramStore()
        monkeypatch.setattr(store_mod, '_store', store)
        store.configure(directory)
        opened.append(store)
        return store

    yield _open
    if opened:
        opened[-1].configure(None)


@pytest.fixture
def no_compile_cache():
    """jax's persistent compilation cache off around a test that reads
    the layout an array SAYS it lies in after a program was loaded: what
    an executable from that cache returns lies as it was compiled to
    and reports the default layout (jax 0.9.0, XLA:CPU and the TPU
    alike; `store._LayoutsOnTrust`), and `ProgramStore.configure` caches
    every program, however small."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', True)
    cc.reset_cache()


def _pool_program(formats):
    """A program over a one-leaf 'pool': argument 0, result 0 of 2."""
    def bump(pool, by):
        return [pool[0] + by], jnp.sum(pool[0])
    return programs.get_store().wrap_jit(
        bump, name='test.pool_program', donate_argnums=(0,),
        pool_io=(programs.PoolIO(0, (0,), lambda: formats),))


def test_the_store_key_and_the_manifest_carry_the_formats(
        open_store, tmp_path, no_compile_cache):
    if not _cpu_takes_a_layout():
        pytest.skip('this backend takes no layout but its default')
    directory = str(tmp_path / 'store')
    store = open_store(directory)
    x = jnp.arange(2 * 4 * 2 * 8, dtype=jnp.float32).reshape(2, 4, 2, 8)
    a, b = _permuted(x), Format(Layout((0, 1, 3, 2), ()), x.sharding)
    keys = {store_mod.store_key('p', 't', 's', ([x], 1.0),
                                store_mod.pool_formats(
                                    (programs.PoolIO(0, (0,), lambda f=f:
                                                     [f]),)))
            for f in (None, a, b, Format(Layout.AUTO, x.sharding))}
    assert len(keys) == 4
    # ... and one that asks nothing is keyed as it always was
    assert store_mod.store_key('p', 't', 's', ([x], 1.0)) in keys

    (out,), total = _pool_program([a])([jax.device_put(x, a)],
                                       jnp.float32(1))
    assert out.format == a and float(total) == float(x.sum())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) + 1)
    (man_path,) = [os.path.join(directory, f)
                   for f in os.listdir(directory) if f.endswith('.json')]
    with open(man_path) as f:
        manifest = json.load(f)
    assert manifest['pool_formats'] == [{
        'arg': 0, 'result': [0], 'formats': [{
            'major_to_minor': [0, 2, 1, 3], 'tiling': []}]}]
    assert manifest['donate_argnums'] == [0]

    # a warm process loads it, compiled to the recorded formats
    store = open_store(directory)
    (out,), _ = _pool_program([a])([jax.device_put(x, a)], jnp.float32(2))
    assert store.stats()['hits_disk'] == 1 and out.format == a
    (ent,) = store._mem.values()
    assert _tree.tree_leaves(ent.callable.input_formats[0][0]) == [a]

    # a pool held otherwise is another key: nothing loaded, compiled anew
    store = open_store(directory)
    (out,), _ = _pool_program([b])([jax.device_put(x, b)], jnp.float32(2))
    assert store.stats()['hits_disk'] == 0 and out.format == b
    assert store.disk_entries() == 2

    # a manifest that records another format than its key stands for is
    # rejected, not loaded: the program is compiled afresh
    manifest['pool_formats'][0]['formats'][0]['major_to_minor'] = \
        [0, 1, 3, 2]
    with open(man_path, 'w') as f:
        json.dump(manifest, f)
    store = open_store(directory)
    (out,), _ = _pool_program([a])([jax.device_put(x, a)], jnp.float32(3))
    st = store.stats()
    assert (st['hits_disk'], st['rejects'], st['misses']) == (0, 1, 1)
    assert out.format == a
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) + 3)


def test_a_program_jaxs_compile_cache_hands_back_runs_on_what_it_returned(
        open_store, tmp_path):
    """jax's persistent compile cache ON, as in every process that
    serves: the executable it hands back writes its results in the
    layout it was compiled to, but the arrays it returns SAY they lie in
    the default one (jax 0.9.0), and jax would refuse them as the next
    call's argument. The store calls such a program without that check
    (`_LayoutsOnTrust`): the pool goes round and the values are right."""
    if not _cpu_takes_a_layout():
        pytest.skip('this backend takes no layout but its default')
    directory = str(tmp_path / 'store')
    x = jnp.arange(2 * 4 * 2 * 8, dtype=jnp.float32).reshape(2, 4, 2, 8)
    a = _permuted(x)
    for process in range(3):        # the first compiles, the others load
        open_store(directory)
        pool = [jax.device_put(x, a)]
        program = _pool_program([a])
        for turn in range(1, 4):
            pool, total = program(pool, jnp.float32(1))
            np.testing.assert_array_equal(np.asarray(pool[0]),
                                          np.asarray(x) + turn)
        assert float(total) == float((x + 2).sum())
        (_, call), = program._entries.values()
        assert _tree.tree_leaves(call.input_formats[0][0]) == [a]
        assert _tree.tree_leaves(call.output_formats[0]) == [a]


def test_preload_compiles_an_entry_to_its_recorded_formats(
        open_store, tmp_path, no_compile_cache):
    if not _cpu_takes_a_layout():
        pytest.skip('this backend takes no layout but its default')
    directory = str(tmp_path / 'store')
    open_store(directory)
    x = jnp.ones((2, 4, 2, 8), jnp.float32)
    a = _permuted(x)
    _pool_program([a])([jax.device_put(x, a)], jnp.float32(1))
    store = open_store(directory)
    assert store.preload()['loaded'] == 1
    (ent,) = store._mem.values()
    assert _tree.tree_leaves(ent.callable.input_formats[0][0]) == [a]
    assert _tree.tree_leaves(ent.callable.output_formats[0]) == [a]


# ---------------------------------------------------------------------------
# the pool's book
# ---------------------------------------------------------------------------
class _Spec:
    def __init__(self, shape, dtype=np.float32):
        self.shape, self.dtype = shape, dtype


def _tiled(major_to_minor, *tiles):
    """A tiled layout as the TPU names them; built in a test, the book
    alone reads it."""
    return lambda: Format(Layout(major_to_minor, tiles),
                          jnp.zeros(()).sharding)


@pytest.mark.parametrize('what, shape, fmt, bytes_', [
    ('the default is booked at the logical size', (32, 4096, 8, 64), None,
     32 * 4096 * 8 * 64 * 4),
    ('lfm2: 64 lanes held in tiles of 128', (32, 4096, 8, 64),
     _tiled((0, 1, 2, 3), (8, 128)), 32 * 4096 * 8 * 128 * 4),
    ('mimo full K: 192 lanes in two tiles, four sublanes', (32, 4096, 4,
     192), _tiled((0, 1, 2, 3), (4, 128)), 32 * 4096 * 4 * 256 * 4),
    ('mimo full K under eight sublanes would be twice that', (32, 4096, 4,
     192), _tiled((0, 1, 2, 3), (8, 128)), 32 * 4096 * 8 * 256 * 4),
    ('mimo ring K', (32, 128, 8, 192), _tiled((0, 1, 2, 3), (8, 128)),
     32 * 128 * 8 * 256 * 4),
    ('rows minor, the TPU default for 64 lanes: compact', (32, 4096, 8, 64),
     _tiled((0, 2, 3, 1), (8, 128)), 32 * 4096 * 8 * 64 * 4),
    ('whole tiles pad nothing', (8, 4096, 4, 128),
     _tiled((0, 1, 2, 3), (4, 128)), 8 * 4096 * 4 * 128 * 4),
])
def test_format_bytes_counts_the_padded_lanes(what, shape, fmt, bytes_):
    assert format_bytes(_Spec(shape), fmt and fmt()) == bytes_, what


def test_stats_book_the_layout_and_the_device_bytes():
    paddle.seed(0)
    model = MiMoV2ForCausalLM(MiMoV2Config.tiny()).eval()
    pool = SlotPool(model, num_slots=2, max_length=32)
    book = pool.stats()
    assert set(book['entry_layouts']) == set(book['entry_bytes'])
    assert set(book['entry_layouts'].values()) == {'default'}
    assert sum(book['entry_bytes'].values()) == pool.pool_bytes
    # K in a tiled layout of its own, V as it was: booked per entry, the
    # bytes with the lanes the tiles pad (no device needed for the book)
    leaves = _tree.tree_leaves(pool.rows)
    kinds = iter('KV' * len(leaves))
    tiled = _tiled((0, 1, 2, 3), (8, 128))()
    pool.formats = [tiled if next(kinds) == 'K' else None for _ in leaves]
    assert pool.own_layout_leaves == len(leaves) // 2
    book = pool.stats()
    for name, layout in book['entry_layouts'].items():
        assert layout == 'K 0,1,2,3:T(8,128), V default', name
    assert sum(book['entry_bytes'].values()) == sum(
        format_bytes(leaf, fmt) for leaf, fmt in zip(leaves, pool.formats))
    assert sum(book['entry_bytes'].values()) > pool.pool_bytes
    assert book['pool_bytes'] == pool.pool_bytes        # the logical ones
    pool.formats = [tiled] * len(leaves)
    assert set(pool.stats()['entry_layouts'].values()) \
        == {'0,1,2,3:T(8,128)'}
    assert layout_name(None) == 'default'
