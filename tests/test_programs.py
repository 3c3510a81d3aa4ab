"""ISSUE-8: unified persistent program store.

Tentpole coverage: compile -> persist -> warm-load round trips with
zero XLA backend compiles and bit-identical outputs; the corruption
gauntlet (truncated entry, bit-flipped payload, checksum mismatch,
fingerprint skew, half-written entry from a killed writer, racing
writers) each degrading to recompile-and-continue with
`program_cache_reject` events and counters, never an unhandled
exception; warm-restart semantics for both a trainer (resume='auto')
and a serving engine; the ref-counted /healthz `warming` state during
bulk preload; the catalog==store no-double-attribution guard; the
dispatch-cache LRU satellite; the typed `ProgramDeserializeError` in
jit.load; and the bench coldstart tier-1 guard.
"""
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import debug, jit, observability as obs, programs
from paddle_tpu.flags import set_flags
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.programs import ProgramDeserializeError
from paddle_tpu.programs import store as store_mod
from paddle_tpu.serving import InferenceEngine, SamplingParams
from paddle_tpu.serving.kv_pool import SlotPool

NO_EOS = -1


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------

@pytest.fixture
def pstore(tmp_path):
    """The process-wide store pointed at a private tmp dir; teardown
    restores the previous directory (and detaches the XLA cache) and
    the previous in-memory entries (other tests' executables stay
    resident)."""
    store = programs.get_store()
    saved_dir = store._dir
    with store._lock:
        snap = dict(store._mem)
    store.configure(str(tmp_path / 'pstore'))
    yield store
    with store._lock:
        store._mem.clear()
        store._mem.update(snap)
    store.configure(None)
    store._dir = saved_dir


def _compile_marks(reg):
    return (reg.value('paddle_jit_compiles_total'),
            reg.value('paddle_jit_cache_hits_total'))


def _real_compiles(reg, marks):
    """XLA compiles that actually ran since `marks` — backend-compile
    ticks not served by the persistent compilation cache."""
    c0, h0 = marks
    return ((reg.value('paddle_jit_compiles_total') - c0)
            - (reg.value('paddle_jit_cache_hits_total') - h0))


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _wrap(store, tag, c=2.0):
    """A distinct store-enrolled program per tag (same source, distinct
    statics -> distinct persistent key)."""
    def f(x, y):
        return jnp.sin(x) @ y + c
    return store.wrap_jit(jax.jit(f), name=f'test.{tag}', kind='jit',
                          statics={'tag': tag, 'c': c})


def _args():
    return jnp.ones((4, 4)), jnp.full((4, 4), 0.5)


def _populate(store, tag):
    """Compile + persist one entry; returns (reference output, args)."""
    w = _wrap(store, tag)
    x, y = _args()
    return np.asarray(w(x, y)), (x, y)


def _entry_files(store, tag=None):
    d = store.directory
    mans = sorted(f for f in os.listdir(d) if f.endswith('.json'))
    if tag is not None:
        mans = [f for f in mans
                if json.load(open(os.path.join(d, f)))['name']
                == f'test.{tag}']
    assert mans, f'no committed entries in {d}'
    man = os.path.join(d, mans[0])
    return man[:-len('.json')] + '.bin', man


def _reject_total(reason=None):
    reg = obs.get_registry()
    fam = reg.get('paddle_program_cache_rejects_total')
    if fam is None:
        return 0.0
    if reason is None:
        return sum(c.value for c in fam._children.values())
    return reg.value('paddle_program_cache_rejects_total', reason=reason)


def _recent_events(name):
    return [e for e in obs.get_event_log().events() if e.get('name') == name]


# ---------------------------------------------------------------------------
# round trip: compile -> persist -> warm load
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_compile_persists_and_warm_loads_with_zero_compiles(self, pstore):
        ref, (x, y) = _populate(pstore, 'rt')
        assert pstore.disk_entries() >= 1
        bin_path, man_path = _entry_files(pstore, 'rt')
        man = json.load(open(man_path))
        assert man['sha256'] and man['fingerprint']['jax']
        # simulated restart: drop the memory tier, rebuild the wrapper
        # from a NEW function object — only the disk knows the program
        pstore.clear_memory()
        reg = obs.get_registry()
        marks = _compile_marks(reg)
        w2 = _wrap(pstore, 'rt')
        out = np.asarray(w2(x, y))
        assert _real_compiles(reg, marks) == 0, \
            'warm load must not pay a real XLA compile'
        assert (out == ref).all(), 'warm output must be bit-identical'
        assert pstore.stats()['hits_disk'] >= 1
        assert _recent_events('program_cache_hit')

    def test_memory_tier_shared_across_wrappers(self, pstore):
        ref, (x, y) = _populate(pstore, 'share')
        misses = pstore.stats()['misses']
        w2 = _wrap(pstore, 'share')   # sibling wrapper, identical key
        out = np.asarray(w2(x, y))
        assert (out == ref).all()
        st = pstore.stats()
        assert st['misses'] == misses, 'sibling wrapper recompiled'
        assert st['hits_memory'] >= 1

    def test_resolve_builds_a_program_without_running_it(self, pstore):
        w = _wrap(pstore, 'resolve')
        x, y = _args()
        reg = obs.get_registry()
        misses = pstore.stats()['misses']
        record, call = w.resolve(x, y)
        assert pstore.stats()['misses'] == misses + 1     # compiled here
        assert record.invocations == 0                    # and not run
        assert _entry_files(pstore, 'resolve')
        marks = _compile_marks(reg)
        assert w.resolve(x, y) == (record, call)
        out = np.asarray(w(x, y))
        assert reg.value('paddle_jit_compiles_total') == marks[0]
        assert pstore.stats()['misses'] == misses + 1
        assert record.invocations == 1
        assert (out == np.asarray(jnp.sin(x) @ y + 2.0)).all()

    def test_a_program_is_compiled_with_room_on_the_stack(self, pstore,
                                                          monkeypatch):
        """The compile site runs trace, lowering and compile below a
        frame too large for a 16 KiB chunk of CPython's frame stack — on
        the caller's thread, so what a trace reads from its thread is
        the caller's; what they raise comes out unchanged."""
        room = store_mod._with_stack_room
        assert room.__code__.co_nlocals * 8 > 8 * 16384
        seen = []

        def spy(fn, *args):
            seen.append('in')
            try:
                return room(fn, *args)
            finally:
                seen.append('out')
        monkeypatch.setattr(store_mod, '_with_stack_room', spy)
        local = threading.local()
        local.scale = 3.0

        def f(x):
            seen.append(threading.current_thread() is me)
            return x * getattr(local, 'scale', 0.0)
        me = threading.current_thread()
        w = pstore.wrap_jit(f, name='test.stack_room', statics={})
        assert float(w(jnp.float32(2.0))) == 6.0
        # through the export artifact: traced there, compiled after
        assert seen == ['in', True, 'out', 'in', 'out']
        assert room(lambda a, b: a + b, 3, 4) == 7
        with pytest.raises(ZeroDivisionError):
            room(lambda: 1 / 0)

    def test_compile_cache_is_placed_from_outside(self, pstore, tmp_path,
                                                  monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set (conftest sets it) no code
        path repoints jax's cache — not configure(dir), not
        configure(None); unset, it resolves to <checkout>/.jax_cache."""
        env_dir = os.environ['JAX_COMPILATION_CACHE_DIR']
        assert jax.config.jax_compilation_cache_dir == env_dir
        pstore.configure(str(tmp_path / 'elsewhere'))
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        pstore.configure(None)
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert programs.ensure_compile_cache() == env_dir
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(paddle.__file__)))
        in_checkout = os.path.join(checkout, '.jax_cache')
        assert programs.compile_cache_dir() == in_checkout
        try:
            assert programs.ensure_compile_cache() == in_checkout
            assert jax.config.jax_compilation_cache_dir == in_checkout
        finally:   # back to the session's cache before anything compiles
            monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', env_dir)
            assert programs.ensure_compile_cache() == env_dir

    def test_store_without_directory_writes_nothing(self, pstore):
        d = pstore.directory
        pstore.configure(None)
        try:
            w = _wrap(pstore, 'nodisk')
            x, y = _args()
            w(x, y)
            assert not pstore.persistent
        finally:
            pstore.configure(d)
        assert not [f for f in os.listdir(d) if 'nodisk' in f]

# ---------------------------------------------------------------------------
# the corruption gauntlet: every poisoning degrades to recompile
# ---------------------------------------------------------------------------

class TestCorruptionGauntlet:
    def _assert_recovers(self, pstore, tag, ref, args, reason):
        """After the poisoning: the load path rejects (event+counter,
        right reason), the call transparently recompiles, the output is
        correct, and the store re-heals the disk entry."""
        rej0 = _reject_total(reason)
        pstore.clear_memory()
        out = np.asarray(_wrap(pstore, tag)(*args))   # must NOT raise
        assert (out == ref).all()
        assert _reject_total(reason) == rej0 + 1, \
            f'expected one {reason} reject'
        ev = _recent_events('program_cache_reject')
        assert any(e.get('attrs', {}).get('reason', '').startswith(reason)
                   for e in ev)
        # self-healed: the fresh compile re-persisted a loadable entry
        pstore.clear_memory()
        reg = obs.get_registry()
        marks = _compile_marks(reg)
        out2 = np.asarray(_wrap(pstore, tag)(*args))
        assert (out2 == ref).all()
        assert _real_compiles(reg, marks) == 0, \
            'store did not re-heal after the reject'

    def test_truncated_payload(self, pstore):
        ref, args = _populate(pstore, 'trunc')
        bin_path, _ = _entry_files(pstore, 'trunc')
        blob = open(bin_path, 'rb').read()
        with open(bin_path, 'wb') as f:
            f.write(blob[:max(1, len(blob) // 2)])
        self._assert_recovers(pstore, 'trunc', ref, args, 'checksum')

    def test_bit_flipped_payload(self, pstore):
        ref, args = _populate(pstore, 'flip')
        bin_path, _ = _entry_files(pstore, 'flip')
        blob = bytearray(open(bin_path, 'rb').read())
        blob[len(blob) // 2] ^= 0xFF
        with open(bin_path, 'wb') as f:
            f.write(bytes(blob))
        self._assert_recovers(pstore, 'flip', ref, args, 'checksum')

    def test_manifest_checksum_mismatch(self, pstore):
        ref, args = _populate(pstore, 'sum')
        _, man_path = _entry_files(pstore, 'sum')
        man = json.load(open(man_path))
        man['sha256'] = '0' * 64
        json.dump(man, open(man_path, 'w'))
        self._assert_recovers(pstore, 'sum', ref, args, 'checksum')

    def test_fingerprint_skew_stale_jaxlib(self, pstore):
        ref, args = _populate(pstore, 'skew')
        _, man_path = _entry_files(pstore, 'skew')
        man = json.load(open(man_path))
        man['fingerprint']['jaxlib'] = '0.0.1-stale'
        json.dump(man, open(man_path, 'w'))
        self._assert_recovers(pstore, 'skew', ref, args, 'fingerprint')

    def test_garbage_manifest(self, pstore):
        ref, args = _populate(pstore, 'garble')
        _, man_path = _entry_files(pstore, 'garble')
        with open(man_path, 'w') as f:
            f.write('{not json')
        self._assert_recovers(pstore, 'garble', ref, args,
                              'manifest_unreadable')

    def test_payload_missing(self, pstore):
        ref, args = _populate(pstore, 'gone')
        bin_path, _ = _entry_files(pstore, 'gone')
        os.unlink(bin_path)
        self._assert_recovers(pstore, 'gone', ref, args, 'payload_missing')

    def test_checksummed_garbage_rejects_at_deserialize(self, pstore):
        import hashlib
        ref, args = _populate(pstore, 'pickle')
        bin_path, man_path = _entry_files(pstore, 'pickle')
        garbage = b'\x80\x04not an executable at all'
        with open(bin_path, 'wb') as f:
            f.write(garbage)
        man = json.load(open(man_path))
        man['sha256'] = hashlib.sha256(garbage).hexdigest()
        json.dump(man, open(man_path, 'w'))
        self._assert_recovers(pstore, 'pickle', ref, args, 'deserialize')

    def test_half_written_entry_from_killed_writer(self, pstore):
        """A writer killed between payload and manifest leaves a
        manifest-less payload plus stray tmp files: the loader treats
        the entry as absent (clean miss, no crash) and the next compile
        commits over it."""
        ref, args = _populate(pstore, 'half')
        bin_path, man_path = _entry_files(pstore, 'half')
        os.unlink(man_path)                    # killed before commit
        with open(bin_path + '.1234.deadbeef.tmp', 'wb') as f:
            f.write(b'partial')               # killed mid-payload-write
        pstore.clear_memory()
        rej0 = _reject_total()
        out = np.asarray(_wrap(pstore, 'half')(*args))
        assert (out == ref).all()
        assert _reject_total() == rej0, 'uncommitted entry is not a reject'
        # committed again; stray tmp ignored by preload too
        assert os.path.exists(man_path)
        pstore.clear_memory()
        st = pstore.preload()
        assert st['loaded'] >= 1

    def test_racing_writers_same_store_dir(self, pstore):
        """Two processes (modeled as two independent ProgramStore
        instances over one dir) compile and persist the same key
        concurrently: atomic renames make last-writer-wins safe — both
        calls succeed, the committed entry verifies, and a third
        'process' warm-loads it."""
        stores = [programs.ProgramStore(directory=pstore.directory)
                  for _ in range(2)]
        x, y = _args()
        outs, errs = [None, None], []

        def worker(i):
            try:
                outs[i] = np.asarray(_wrap(stores[i], 'race')(x, y))
            except BaseException as e:   # noqa: BLE001
                errs.append(e)
        ts = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errs, f'racing writer raised: {errs}'
        assert (outs[0] == outs[1]).all()
        reader = programs.ProgramStore(directory=pstore.directory)
        reg = obs.get_registry()
        marks = _compile_marks(reg)
        out3 = np.asarray(_wrap(reader, 'race')(x, y))
        assert (out3 == outs[0]).all()
        assert _real_compiles(reg, marks) == 0

    def test_wipe_clears_committed_and_tmp(self, pstore):
        _populate(pstore, 'wipe')
        d = pstore.directory
        with open(os.path.join(d, 'stray.0.aaaa.tmp'), 'wb') as f:
            f.write(b'x')
        assert pstore.wipe() >= 3   # bin + manifest + stray tmp
        assert pstore.disk_entries() == 0


# ---------------------------------------------------------------------------
# preload / warming / invalidation
# ---------------------------------------------------------------------------

class TestPreload:
    def test_preload_holds_refcounted_warming_state(self, pstore,
                                                    monkeypatch):
        _populate(pstore, 'warm1')
        _populate(pstore, 'warm2')
        pstore.clear_memory()
        seen = []
        orig = programs.ProgramStore._load_disk

        def spy(self, key):
            seen.append(sorted(obs.degraded_states()))
            return orig(self, key)
        monkeypatch.setattr(programs.ProgramStore, '_load_disk', spy)
        st = pstore.preload()
        assert st['loaded'] == 2
        assert seen and all('warming' in s for s in seen), \
            '/healthz must report warming during the bulk load'
        assert 'warming' not in obs.degraded_states(), \
            'warming must clear when preload finishes'
        assert obs.health()['status'] == 'ok' or \
            'warming' not in obs.health()['states']

    def test_preload_idempotent_and_coldstart_metric(self, pstore):
        _populate(pstore, 'once')
        pstore.clear_memory()
        st1 = pstore.preload()
        assert st1['loaded'] >= 1
        st2 = pstore.preload()
        assert st2['loaded'] == 0 and st2['skipped'] >= 1
        assert pstore.stats()['coldstart_seconds'] is not None
        assert obs.get_registry().value('paddle_coldstart_seconds') > 0
        text = debug.observability_summary()
        assert 'program store:' in text and 'cold start' in text

    def test_preload_match_filter(self, pstore):
        _populate(pstore, 'pick_me')
        _populate(pstore, 'not_me')
        pstore.clear_memory()
        st = pstore.preload(match='test.pick_me')
        assert st['loaded'] == 1

    def test_refresh_fingerprint_drops_stale_entries(self, pstore):
        _populate(pstore, 'stale')
        key = next(iter(pstore._mem))
        pstore._mem[key].fingerprint = {'jaxlib': 'other'}
        dropped = pstore.refresh_fingerprint()
        assert dropped == 1
        assert pstore.stats()['invalidated'] >= 1
        assert _recent_events('program_store_invalidate')


# ---------------------------------------------------------------------------
# warm restart: trainer
# ---------------------------------------------------------------------------

def _mlp_model():
    paddle.seed(3)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    m = paddle.Model(net)
    m.prepare(
        optimizer=paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    return m


def _mlp_data(n=8):
    rng = np.random.RandomState(0)
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([
        paddle.to_tensor(rng.standard_normal((n, 16)).astype('float32')),
        paddle.to_tensor(rng.randint(0, 4, (n,)))])
    return DataLoader(ds, batch_size=4, shuffle=False)


class TestWarmRestartTrainer:
    def test_resume_auto_zero_compiles_bit_exact(self, pstore, tmp_path):
        ckpt = str(tmp_path / 'ckpt')
        # uninterrupted reference: 4 steps
        ref = _mlp_model().fit(_mlp_data(), epochs=2, verbose=0)
        # leg 1: 2 steps (1 epoch), checkpointed, programs persisted
        m1 = _mlp_model()
        m1.fit(_mlp_data(), epochs=1, verbose=0, ckpt_dir=ckpt)
        assert pstore.disk_entries() >= 1
        # 'process restart': fresh Model, empty store memory
        pstore.clear_memory()
        m2 = _mlp_model()
        reg = obs.get_registry()
        marks = _compile_marks(reg)
        hist = m2.fit(_mlp_data(), epochs=2, verbose=0, ckpt_dir=ckpt,
                      resume='auto')
        assert _real_compiles(reg, marks) == 0, \
            'warm resume must not pay any real XLA compile'
        assert pstore.stats()['hits_disk'] >= 1
        # the resumed trajectory is bit-exact vs the uninterrupted run
        assert hist['loss'] == ref['loss'][2:]

    def test_fit_preload_is_noop_without_store_dir(self, tmp_path):
        store = programs.get_store()
        saved = store._dir
        store.configure(None)
        try:
            hist = _mlp_model().fit(_mlp_data(), epochs=1, verbose=0)
            assert len(hist['loss']) == 2
        finally:
            store._dir = saved


# ---------------------------------------------------------------------------
# one compile site, one donation rule: a program's declared
# donate_argnums is applied on every route to an executable
# ---------------------------------------------------------------------------

@pytest.fixture
def open_store(monkeypatch):
    """`open_store(directory)` replaces the process-wide store with a
    NEW, empty one pointed at `directory` (None: no persistent tier) —
    what a fresh process would hold. Teardown puts the old one back."""
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


def _mlp_step(offload=None):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.BatchNorm1D(16), nn.ReLU(),
                        nn.Linear(16, 4))
    kind = paddle.optimizer.AdamW if offload else paddle.optimizer.SGD
    extra = {'offload': offload} if offload else {}
    opt = kind(learning_rate=0.01, parameters=net.parameters(), **extra)
    step = jit.TrainStep(net, lambda o, l: F.cross_entropy(o, l), opt)
    rng = np.random.RandomState(0)
    batch = step._as_batch(
        paddle.to_tensor(rng.standard_normal((4, 8)).astype('float32')),
        paddle.to_tensor(rng.randint(0, 4, (4,))))
    params, frozen, buffers = jit.functional_state(net)
    key = jax.random.fold_in(step._step_key_root, 0)
    return step, opt, params, frozen, buffers, key, batch


def _prog_train_step():
    step, opt, params, frozen, buffers, key, batch = _mlp_step()
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    return step._jitted, (params, opt.init_state(params), buffers, frozen,
                          key, lr, batch)


def _prog_train_step_grads():
    step, _, params, frozen, buffers, key, batch = _mlp_step('host')
    return step._jitted_grads, (params, buffers, frozen, key, batch)


def _engine(gpt, **kw):
    return InferenceEngine(gpt, num_slots=2, max_length=32,
                           decode_block=2, **kw)


def _slot_args(eng):
    """The slot state as every decode and speculation program takes it:
    ONE buffer (ISSUE 36), where nine arrays (ten with `eos`) stood."""
    return (eng._slot_state.buffer,)


def _decode_slot_args(eng):
    """A decode block takes, after the buffer, the tokens of the block
    before (ISSUE 45): the device's, never donated."""
    return (eng._slot_state.buffer, eng._prev_toks)


def _prog_decode(gpt):
    eng = _engine(gpt)
    return eng._decode_jit, (eng._params, eng._frozen, eng._buffers,
                             eng.pool.cache, *_decode_slot_args(eng))


def _prog_paged_decode(gpt):
    eng = _engine(gpt, kv_page_size=8)
    pages, scales = eng.pool.device_state()
    return eng._decode_jit, (eng._params, eng._frozen, eng._buffers,
                             pages, scales, jnp.asarray(eng.pool.page_table),
                             *_decode_slot_args(eng))


def _prog_spec(gpt):
    eng = _engine(gpt, draft_model=gpt, num_draft_tokens=2)
    return eng._spec_jit, (eng._params, eng._frozen, eng._buffers,
                           eng.pool.cache, *eng._draft_state,
                           eng.draft_pool.cache, *_slot_args(eng))


def _prog_paged_spec(gpt):
    eng = _engine(gpt, kv_page_size=8, draft_model=gpt,
                  num_draft_tokens=2)
    pages, scales = eng.pool.device_state()
    return eng._spec_jit, (eng._params, eng._frozen, eng._buffers,
                           pages, scales, jnp.asarray(eng.pool.page_table),
                           *eng._draft_state, eng.draft_pool.cache,
                           *_slot_args(eng))


def _pool_row(gpt, pool, value):
    return jax.tree_util.tree_map(
        lambda c: jnp.full((1,) + c.shape[1:], value, c.dtype), pool.rows)


def _prog_set_row(gpt):
    pool = SlotPool(gpt, num_slots=3, max_length=16)
    return pool._seat_jit, (pool.rows, _pool_row(gpt, pool, 1.5),
                            jnp.int32(1))


def _prog_copy_slot(gpt):
    pool = SlotPool(gpt, num_slots=3, max_length=16)
    rows = jax.tree_util.tree_map(
        lambda c: jnp.arange(c.size, dtype=c.dtype).reshape(c.shape),
        pool.rows)
    return pool._copy_jit, (rows, jnp.int32(0), jnp.int32(2))


#: name -> (builder of (StoredJit, args), declared donate_argnums)
_DONATING_PROGRAMS = {
    'train_step': (lambda gpt: _prog_train_step(), (0, 1, 2)),
    'train_step_grads': (lambda gpt: _prog_train_step_grads(), (1,)),
    'decode_block': (_prog_decode, (3,)),
    'paged_decode_block': (_prog_paged_decode, (3, 4)),
    'spec_decode': (_prog_spec, (3, 7)),
    'paged_spec_decode': (_prog_paged_spec, (3, 4, 9)),
    'set_row': (_prog_set_row, (0,)),
    'copy_slot': (_prog_copy_slot, (0,)),
}
_REFERENCES = {}


def _copy_args(args):
    return jax.tree_util.tree_map(
        lambda v: jnp.array(v) if isinstance(v, jax.Array) else v, args)


def _unstored_reference(name, wrapper, args, donate):
    """What a plain `jax.jit` of the same function gives at these
    arguments, and the bytes it aliases when it donates as declared —
    no store, no export, computed once per program."""
    if name not in _REFERENCES:
        raw = wrapper._fn.__wrapped__
        out = jax.jit(raw)(*_copy_args(args))
        mem = jax.jit(raw, donate_argnums=donate).lower(
            *args).compile().memory_analysis()
        _REFERENCES[name] = (
            [np.asarray(v) for v in jax.tree_util.tree_leaves(out)],
            mem.alias_size_in_bytes)
    return _REFERENCES[name]


class TestDonationRoutes:
    """Every program that declares a donation, on every route to its
    executable: the compiled program aliases the declared arguments,
    the call consumes them, and the results are those of an unstored
    `jax.jit` of the same function."""

    @pytest.mark.parametrize('route', ['direct', 'cold_export',
                                       'warm_load'])
    @pytest.mark.parametrize('name', list(_DONATING_PROGRAMS))
    def test_declared_donation_is_applied(self, name, route, gpt,
                                          open_store, tmp_path):
        build, donate = _DONATING_PROGRAMS[name]
        directory = None if route == 'direct' else str(tmp_path / 'store')
        store = open_store(directory)
        wrapper, args = build(gpt)
        assert wrapper._donate == donate
        ref_out, ref_alias = _unstored_reference(name, wrapper, args,
                                                 donate)
        reg = obs.get_registry()
        if route == 'warm_load':
            wrapper(*args)                   # the cold process persists
            assert store.stats()['persisted'] == 1
            store = open_store(directory)    # the warm one starts empty
            marks = _compile_marks(reg)
            wrapper, args = build(gpt)
        donated = [leaf for i in donate
                   for leaf in jax.tree_util.tree_leaves(args[i])]
        kept = [leaf for i, a in enumerate(args) if i not in donate
                for leaf in jax.tree_util.tree_leaves(a)
                if isinstance(leaf, jax.Array)]
        out = wrapper(*args)
        (_, compiled), = wrapper._entries.values()
        st = store.stats()
        if route == 'warm_load':
            assert _real_compiles(reg, marks) == 0
            assert (st['hits_disk'], st['misses']) == (1, 0)
        else:
            assert (st['hits_disk'], st['misses']) == (0, 1)
            assert st['persisted'] == (route == 'cold_export')
        assert ref_alias > 0
        assert compiled.memory_analysis().alias_size_in_bytes == ref_alias
        assert donated and all(leaf.is_deleted() for leaf in donated)
        assert not any(leaf.is_deleted() for leaf in kept)
        got = [np.asarray(v) for v in jax.tree_util.tree_leaves(out)]
        assert len(got) == len(ref_out)
        for g, r in zip(got, ref_out):
            assert g.dtype == r.dtype and g.tobytes() == r.tobytes()


@pytest.mark.parametrize('fallback', ['aot_noexport', 'persist_skipped'])
def test_fallbacks_keep_the_declared_donation(fallback, open_store,
                                              tmp_path):
    """A program the persistent tier cannot take — an argument the
    export refuses, a directory that cannot be written — is still
    compiled, donated as declared, and served from the memory tier."""
    directory = tmp_path / 'store'
    store = open_store(str(directory))

    def f(x, key):
        return x + 1.0, key

    w = store.wrap_jit(f, name=f'test.{fallback}', donate_argnums=(0,))
    x = jnp.ones((4, 4))
    if fallback == 'aot_noexport':
        key = jax.random.key(0)              # typed keys do not export
    else:
        key = jax.random.PRNGKey(0)
        directory.rmdir()
        directory.write_text('not a directory')
    out, _ = w(x, key)
    assert x.is_deleted() and (np.asarray(out) == 2.0).all()
    (_, compiled), = w._entries.values()
    assert compiled.memory_analysis().alias_size_in_bytes == 64
    st = store.stats()
    assert (st['misses'], st['persisted']) == (1, 0)
    skipped = [e for e in _recent_events('program_store_persist_skipped')
               if e['attrs']['program'] == f'test.{fallback}']
    assert len(skipped) == 1
    record = store.catalog.record(f'test.{fallback}')
    if fallback == 'aot_noexport':
        assert record.note == 'aot_noexport'
    else:
        assert st['persist_skips'] == 1 and record.note == ''
        directory.unlink()


def _train_losses(steps=3):
    rng = np.random.RandomState(0)
    x = rng.standard_normal((16, 32)).astype('float32')
    y = rng.randint(0, 4, (16,))
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=m.parameters())
    step = jit.TrainStep(m, lambda o, l: F.cross_entropy(o, l), opt)
    return [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
            for _ in range(steps)]


def _greedy_tokens(gpt):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).tolist() for n in (5, 9, 13, 7)]
    eng = InferenceEngine(gpt, num_slots=4, max_length=64)
    handles = eng.generate_many(
        prompts, SamplingParams(max_new_tokens=6, eos_token_id=NO_EOS))
    return [list(h.tokens) for h in handles]


def _alias_bytes(store, name):
    with store._lock:
        return [e.callable.memory_analysis().alias_size_in_bytes
                for e in store._mem.values() if e.name == name]


def test_store_served_donated_losses_bit_exact(open_store, tmp_path,
                                               sanitizer_strict):
    """A train loop served through the export artifact, cold and then
    warm from disk, donates its state and reproduces the losses of the
    directory-less run bit for bit."""
    open_store(None)
    ref = _train_losses()
    for source in ('compile', 'disk'):
        store = open_store(str(tmp_path / 'store'))
        assert _train_losses() == ref
        (ent,) = [e for e in store.entries() if e['name'] == 'train_step']
        assert (ent['source'], ent['format']) == (source, 'stablehlo')
        assert all(n > 0 for n in _alias_bytes(store, 'train_step'))


def test_donated_pool_greedy_parity_store_served(gpt, open_store, tmp_path,
                                                 sanitizer_strict):
    """Serving through the export artifact, cold and then warm, aliases
    the whole pool into the decode block and serves the tokens of the
    directory-less engine."""
    open_store(None)
    ref = _greedy_tokens(gpt)
    for source in ('compile', 'disk'):
        store = open_store(str(tmp_path / 'store'))
        assert _greedy_tokens(gpt) == ref
        (ent,) = [e for e in store.entries()
                  if e['name'] == 'serving.decode_block']
        assert (ent['source'], ent['format']) == (source, 'stablehlo')
        pool_bytes = SlotPool(gpt, num_slots=4, max_length=64).pool_bytes
        assert _alias_bytes(store, 'serving.decode_block') == [pool_bytes]


@pytest.mark.parametrize('route', ['direct', 'cold_export', 'warm_load'])
def test_every_route_compiles_through_one_function(route, open_store,
                                                   monkeypatch, tmp_path):
    """`store._compile_program` is the store's one compile site: each
    route to an executable calls it exactly once, with the declared
    donation."""
    directory = None if route == 'direct' else str(tmp_path / 'store')
    store = open_store(directory)

    def build():
        def f(x, y):
            return x * 2.0 + y, y
        return store_mod.get_store().wrap_jit(
            f, name='test.one_site', statics={'route': route},
            donate_argnums=(0,))

    if route == 'warm_load':
        build()(*_args())
        store = open_store(directory)
    calls = []
    real = store_mod._compile_program

    def counting(fn, args, donate_argnums=(), formats=()):
        assert not formats      # no pool declared: nothing asked
        calls.append(tuple(donate_argnums))
        return real(fn, args, donate_argnums, formats)

    monkeypatch.setattr(store_mod, '_compile_program', counting)
    x, y = _args()
    out, _ = build()(x, y)
    assert calls == [(0,)]
    assert x.is_deleted() and not y.is_deleted()
    assert (np.asarray(out) == 2.5).all()
    assert store.stats()['hits_disk'] == (route == 'warm_load')


# ---------------------------------------------------------------------------
# a build books itself by phase (ISSUE 48)
# ---------------------------------------------------------------------------

_BUILD_FAMILY = 'paddle_program_build_seconds_total'


def _build_seconds():
    """phase -> the counter family's running seconds ({} undeclared)."""
    fam = obs.get_registry().get(_BUILD_FAMILY)
    return {} if fam is None else {
        key[0]: child.value for key, child in fam.children()}


def _builds(source):
    return obs.get_registry().value('paddle_program_builds_total',
                                    source=source)


def _moved(before):
    now = _build_seconds()
    return {p: now.get(p, 0.0) - before.get(p, 0.0)
            for p in obs.telemetry.BUILD_PHASES}


def _phases_of(record):
    return {f: getattr(record, f) for f in obs.cost.BUILD_FIELDS}


def _layered():
    """-> a NEW function (jax keeps what it traced and lowered by the
    function) whose layers are jitted functions of their own: jax times
    each inner trace, and the outer one around them all."""
    def layered(x, y):
        layer = jax.jit(lambda h, w: jnp.tanh(h @ w))
        for _ in range(6):
            x = layer(x, y)
        return jnp.sin(x) @ y
    return layered


@pytest.mark.parametrize('route', ['direct', 'cold_export', 'warm_load',
                                   'memory', 'helper_thread'])
def test_a_build_books_itself_by_phase(route, open_store, monkeypatch,
                                       tmp_path):
    """One `StoredJit._build` is one build: its wall, jax's own split
    inside it (never past the wall, a nested trace booked once), one
    `program_built` event once it has run, one `first_call` — on every
    route to an executable, and with the compile on another thread than
    the build's."""
    directory = str(tmp_path / 'store') if route in (
        'cold_export', 'warm_load') else None
    store = open_store(directory)

    def build():
        return store_mod.get_store().wrap_jit(
            _layered(), name=f'test.build_{route}', kind='serving',
            statics={'route': route})

    x, y = _args()
    if route == 'warm_load':
        build()(x, y)
        store = open_store(directory)
    elif route == 'memory':
        build()(x, y)           # a sibling wrapper compiled it
    elif route == 'helper_thread':
        room = store_mod._with_stack_room

        def on_a_thread(fn, *args):
            out = []
            t = threading.Thread(
                target=lambda: out.append(room(fn, *args)))
            t.start()
            t.join()
            return out[0]
        monkeypatch.setattr(store_mod, '_with_stack_room', on_a_thread)
    source = {'warm_load': 'disk', 'memory': 'memory'}.get(route, 'compile')
    before, n_before = _build_seconds(), _builds(source)
    events = len(_recent_events('program_built'))
    w = build()
    had = _phases_of(store_mod.get_store().catalog.record(
        f'test.build_{route}', kind='serving'))
    record, call = w.resolve(x, y)          # built, and not run
    got = _moved(before)
    mine = {f: v - had[f] for f, v in _phases_of(record).items()}
    assert _builds(source) == n_before + 1
    assert got['first_call'] == 0.0 and not hasattr(call, 'program')
    assert len(_recent_events('program_built')) == events
    split = got['trace'] + got['lower'] + got['backend']
    assert got['wall'] >= split
    if route == 'memory':
        assert split == 0.0
    else:
        assert got['lower'] > 0 and got['backend'] > 0
        # the exported module is traced as one call; the function
        # itself, with its six inner traces inside the outer one, on
        # the routes that trace it
        assert got['trace'] > 0
    assert mine['build_seconds'] == pytest.approx(got['wall']) \
        and got['wall'] > 0
    assert mine['trace_seconds'] + mine['lower_seconds'] \
        + mine['backend_seconds'] == pytest.approx(split)
    w(x, y)
    got = _moved(before)
    assert got['first_call'] > 0
    assert record.first_call_seconds - had['first_call_seconds'] \
        == pytest.approx(got['first_call'])
    (ev,) = _recent_events('program_built')[events:]
    assert ev['attrs']['program'] == f'test.build_{route}'
    assert (ev['attrs']['kind'], ev['attrs']['source']) \
        == ('serving', source)
    assert {f'{p}_seconds' for p in obs.telemetry.BUILD_PHASES} \
        <= set(ev['attrs'])
    assert ev['attrs']['wall_seconds'] == pytest.approx(got['wall'],
                                                        abs=1e-5)
    # a second call of the same signature: nothing booked, no event,
    # and no span beside the two of every call
    log = obs.get_event_log()
    seq = max(e['seq'] for e in log.events())
    settled = _build_seconds()
    w(x, y)
    assert _build_seconds() == settled
    assert _builds(source) == n_before + 1
    assert [e['name'] for e in log.events() if e['seq'] > seq] \
        == ['serving.program_resolve', 'serving.program_call']
    assert record.invocations >= 2


def test_a_nested_trace_is_booked_once(open_store):
    """jax fires a trace duration for every jitted function traced
    inside another's trace, inner ones first: the process-wide counter
    takes them all, the build only the outermost."""
    open_store(None)
    reg = obs.get_registry()
    traced = reg.value('paddle_jit_trace_seconds_total')
    before = _build_seconds()
    w = store_mod.get_store().wrap_jit(
        _layered(), name='test.nested', statics={})
    w.resolve(*_args())
    got = _moved(before)
    every = reg.value('paddle_jit_trace_seconds_total') - traced
    assert 0 < got['trace'] < every
    assert got['trace'] + got['lower'] + got['backend'] <= got['wall']


def test_a_jit_outside_any_build_moves_only_the_process_counters():
    reg = obs.get_registry()
    store_mod.get_store().wrap_jit(
        lambda x: x + 1.0, name='test.declares', statics={})(
            jnp.float32(1.0))           # the families exist
    before = _build_seconds()
    built = reg.get('paddle_program_builds_total').total()
    marks = (reg.value('paddle_jit_compiles_total'),
             reg.value('paddle_jit_compile_seconds_total'),
             reg.value('paddle_jit_trace_seconds_total'))
    events = len(_recent_events('program_built'))
    out = jax.jit(lambda a: jnp.cos(a) * 3.0 + 0.125)(jnp.ones((5, 3)))
    assert out.shape == (5, 3)
    assert reg.value('paddle_jit_compiles_total') > marks[0]
    assert reg.value('paddle_jit_compile_seconds_total') > marks[1]
    assert reg.value('paddle_jit_trace_seconds_total') > marks[2]
    assert _build_seconds() == before
    assert reg.get('paddle_program_builds_total').total() == built
    assert len(_recent_events('program_built')) == events


def test_with_observability_off_a_build_books_nothing(open_store):
    open_store(None)
    before = _build_seconds()
    built = obs.get_registry().get('paddle_program_builds_total')
    built = built.total() if built is not None else 0.0
    events = len(obs.get_event_log())
    obs.disable()
    try:
        w = store_mod.get_store().wrap_jit(
            _layered(), name='test.dark', statics={})
        x, y = _args()
        record, call = w.resolve(x, y)
        assert (np.asarray(w(x, y)) == np.asarray(call(x, y))).all()
        paddle.jit.TrainStep       # the decorated constructors still run
        _mlp_step()
    finally:
        obs.enable()
    assert _build_seconds() == before
    fam = obs.get_registry().get('paddle_program_builds_total')
    assert (fam.total() if fam is not None else 0.0) == built
    assert len(obs.get_event_log()) == events
    # the catalog's own wall is kept, like `host_seconds`
    assert record.build_seconds > 0 and record.compile_seconds > 0
    assert record.trace_seconds == record.first_call_seconds == 0.0
    assert record.invocations == 1


def test_top_programs_carries_the_phases(open_store):
    open_store(None)
    w = store_mod.get_store().wrap_jit(
        _layered(), name='test.table', statics={})
    w(*_args())
    row, = [r for r in store_mod.get_store().catalog.top_programs(n=1000)
            if r['name'] == 'test.table']
    assert row['build_seconds'] >= row['trace_seconds'] \
        + row['lower_seconds'] + row['backend_seconds'] > 0
    assert row['first_call_seconds'] > 0
    head = store_mod.get_store().catalog.report().splitlines()[1]
    for column in ('compile s', 'build s', 'trace s', 'lower s',
                   'backend s', '1st call s'):
        assert column in head


# ---------------------------------------------------------------------------
# warm restart: serving replica
# ---------------------------------------------------------------------------

class TestWarmRestartServing:
    def test_cold_replica_decodes_with_zero_compiles(self, pstore, gpt):
        prompts = [[1, 2, 3], [5, 6, 7, 8, 9]]
        sp = [SamplingParams(max_new_tokens=5, eos_token_id=NO_EOS)] * 2
        eng1 = InferenceEngine(gpt, num_slots=2, max_length=48,
                               decode_block=2)
        ref = [h.tokens for h in eng1.generate_many(prompts, sp)]
        assert pstore.disk_entries() >= 2   # decode block + bucket(s)
        # 'replica restart': fresh engine, disk-only knowledge
        pstore.clear_memory()
        reg = obs.get_registry()
        marks = _compile_marks(reg)
        eng2 = InferenceEngine(gpt, num_slots=2, max_length=48,
                               decode_block=2)
        got = [h.tokens for h in eng2.generate_many(prompts, sp)]
        assert _real_compiles(reg, marks) == 0, \
            'warm replica must not pay any real XLA compile'
        assert got == ref, 'warm replica outputs must be bit-identical'
        assert not eng2._trace_counts, \
            'warm replica must never re-trace python'
        assert pstore.stats()['hits_disk'] >= 2

    def test_engine_auto_preloads_on_persistent_store(self, pstore, gpt):
        eng1 = InferenceEngine(gpt, num_slots=2, max_length=48,
                               decode_block=2)
        eng1.generate_many(
            [[4, 4, 4]],
            [SamplingParams(max_new_tokens=3, eos_token_id=NO_EOS)])
        pstore.clear_memory()
        InferenceEngine(gpt, num_slots=2, max_length=48, decode_block=2)
        assert pstore.stats()['loaded_from_disk'] >= 1, \
            'engine construction must preload persisted serving programs'


# ---------------------------------------------------------------------------
# ISSUE 36: a signature's key names a dtype by a name looked up once a
# dtype (`_DTYPE_NAME`), not by `str(dtype)` a leaf a call: the same keys
# ---------------------------------------------------------------------------

def _weights(dtype=jnp.float32, cols=4):
    return {'a': jnp.ones((4, cols), dtype), 'b': jnp.ones((cols,), dtype),
            'c': {'d': jnp.zeros((2, 2), dtype)}}


def _keyed(store, tag):
    def f(w, x, k):
        return x @ w['a'] + w['b'] + w['c']['d'].sum() + k
    return store.wrap_jit(f, name=f'test.{tag}', kind='jit',
                          statics={'tag': tag})


class TestSignatureKey:
    def test_the_key_is_the_one_str_of_the_dtype_gave(self, open_store):
        store = open_store(None)
        prog = _keyed(store, 'key')
        w, x = _weights(jnp.bfloat16), jnp.ones((3, 4))
        key, leaves, host = prog._signature((w, x, np.float32(1)))
        treedef, sig = key
        assert treedef == jax.tree_util.tree_structure((w, x, 0))
        assert sig == (((4, 4), 'bfloat16', False), ((4,), 'bfloat16', False),
                       ((2, 2), 'bfloat16', False), ((3, 4), 'float32', False),
                       ((), 'float32', False))
        assert (leaves, host) == (5, 1)
        assert all(name == str(dt)
                   for dt, name in store_mod._DTYPE_NAME.items())
        # a Python scalar and a weak-typed value keep keys of their own
        assert prog._signature((w, x, 1))[0][1][-1] == ('py', int)
        assert prog._signature((w, x, jnp.float32(1)))[0] == key
        assert prog._signature((w, x, jnp.asarray(1.0)))[0][1][-1] \
            == ((), 'float32', True)

    def test_equal_arguments_find_one_program(self, open_store):
        store = open_store(None)
        prog = _keyed(store, 'equal')
        x = jnp.ones((3, 4))
        log = obs.get_event_log()
        log.clear()
        first = prog(_weights(), x, np.float32(1))
        second = prog(_weights(), x + 1, np.float32(2))
        attrs = [e['attrs'] for e in log.events()
                 if e['name'] == 'jit.program_resolve']
        assert attrs == [{'leaves': 5, 'host_leaves': 1}] * 2
        assert len(prog._entries) == 1 and store.stats()['misses'] == 1
        assert np.asarray(second - first).tolist() == [[5.0] * 4] * 3

    @pytest.mark.parametrize('other', ['dtype', 'shape', 'tree'])
    def test_arguments_of_another_kind_find_another_program(
            self, open_store, other):
        store = open_store(None)
        w, x = _weights(), jnp.ones((3, 4))
        prog = _keyed(store, f'replace_{other}')
        out = prog(w, x, np.float32(0))
        assert out.dtype == jnp.float32
        if other == 'dtype':
            w2 = _weights(jnp.bfloat16)
            prog(w2, x, np.float32(0))
        elif other == 'shape':
            w2 = _weights(cols=6)
            assert prog(w2, x, np.float32(0)).shape == (3, 6)
        else:
            w2 = dict(_weights(), e=jnp.ones(()))
            prog(w2, x, np.float32(0))
        assert len(prog._entries) == 2 and store.stats()['misses'] == 2
        assert prog.resolve(w2, x, np.float32(0))[1] \
            is not prog.resolve(w, x, np.float32(0))[1]
        # and a mismatch the program cannot take still raises
        with pytest.raises(TypeError):
            prog(_weights(cols=6), jnp.ones((3, 5)), np.float32(0))

    def test_swapped_weights_find_the_program_they_had(self, open_store,
                                                       gpt):
        """`swap_weights` builds new dicts of the same kind: the next
        round's signature is the last one's — no compile, no trace,
        the same tokens."""
        open_store(None)
        eng = _engine(gpt)
        sp = SamplingParams(max_new_tokens=6, eos_token_id=NO_EOS)
        toks = eng.generate_many([[3, 1, 4, 1, 5]], [sp])[0].tokens
        compiles = obs.get_registry().value('paddle_jit_compiles_total')
        traces = dict(eng.stats()['traces'])
        entries = len(eng._decode_jit._entries)
        eng.swap_weights(gpt.state_dict(), version=2)
        assert eng.generate_many([[3, 1, 4, 1, 5]], [sp])[0].tokens == toks
        assert eng.stats()['traces'] == traces
        assert len(eng._decode_jit._entries) == entries
        assert obs.get_registry().value('paddle_jit_compiles_total') \
            == compiles


# ---------------------------------------------------------------------------
# satellite: no double attribution (catalog == store)
# ---------------------------------------------------------------------------

_CONSISTENCY_CHILD = r'''
import json
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import jit, observability as obs, programs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import InferenceEngine, SamplingParams
from paddle_tpu.serving.kv_pool import SlotPool

paddle.seed(0)
# tier 1: eager dispatch (catalog 'dispatch' records, store-external)
x = paddle.ones([8, 8])
for _ in range(3):
    x = x * 1.0 + 0.5
# tier 2: jitted train step + to_static
net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
opt = paddle.optimizer.SGD(learning_rate=0.01,
                           parameters=net.parameters())
step = jit.TrainStep(net, lambda o, l: F.cross_entropy(o, l), opt)
ids = paddle.to_tensor(np.random.RandomState(0).standard_normal(
    (4, 8)).astype('float32'))
lab = paddle.to_tensor(np.array([0, 1, 2, 3]))
step(ids, lab); step(ids, lab)

@paddle.jit.to_static
def affine(t):
    return t @ t + 1.0
affine(paddle.ones([4, 4]))
# tier 3: the serving engine
gpt = GPTForCausalLM(GPTConfig.tiny()).eval()
eng = InferenceEngine(gpt, num_slots=2, max_length=32, decode_block=2)
eng.generate_many([[1, 2, 3]],
                  [SamplingParams(max_new_tokens=3, eos_token_id=-1)])
res = programs.get_store().verify_catalog_consistency()
cat = obs.program_catalog()
res['n_dispatch'] = sum(1 for r in cat.records() if r.kind == 'dispatch')
print(json.dumps(res))
'''


def test_catalog_store_consistency_after_example_flow():
    """Satellite: once the store owns compilation, every jitted-tier
    program is tracked by exactly one catalog record — store entry
    names == catalog record names (dispatch-tier records excluded; they
    mirror the eager cache through the same catalog). Run in a fresh
    process so the comparison sees exactly one flow."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, '-c', _CONSISTENCY_CHILD],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.join(os.path.dirname(__file__), '..'))
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res['consistent'], (
        f"double attribution: only_in_store={res['only_in_store']} "
        f"only_in_catalog={res['only_in_catalog']}")
    assert len(res['store']) >= 4   # train_step, to_static, decode, prefill
    assert 'train_step' in res['store']
    assert 'serving.decode_block' in res['store']
    assert any(n.startswith('to_static:') for n in res['store'])
    assert res['n_dispatch'] >= 1   # eager tier reported, not duplicated


# ---------------------------------------------------------------------------
# satellite: bounded eager dispatch cache (LRU + flag + counter)
# ---------------------------------------------------------------------------

class TestDispatchLRUBound:
    def _op_on(self, n):
        # distinct shape => distinct dispatch key for the same op
        t = paddle.to_tensor(np.ones(n, np.float32))
        return (t * 2.0).numpy()

    def test_cap_bounds_cache_and_counts_evictions(self):
        from paddle_tpu import _dispatch
        debug.clear_dispatch_cache()
        debug.reset_dispatch_stats()
        set_flags({'FLAGS_eager_dispatch_cache_size': 4})
        try:
            for n in range(1, 10):
                self._op_on(n)
            s = _dispatch.stats()
            assert s['cache_size'] <= 4, s
            assert s['evictions'] > 0
            # the registry mirror exposes the evictions to scrapes
            obs.get_registry().snapshot()
            assert obs.get_registry().value(
                'paddle_dispatch_evictions_total') == s['evictions']
            text = obs.to_prometheus_text()
            assert 'paddle_dispatch_evictions_total' in text
        finally:
            set_flags({'FLAGS_eager_dispatch_cache_size': 512})
            debug.clear_dispatch_cache()

    def test_lru_keeps_the_touched_entry(self):
        from paddle_tpu import _dispatch
        debug.clear_dispatch_cache()
        debug.reset_dispatch_stats()
        set_flags({'FLAGS_eager_dispatch_cache_size': 2})
        try:
            self._op_on(2)              # A (miss)
            self._op_on(3)              # B (miss)
            self._op_on(2)              # touch A (hit)
            self._op_on(4)              # C (miss) -> evicts B, not A
            hits_before = _dispatch.stats()['hits']
            self._op_on(2)              # A must still be resident
            assert _dispatch.stats()['hits'] == hits_before + 1, \
                'LRU evicted the most-recently-touched entry'
        finally:
            set_flags({'FLAGS_eager_dispatch_cache_size': 512})
            debug.clear_dispatch_cache()


# ---------------------------------------------------------------------------
# satellite: typed deserialize error in jit.load
# ---------------------------------------------------------------------------

class TestJitLoadTyped:
    def _save(self, tmp_path):
        paddle.seed(1)
        net = nn.Linear(4, 2)
        path = str(tmp_path / 'model')
        jit.save(net, path, input_spec=[jit.InputSpec([2, 4])])
        return net, path

    def test_corrupt_artifact_raises_typed_error(self, tmp_path):
        _, path = self._save(tmp_path)
        hlo = path + '.pdmodel.stablehlo'
        blob = open(hlo, 'rb').read()
        with open(hlo, 'wb') as f:
            f.write(blob[:len(blob) // 3])
        rej0 = _reject_total('deserialize')
        with pytest.raises(ProgramDeserializeError) as ei:
            jit.load(path)
        assert ei.value.path == hlo
        assert ei.value.reason
        assert _reject_total('deserialize') == rej0 + 1
        assert _recent_events('program_cache_reject')

    def test_caller_can_fall_back_to_layer_restore(self, tmp_path):
        net, path = self._save(tmp_path)
        hlo = path + '.pdmodel.stablehlo'
        with open(hlo, 'wb') as f:
            f.write(b'garbage')
        paddle.seed(2)
        net2 = nn.Linear(4, 2)
        try:
            loaded = jit.load(path)
        except ProgramDeserializeError:
            loaded = jit.load(path, net2)   # the documented fallback
        x = paddle.ones([2, 4])
        np.testing.assert_allclose(np.asarray(loaded(x).numpy()),
                                   np.asarray(net(x).numpy()), rtol=1e-6)


# ---------------------------------------------------------------------------
# tier-1 bench guard: coldstart A/B
# ---------------------------------------------------------------------------

def _bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'bench', os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_coldstart_guard():
    """Tier-1: the warm arm of the restart A/B pays ZERO XLA compiles
    in both measured windows (train step, first served tokens) and is
    bit-identical to the cold arm."""
    res = _bench().coldstart_ab(steps=2)
    assert res['warm_train_compiles'] == 0, res
    assert res['warm_decode_compiles'] == 0, res
    assert res['cold_train_compiles'] >= 1   # the contrast is real
    assert res['parity_losses'] and res['parity_tokens'], res
    assert res['warm_loaded_from_disk'] >= 3
    assert res['warm_rejects'] == 0
    assert res['warm_cold_ratio'] > 1.0, res
