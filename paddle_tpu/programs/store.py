"""Unified persistent program store: one owner for every compiled XLA
program in the process, with a crash-safe on-disk tier.

The `ProgramStore` is the single compilation owner for the jitted tiers
(`jit.TrainStep` / `to_static`, the serving engine's decode and prefill
set, the slot pool's seat and copy programs; the eager dispatch cache
keeps its own in-process tier and reports through the same catalog).
`wrap_jit` AOT-compiles ONCE per (name, fn source, statics, treedef,
avals, sharding) key, folds the `ProgramCatalog` cost attribution in as
its bookkeeping (one `ProgramRecord` per named program — never tracked
twice), shares executables across wrappers with the same key (N serving
replicas of one model compile the decode block once), and — when a
store directory is configured — persists each program so the next
process *loads* instead of compiling.

One compile site, one donation rule. Every route to an executable —
the direct route (no directory), the cold route through the export
artifact, the warm route from a persisted payload — ends in
`_compile_program`, the only function here that lowers and compiles,
and a program's declared `donate_argnums` is applied wherever it is
compiled. The manifest records the argnums so the warm process
re-applies them. A donated program that dies mid-call may have consumed
its inputs; the owners of donated state recover from that themselves
(`InferenceEngine._recover_pool`).

Persistence is two complementary layers:

  'stablehlo'   `jax.export` bytes (the serialization `jit.save` already
                uses) — removes Python tracing from the restart path.
                The cold path compiles THROUGH the exported program, so
                the cold and warm processes compile the identical
                module.
  compile cache jax's persistent compilation cache, at the directory
                `ensure_compile_cache` resolves
                (`JAX_COMPILATION_CACHE_DIR`, else
                `<checkout>/.jax_cache`) — serves the compiled
                executable BYTES on the warm path, so re-compiling the
                deserialized module is a cache read, not an XLA
                compile. The warm-restart tier-1 guard asserts every
                `paddle_jit_compiles_total` tick in the warm window is
                matched by a `paddle_jit_cache_hits_total` tick (zero
                real compiles).

Crash safety (the robustness contract, fault-injection-tested in
tests/test_programs.py): entries are written payload-first with atomic
renames and committed by their manifest, every manifest carries a
sha256 of the payload plus a backend fingerprint (paddle_tpu/jax/jaxlib
versions, backend, device kind, device/process counts), and the load
path verifies ALL of it — a truncated file, a flipped byte, a stale
jaxlib, a half-written entry from a killed writer, or a racing second
writer can only ever produce a `program_cache_reject` event and a fresh
compile, never an exception out of the store. A poisoned cache degrades
to cold-start behavior; it cannot take down a trainer or replica.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
import uuid
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax

from .. import flags as _flags
from .. import observability as _obs
from ..analysis.runtime import concurrency as _concurrency
from ..observability import cost as _cost
from ..observability import telemetry as _telemetry

_MANIFEST_VERSION = 1

_flags.register_flag('FLAGS_program_store_dir', '')


class ProgramDeserializeError(RuntimeError):
    """A serialized program artifact could not be deserialized.

    Typed so callers (jit.load, the store's own disk tier) can fall back
    to a fresh compile instead of crashing on a raw internal exception.
    Carries the artifact path and the underlying reason."""

    def __init__(self, path: str, reason: str):
        self.path = path
        self.reason = reason
        super().__init__(f'cannot deserialize program artifact {path}: '
                         f'{reason}')


# ---------------------------------------------------------------------------
# fingerprint + keying
# ---------------------------------------------------------------------------

def backend_fingerprint() -> Dict[str, Any]:
    """The compatibility envelope of a compiled executable: an entry
    written under a different fingerprint is rejected at load (a PjRt
    executable is only valid for the exact runtime that produced it;
    StableHLO survives more skew, but version-gating both keeps the
    invalidation rule simple and safe)."""
    try:
        import jaxlib
        jaxlib_version = jaxlib.__version__
    except Exception:
        jaxlib_version = 'unknown'
    try:
        from .. import version as _version
        own = _version.full_version
    except Exception:
        own = 'unknown'
    try:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else 'none'
        count = len(devs)
    except Exception:
        kind, count = 'unknown', 0
    try:
        procs = jax.process_count()
    except Exception:
        procs = 1
    return {
        'paddle_tpu': own,
        'jax': jax.__version__,
        'jaxlib': jaxlib_version,
        'backend': jax.default_backend(),
        'device_kind': kind,
        'device_count': count,
        'process_count': procs,
    }


def code_token(fn, _depth: int = 0) -> str:
    """Best-effort stable identity for a function/class body ACROSS
    processes (the in-process `id()` the dispatch cache uses is
    meaningless after a restart): sha256 of the source text plus the
    tokens of closure cells (a generic wrapper closing over the real
    loss fn keys on THAT fn's body, not the wrapper's), falling back to
    the bytecode, falling back to the qualified name. Catches a changed
    function/closure body; deeper changes (a helper the body calls) are
    covered by the fingerprint + the documented wipe rule."""
    target = getattr(fn, '__wrapped__', fn)
    try:
        import inspect
        blob = inspect.getsource(target)
    except Exception:  # paddle-lint: disable=swallowed-exception -- source unavailable (REPL/frozen); bytecode/qualname fallbacks below
        code = getattr(target, '__code__', None)
        if code is not None:
            blob = code.co_code.hex() + repr(code.co_consts)
        else:
            blob = getattr(target, '__qualname__',
                           type(target).__name__)
    if _depth < 3:
        func = getattr(target, '__func__', target)
        for cell in (getattr(func, '__closure__', None) or ()):
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if callable(v):
                # body token + scalar-attr token: a loss Layer keys on
                # its class AND its baked hyperparams (label smoothing)
                blob += code_token(v, _depth + 1) + describe_statics(v)
            else:
                blob += describe_statics(v)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def describe_statics(obj, _depth: int = 0) -> str:
    """Stable textual token for compile-time constants baked into a
    program (optimizer hyperparams, model config, engine geometry) —
    values that change the compiled computation WITHOUT changing any
    input aval. Best-effort: unknown objects degrade to their class
    name, never raise."""
    if _depth > 4:
        return '...'
    try:
        if obj is None or isinstance(obj, (bool, int, float, str)):
            return repr(obj)
        if isinstance(obj, (list, tuple)):
            inner = ','.join(describe_statics(v, _depth + 1) for v in obj)
            return f'[{inner}]'
        if isinstance(obj, dict):
            inner = ','.join(
                f'{k!r}:{describe_statics(obj[k], _depth + 1)}'
                for k in sorted(obj, key=repr))
            return f'{{{inner}}}'
        if hasattr(obj, '__dict__'):
            scalars = {k: v for k, v in vars(obj).items()
                       if isinstance(v, (bool, int, float, str, type(None)))
                       and not k.startswith('_')}
            return (f'{type(obj).__qualname__}'
                    f'({describe_statics(scalars, _depth + 1)})')
        return type(obj).__qualname__
    except Exception:  # paddle-lint: disable=swallowed-exception -- statics token must never raise; class name is the degraded token
        return type(obj).__name__


def _leaf_sig(leaf):
    dt = getattr(leaf, 'dtype', None)
    if dt is not None:
        shard = ''
        try:
            s = getattr(leaf, 'sharding', None)
            if s is not None and type(s).__name__ not in (
                    'SingleDeviceSharding',):
                shard = str(s)
        except Exception:  # paddle-lint: disable=swallowed-exception -- sharding probe; empty token means single-device layout
            pass
        return (tuple(getattr(leaf, 'shape', ())), str(dt),
                bool(getattr(leaf, 'weak_type', False)), shard)
    if isinstance(leaf, (bool, int, float, str, type(None))):
        return ('py', repr(leaf))
    return ('py', type(leaf).__name__)


def _mesh_token() -> str:
    """Active fleet mesh topology (axis names/sizes), part of the key so
    re-meshed programs never collide with their pre-resize ancestors."""
    from ..distributed import env
    if not env.has_mesh():
        return ''
    mesh = env.get_mesh(auto_init=False)
    return repr(tuple(zip(mesh.axis_names, mesh.devices.shape)))


def store_key(name: str, fn_token: str, statics_token: str, args,
              formats=()) -> str:
    """The persistent cache key: sha256 over (name, fn identity, input
    treedef, tensor avals, static leaves, sharding, mesh) — the dispatch
    cache's key shape, made process-independent — and, where the
    program takes a pool in formats of its own (`pool_formats`), those:
    a program compiled for another layout is another program. The
    backend fingerprint is deliberately NOT part of the key: a skewed
    entry must be FOUND and rejected (with an event) rather than
    silently missed."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = tuple(_leaf_sig(leaf) for leaf in leaves)
    blob = repr((_MANIFEST_VERSION, name, fn_token, statics_token,
                 str(treedef), sig, _mesh_token())
                + ((_io_manifest(formats),) if formats else ()))
    return hashlib.sha256(blob.encode('utf-8')).hexdigest()[:32]


# ---------------------------------------------------------------------------
# a pool held in formats of its own
# ---------------------------------------------------------------------------

class PoolIO(NamedTuple):
    """The one property a program declares beside its donation: which
    argument is a slot pool, where that pool comes back among the
    results, and the formats the pool's leaves are held in on the
    device (`serving/kv_pool.py`: a leaf the device's default layout
    would have the decode block relay at its edges lives in the layout
    the block reads it in). The program is compiled to take and return
    those leaves as they lie."""
    arg: int
    # path into the result: () where the result IS the pool, (1,) where
    # it is the second of a tuple, None where it does not come back
    result: Optional[Tuple[int, ...]]
    # -> one entry per leaf of that argument, in tree order: a
    # `jax.experimental.layout.Format` (its layout concrete, or AUTO:
    # the compiler's choice, read off the `Compiled` afterwards), or
    # None for the device's default
    formats: Callable[[], Sequence]


def pool_formats(pool_io: Sequence[PoolIO]) -> tuple:
    """What `pool_io` asks for NOW, as `((arg, result, formats), ...)`
    over the pools that hold a leaf in a format of its own — () where
    none does, and the program is then compiled, keyed and persisted as
    one that never declared a pool."""
    asked = ((io.arg, io.result, tuple(io.formats())) for io in pool_io)
    return tuple(a for a in asked if any(f is not None for f in a[2]))


def _layout_manifest(fmt):
    """A leaf's format as the key and the manifest carry it: None (the
    default), 'auto', or the layout's own numbers."""
    from jax.experimental.layout import Layout
    if fmt is None:
        return None
    if not isinstance(fmt.layout, Layout):
        return 'auto'
    lay = fmt.layout
    return {'major_to_minor': list(lay.major_to_minor),
            'tiling': [list(t) for t in lay.tiling or ()]}


def _io_manifest(formats: tuple) -> list:
    return [{'arg': arg,
             'result': None if result is None else list(result),
             'formats': [_layout_manifest(f) for f in fmts]}
            for arg, result, fmts in formats]


def _io_from_manifest(entries) -> tuple:
    """`_io_manifest`'s inverse, on this process's first device."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    here = SingleDeviceSharding(jax.devices()[0])

    def fmt(m):
        if m is None:
            return None
        if m == 'auto':
            return Format(Layout.AUTO, here)
        return Format(Layout(tuple(m['major_to_minor']),
                             tuple(tuple(t) for t in m['tiling'])), here)

    return tuple(
        (int(e['arg']),
         None if e['result'] is None else tuple(e['result']),
         tuple(fmt(m) for m in e['formats']))
        for e in entries or ())


def _io_shardings(fn, args, formats: tuple):
    """-> (`args`, `in_shardings`, `out_shardings`) for `jax.jit(fn)`:
    the pools' formats where `formats` puts them, None (the device's
    default, nothing asked) everywhere else. A format goes to the
    device its argument lies on — a described one, where the arguments
    are shapes given a sharding — and a pool argument goes as its
    shapes: the program is compiled for the formats whatever layout the
    arrays at hand lie in. The results' structure is read from a trace,
    which the compile that follows finds cached."""
    from jax.experimental.layout import Format
    tree = jax.tree_util
    args, ins = list(args), [None] * len(args)
    for arg, _, fmts in formats:
        leaves, treedef = tree.tree_flatten(args[arg])
        if len(leaves) != len(fmts):
            raise ValueError(
                f'argument {arg} has {len(leaves)} leaves and '
                f'{len(fmts)} formats')
        there = [getattr(leaf, 'sharding', None) for leaf in leaves]
        args[arg] = treedef.unflatten(
            [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)
             for leaf, sh in zip(leaves, there)])
        ins[arg] = treedef.unflatten(
            [None if f is None else Format(f.layout, sh or f.sharding)
             for f, sh in zip(fmts, there)])
    outs = tree.tree_map(lambda _: None,
                         jax.jit(fn).trace(*args).out_info)
    for arg, result, _ in formats:
        if result is not None:
            outs = _set_at(outs, result, ins[arg])
    return args, tuple(ins), outs


def _set_at(tree, path, value):
    if not path:
        return value
    items = list(tree)
    items[path[0]] = _set_at(items[path[0]], path[1:], value)
    return type(tree)(items)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def _export_program(jitted, args):
    """Trace `jitted` into a portable `jax.export.Exported` at the
    abstract shapes of `args` (the artifact the persistent tier
    stores). Typed PRNG-key leaves are rejected up front (the export
    flatbuffer cannot encode `key<fry>` avals — framework RNG uses raw
    keys for exactly this reason); callers degrade to the plain
    unpersisted compile."""
    from jax import export as _jex
    for leaf in jax.tree_util.tree_leaves(args):
        dt = getattr(leaf, 'dtype', None)
        if dt is not None and jax.dtypes.issubdtype(
                dt, jax.dtypes.prng_key):
            raise TypeError(
                'typed PRNG-key argument cannot be exported; pass raw '
                'uint32 key data (jax.random.PRNGKey / key_data)')
    plats = {'tpu', 'cpu', jax.default_backend()}
    abstract = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
        if hasattr(v, 'shape') else v, args)
    return _with_stack_room(
        _jex.export(jitted, platforms=tuple(sorted(plats))), *abstract)


def _compile_program(fn, args, donate_argnums=(), formats=()):
    """THE compile site of the store: every route to an executable ends
    here, so a program's declared `donate_argnums` — and the `formats`
    its pools are held in (`pool_formats`) — are applied wherever that
    program is compiled. `fn` is a jitted callable — it carries the
    donation it was declared with — or a plain one (an exported
    program's `call`), jitted here with `donate_argnums`; with
    `formats` what a jitted one wraps is jitted anew, to take and
    return those leaves as they lie. `args` are the arguments or their
    abstract shapes. Returns the `Compiled`."""
    def compile_():
        if formats:
            plain = getattr(fn, '__wrapped__', fn)
            at, ins, outs = _io_shardings(plain, args, formats)
            return _LayoutsOnTrust(jax.jit(
                plain, donate_argnums=tuple(donate_argnums),
                in_shardings=ins, out_shardings=outs).lower(*at).compile())
        jitted = fn if hasattr(fn, 'lower') else jax.jit(
            fn, donate_argnums=tuple(donate_argnums))
        return jitted.lower(*args).compile()
    return _with_stack_room(compile_)


class _LayoutsOnTrust:
    """A program compiled to layouts of its own, called without jax's
    check of the layouts its arguments SAY they lie in.

    Why (jax 0.9.0, XLA:CPU and the TPU alike; PERF.md 7m): an
    executable that jax's persistent compile cache hands back writes its
    results in the layouts it was compiled to, but the arrays it returns
    report the device's DEFAULT layout. jax compares what an argument
    reports with what the program takes — so in a process that loads its
    programs, the pool a decode block has just returned would be refused
    by the next one. Here the program's expectations of its arguments'
    layouts are taken out of jax's sight (three fields of the loaded
    executable, before its first call builds on them); the runtime still
    refuses a buffer that does not lie as the program reads it. What the
    program takes and returns is read once, from the compiler
    (`input_formats`, `output_formats`); the rest is the `Compiled`'s."""

    def __init__(self, compiled):
        self.input_formats = compiled.input_formats
        self.output_formats = compiled.output_formats
        exe = compiled._executable
        own = [lay is not None for lay in exe._dispatch_in_layouts]
        exe._xla_in_layouts = [None if o else lay for o, lay in
                               zip(own, exe._xla_in_layouts)]
        exe._dispatch_in_layouts = [None] * len(own)
        exe._unloaded_executable.dispatch_in_layouts = [None] * len(own)
        self._compiled = compiled

    def __call__(self, *args):
        return self._compiled(*args)

    def __getattr__(self, name):
        return getattr(self._compiled, name)


def _roomy_frame(slots=17000):
    """-> `with_stack_room(fn, *args)`: calls `fn(*args)` from a frame
    of `slots` local variables (136 KB), and returns or raises what it
    does. The store traces, lowers and compiles below it.

    CPython keeps a thread's frames in chunks of 16 KiB, maps a new chunk
    when a call does not fit in the last one, and unmaps it again when
    that call returns. Tracing and lowering a large program is a few
    hundred thousand Python calls, recursing a hundred frames below the
    caller; where the caller's depth puts a chunk's end inside the inner
    calls, every one of them costs two system calls. Measured on the
    v5e's host (PR 29, PERF.md section 6): the decode block lowered in
    0.45 to 0.6 s from a shallow stack, and in 3.5 to 9.7 s, by the
    frame, from inside `InferenceEngine.step`; on this CPU 0.24 against
    0.5 to 0.8 s at some depths. A frame that fits no 16 KiB chunk gets
    one of its own, the next power of two (256 KiB), and its callees run
    in what is left of that: 120 KB, with no boundary to cross — so what
    a program costs to lower no longer depends on who asks for it."""
    names = ' = '.join(f'_{i}' for i in range(slots))
    scope = {}
    exec(f'def with_stack_room(fn, *args):\n    {names} = None\n'
         f'    return fn(*args)\n', scope)
    return scope['with_stack_room']


_with_stack_room = _roomy_frame()


def _compile_exported(exported, donate_argnums=(), formats=()):
    """AOT-compile an exported program from its own recorded in_avals.

    No Python tracing of the original function; the backend compile of
    this module is served by jax's persistent compilation cache on warm
    restarts (same module bytes -> same cache key), so it costs a disk
    read, not an XLA compile."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in exported.in_avals]
    args, _ = jax.tree_util.tree_unflatten(exported.in_tree, specs)
    return _compile_program(exported.call, args, donate_argnums, formats)


def _load_stablehlo(payload: bytes, path: str, donate_argnums=(),
                    formats=()):
    """Deserialize exported StableHLO and AOT-compile it — the warm
    half of the restart path."""
    from jax import export as _jex
    try:
        exported = _jex.deserialize(bytearray(payload))
    except Exception as exc:
        raise ProgramDeserializeError(
            path, f'{type(exc).__name__}: {exc}') from exc
    try:
        return _compile_exported(exported, donate_argnums, formats)
    except Exception as exc:
        raise ProgramDeserializeError(
            path, f'aot compile of deserialized program failed: '
                  f'{type(exc).__name__}: {exc}') from exc


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

class _StoreEntry:
    __slots__ = ('key', 'name', 'kind', 'callable', 'source', 'format',
                 'fingerprint')

    def __init__(self, key, name, kind, call, source, fmt, fingerprint):
        self.key = key
        self.name = name
        self.kind = kind
        self.callable = call
        self.source = source          # 'compile' | 'disk'
        self.format = fmt             # 'stablehlo' | '' (unpersisted)
        self.fingerprint = fingerprint


class ProgramStore:
    """Process-wide owner of AOT-compiled executables, with an optional
    persistent tier. All state-changing paths are exception-safe: disk
    problems degrade to a fresh compile, never propagate."""

    def __init__(self, catalog: Optional[_cost.ProgramCatalog] = None,
                 directory: Optional[str] = None):
        # `is None`, not truthiness: these framework objects are falsy
        # when empty (the PR 10 EventLog rerouting bug class)
        self.catalog = catalog if catalog is not None else _cost.get_catalog()
        self._lock = _concurrency.RLock('ProgramStore._lock')
        self._mem: Dict[str, _StoreEntry] = {}
        self._dir = directory
        self._fingerprint = backend_fingerprint()
        self._hits_memory = 0
        self._hits_disk = 0
        self._misses = 0
        self._rejects = 0
        self._persisted = 0
        self._persist_skips = 0
        self._invalidated = 0
        self._preload: Optional[Dict[str, Any]] = None
        self._coldstart_s: Optional[float] = None

    # -- configuration -------------------------------------------------------
    @property
    def directory(self) -> Optional[str]:
        if self._dir is not None:
            return self._dir or None
        d = str(_flags.flag('FLAGS_program_store_dir') or '')
        return d or None

    @property
    def persistent(self) -> bool:
        return self.directory is not None

    def configure(self, directory: Optional[str]):
        """Point the store at a directory ('' / None disables the
        persistent tier; the in-memory tier is unaffected). Enabling
        also turns on jax's persistent compilation cache — the second
        half of the warm-restart path: our manifests carry the traced
        program, the XLA cache carries its compiled bytes. WHERE that
        cache lives is `ensure_compile_cache`'s decision, never the
        store's: a store directory that moves must not move (and so
        empty) the compile cache."""
        self._dir = directory if directory else ''
        if directory:
            os.makedirs(directory, exist_ok=True)
            ensure_compile_cache()
            # cache every program, however small/fast: the zero-compile
            # warm guard covers incidental converts too
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', 0.0)
            jax.config.update(
                'jax_persistent_cache_min_entry_size_bytes', 0)
        else:
            # no persistent tier, no reason to write every tiny program
            # to the compile cache: jax's own threshold (1 s) again
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', 1.0)
        return self

    def refresh_fingerprint(self):
        """Recompute the backend fingerprint (the elastic layer calls
        this after a re-mesh: device count changed, so entries written
        under the old topology must stop matching) and drop in-memory
        entries that no longer match."""
        with self._lock:
            self._fingerprint = backend_fingerprint()
            stale = [k for k, e in self._mem.items()
                     if e.fingerprint != self._fingerprint]
            for k in stale:
                del self._mem[k]
            self._invalidated += len(stale)
        if stale:
            _obs.emit('program_store_invalidate', entries=len(stale),
                      reason='fingerprint_change')
        return len(stale)

    # -- metrics/events helpers ---------------------------------------------
    def _counter(self, name, help_, **labels):
        if not _obs.enabled():
            return None
        reg = _obs.get_registry()
        if labels:
            return reg.counter(name, help_,
                               tuple(sorted(labels))).labels(**labels)
        return reg.counter(name, help_)

    def _note_hit(self, name: str, tier: str, fmt: str = ''):
        with self._lock:
            if tier == 'memory':
                self._hits_memory += 1
            else:
                self._hits_disk += 1
        c = self._counter('paddle_program_cache_hits_total',
                          'program-store hits by tier', tier=tier)
        if c is not None:
            c.inc()
        _obs.emit('program_cache_hit', program=name, tier=tier,
                  **({'format': fmt} if fmt else {}))

    def _note_miss(self, name: str):
        with self._lock:
            self._misses += 1
        c = self._counter('paddle_program_cache_misses_total',
                          'program-store misses (fresh compiles)')
        if c is not None:
            c.inc()
        _obs.emit('program_cache_miss', program=name)

    def _note_reject(self, name: str, path: str, reason: str,
                     detail: str = ''):
        with self._lock:
            self._rejects += 1
        c = self._counter('paddle_program_cache_rejects_total',
                          'persisted entries rejected at load',
                          reason=reason)
        if c is not None:
            c.inc()
        _obs.emit('program_cache_reject', program=name, path=path,
                  reason=reason, **({'detail': detail} if detail else {}))

    # -- disk tier -----------------------------------------------------------
    def _paths(self, key: str):
        d = self.directory
        return (os.path.join(d, f'{key}.bin'),
                os.path.join(d, f'{key}.json'))

    def _save_disk(self, key: str, name: str, kind: str, payload: bytes,
                   donate_argnums=(), formats=()) -> Optional[str]:
        """Persist one exported program: payload first, manifest second,
        both through atomic renames (a crash between the two leaves a
        manifest-less payload, which the load path treats as absent; a
        racing writer's os.replace wins wholesale — either way every
        committed entry is internally consistent)."""
        d = self.directory
        if d is None:
            return None
        try:
            os.makedirs(d, exist_ok=True)
            fmt = 'stablehlo'
            bin_path, man_path = self._paths(key)
            nonce = f'.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp'
            tmp_bin = bin_path + nonce
            with open(tmp_bin, 'wb') as f:
                f.write(payload)
            os.replace(tmp_bin, bin_path)
            manifest = {
                'version': _MANIFEST_VERSION,
                'key': key,
                'name': name,
                'kind': kind,
                'format': fmt,
                'sha256': hashlib.sha256(payload).hexdigest(),
                'size': len(payload),
                'donate_argnums': list(donate_argnums),
                **({'pool_formats': _io_manifest(formats)}
                   if formats else {}),
                'fingerprint': self._fingerprint,
                'created': time.time(),
            }
            tmp_man = man_path + nonce
            with open(tmp_man, 'w') as f:
                json.dump(manifest, f, indent=1)
            os.replace(tmp_man, man_path)
            with self._lock:
                self._persisted += 1
            _obs.emit('program_store_persist', program=name, format=fmt,
                      bytes=len(payload))
            return fmt
        except Exception as exc:
            # persistence is an optimization: failing to write must
            # never fail the call that just compiled successfully
            with self._lock:
                self._persist_skips += 1
            _obs.emit('program_store_persist_skipped', program=name,
                      error=type(exc).__name__)
            return None

    def _load_disk(self, key: str, formats=None):
        """Integrity-verified load of one persisted entry. Returns a
        `_StoreEntry` or None; NEVER raises. Every rejection emits
        `program_cache_reject` with its reason. An entry is compiled to
        the pool formats its manifest records; `formats` is what the
        caller's pools are held in (None: nobody asks, `preload`), and
        an entry recorded for others is rejected."""
        d = self.directory
        if d is None:
            return None
        bin_path, man_path = self._paths(key)
        if not os.path.exists(man_path):
            return None   # absent (or uncommitted half-write): plain miss
        try:
            with open(man_path) as f:
                manifest = json.load(f)
        except Exception as exc:
            self._note_reject(key, man_path, 'manifest_unreadable',
                              type(exc).__name__)
            return None
        name = str(manifest.get('name', key))
        if manifest.get('version') != _MANIFEST_VERSION:
            self._note_reject(name, man_path, 'manifest_version')
            return None
        if manifest.get('fingerprint') != self._fingerprint:
            self._note_reject(name, man_path, 'fingerprint')
            return None
        try:
            with open(bin_path, 'rb') as f:
                payload = f.read()
        except OSError:
            self._note_reject(name, bin_path, 'payload_missing')
            return None
        if hashlib.sha256(payload).hexdigest() != manifest.get('sha256'):
            self._note_reject(name, bin_path, 'checksum')
            return None
        fmt = manifest.get('format', '')
        donate = tuple(manifest.get('donate_argnums') or ())
        recorded = manifest.get('pool_formats') or []
        if formats is not None and recorded != _io_manifest(formats):
            self._note_reject(name, man_path, 'pool_formats')
            return None
        try:
            if fmt == 'stablehlo':
                call = _load_stablehlo(payload, bin_path, donate,
                                       _io_from_manifest(recorded))
            else:
                self._note_reject(name, bin_path, 'format', fmt)
                return None
        except ProgramDeserializeError as exc:
            self._note_reject(name, bin_path, 'deserialize', exc.reason)
            return None
        except Exception as exc:   # belt and braces: load NEVER raises
            self._note_reject(name, bin_path, 'deserialize',
                              type(exc).__name__)
            return None
        return _StoreEntry(key, name, str(manifest.get('kind', 'jit')),
                           call, 'disk', fmt, self._fingerprint)

    # -- the acquisition path ------------------------------------------------
    def acquire(self, key: str, name: str, kind: str,
                record: _cost.ProgramRecord, jitted, args,
                persist: bool = True, donate_argnums=(), formats=(),
                build: Optional[_telemetry.ProgramBuild] = None):
        """Resolve one program key to an executable: memory tier, then
        the integrity-verified disk tier, then a fresh AOT compile of
        `jitted` at `args`.

        With a persistent store, the fresh compile goes THROUGH the
        export artifact (trace -> serialize -> compile the exported
        module) so the cold process compiles the exact module a warm
        process will deserialize — the XLA persistent cache then serves
        the warm compile from disk. Export failures fall back to the
        plain direct compile (memory tier only, note='aot_noexport').
        Every route compiles in `_compile_program`, with
        `donate_argnums` and the pools' `formats` applied (a program
        that cannot be exported and compiled to them goes the
        'aot_noexport' way too). Returns the resolved `_StoreEntry`,
        or None when no AOT path works at all — callers fall back to
        their plain jitted call. `build`, the caller's open
        `ProgramBuild`, is told which of the three it was; the build is
        the one timer (`ProgramRecord.compile_seconds` and the phases
        beside it)."""
        with self._lock:
            ent = self._mem.get(key)
        if ent is not None:
            if build is not None:
                build.source = 'memory'
            self._note_hit(name, 'memory', ent.format)
            if ent.source == 'disk':
                record.note = record.note or f'loaded:{ent.format}'
            return ent
        ent = self._load_disk(key, formats)
        if ent is not None:
            if build is not None:
                build.source = 'disk'
            _cost._read_analysis(ent.callable, record)
            record.note = f'loaded:{ent.format}'
            with self._lock:
                self._mem[key] = ent
            self._note_hit(name, 'disk', ent.format)
            return ent
        # cold: compile fresh
        persisting = persist and self.persistent
        compiled = payload = None
        fmt = ''
        if persisting:
            try:
                exported = _export_program(jitted, args)
                payload = exported.serialize()
                compiled = _compile_exported(exported, donate_argnums,
                                             formats)
                fmt = 'stablehlo'
            except Exception as exc:
                _obs.emit('program_store_persist_skipped', program=name,
                          error=type(exc).__name__)
        if compiled is None:
            try:
                compiled = _compile_program(jitted, args, donate_argnums,
                                            formats)
            except Exception:  # paddle-lint: disable=swallowed-exception -- no AOT path for this callable; caller serves the plain jitted call which surfaces any real error
                return None   # no AOT path; caller serves the plain call
            if persisting:
                record.note = 'aot_noexport'
        with self.catalog._lock:
            record.compile_count += 1
        _cost._read_analysis(compiled, record)
        self._note_miss(name)
        ent = _StoreEntry(key, name, kind, compiled, 'compile', fmt,
                          self._fingerprint)
        with self._lock:
            self._mem[key] = ent
        if payload is not None:
            self._save_disk(key, name, kind, payload,
                            donate_argnums=donate_argnums, formats=formats)
        return ent

    # -- warm restart --------------------------------------------------------
    def preload(self, match: Optional[str] = None) -> Dict[str, Any]:
        """Bulk-load every committed, fingerprint-matching entry into
        the in-memory tier (the warm-restart path: a resumed trainer or
        a cold replica materializes its executables BEFORE serving).
        Holds the ref-counted `warming` degraded state on /healthz for
        the duration. Idempotent: already-resident keys are skipped.
        `match` restricts to names containing the substring."""
        d = self.directory
        stats = {'loaded': 0, 'skipped': 0, 'rejected': 0, 'seconds': 0.0}
        if d is None or not os.path.isdir(d):
            return stats
        t0 = time.perf_counter()
        rejects_before = self._rejects
        _obs.note_degraded('warming', {'dir': d})
        try:
            for fname in sorted(os.listdir(d)):
                if not fname.endswith('.json') or '.tmp' in fname:
                    continue
                key = fname[:-len('.json')]
                with self._lock:
                    if key in self._mem:
                        stats['skipped'] += 1
                        continue
                if match is not None:
                    try:
                        with open(os.path.join(d, fname)) as f:
                            if match not in str(json.load(f).get('name')):
                                stats['skipped'] += 1
                                continue
                    except Exception:  # paddle-lint: disable=swallowed-exception -- unreadable manifest: _load_disk rejects it with a counted program_cache_reject
                        pass   # unreadable manifest: let _load_disk reject
                ent = self._load_disk(key)
                if ent is None:
                    continue
                record = self.catalog.record(ent.name, kind=ent.kind)
                _cost._read_analysis(ent.callable, record)
                record.note = f'loaded:{ent.format}'
                with self._lock:
                    self._mem[key] = ent
                self._note_hit(ent.name, 'disk', ent.format)
                stats['loaded'] += 1
        finally:
            _obs.clear_degraded('warming')
        stats['seconds'] = round(time.perf_counter() - t0, 4)
        stats['rejected'] = self._rejects - rejects_before
        try:
            from ..observability import server as _srv
            self._coldstart_s = round(
                time.monotonic() - _srv._START, 4)
        except Exception:  # paddle-lint: disable=swallowed-exception -- server module optional; coldstart gauge just stays unset
            self._coldstart_s = None
        with self._lock:
            self._preload = dict(stats)
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.gauge('paddle_program_preload_seconds',
                      'wall seconds of the last program-store preload'
                      ).set(stats['seconds'])
            reg.gauge('paddle_program_preload_loaded',
                      'programs loaded by the last preload'
                      ).set(stats['loaded'])
            if self._coldstart_s is not None:
                reg.gauge('paddle_coldstart_seconds',
                          'process start -> program store warm'
                          ).set(self._coldstart_s)
        _obs.emit('program_store_preload', **stats)
        return stats

    # -- wrapping ------------------------------------------------------------
    def wrap_jit(self, fn, name: Optional[str] = None,
                 name_fn: Optional[Callable] = None, kind: str = 'jit',
                 statics: Any = None, persist: bool = True,
                 donate_argnums=(),
                 pool_io: Sequence[PoolIO] = ()) -> 'StoredJit':
        """Enroll a jax.jit'd callable: AOT compile through the store
        (memory -> disk -> compile), cost attribution folded into the
        catalog. `statics` names the compile-time constants baked into
        the program that its input avals cannot see (optimizer
        hyperparams, model config, engine geometry) — part of the
        persistent key. `donate_argnums` is the program's declared
        donation: applied wherever the program is compiled, and
        recorded in the manifest for the warm process. `pool_io`
        declares which arguments and results are slot pools (`PoolIO`):
        the program takes and returns their leaves in the formats the
        pools hold them in, on every route likewise."""
        return StoredJit(self, fn, name=name, name_fn=name_fn, kind=kind,
                         statics=statics, persist=persist,
                         donate_argnums=donate_argnums, pool_io=pool_io)

    # -- bookkeeping / reporting --------------------------------------------
    def program_names(self) -> List[str]:
        with self._lock:
            return sorted({e.name for e in self._mem.values()})

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{'key': e.key, 'name': e.name, 'kind': e.kind,
                     'source': e.source, 'format': e.format}
                    for e in self._mem.values()]

    def disk_entries(self) -> int:
        d = self.directory
        if d is None or not os.path.isdir(d):
            return 0
        try:
            return sum(1 for f in os.listdir(d)
                       if f.endswith('.json') and '.tmp' not in f)
        except OSError:
            return 0

    def wipe(self) -> int:
        """Safely clear the persistent tier (the documented answer to a
        suspect cache): removes committed entries AND stray tmp files;
        in-memory executables stay valid."""
        d = self.directory
        if d is None or not os.path.isdir(d):
            return 0
        n = 0
        for fname in os.listdir(d):
            if fname.endswith(('.bin', '.json')) or '.tmp' in fname:
                try:
                    os.unlink(os.path.join(d, fname))
                    n += 1
                except OSError:
                    pass
        _obs.emit('program_store_wipe', files=n, dir=d)
        return n

    def clear_memory(self):
        with self._lock:
            self._mem.clear()

    @property
    def built(self) -> int:
        """Programs compiled or loaded from the disk tier so far (not
        those found in memory): a count a caller reads before and after
        a region to learn whether a program was built inside it."""
        return self._misses + self._hits_disk

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                'persistent': self.persistent,
                'dir': self.directory,
                'memory_entries': len(self._mem),
                'programs': len({e.name for e in self._mem.values()}),
                'loaded_from_disk': sum(1 for e in self._mem.values()
                                        if e.source == 'disk'),
                'hits_memory': self._hits_memory,
                'hits_disk': self._hits_disk,
                'misses': self._misses,
                'rejects': self._rejects,
                'persisted': self._persisted,
                'persist_skips': self._persist_skips,
                'invalidated': self._invalidated,
                'preload': dict(self._preload) if self._preload else None,
                'coldstart_seconds': self._coldstart_s,
            }
        out['disk_entries'] = self.disk_entries()
        return out

    def verify_catalog_consistency(self) -> Dict[str, Any]:
        """The double-attribution guard: every store-owned program is
        tracked by exactly one catalog record, and no jitted-tier
        catalog record exists outside the store. (Dispatch-tier records
        mirror the eager cache and are excluded — the eager tier keeps
        its own in-process cache and reports through the same catalog.)
        Returns the comparison; tier-1 asserts the sets match."""
        store_names = set(self.program_names())
        catalog_names = {r.name for r in self.catalog.records()
                         if r.kind != 'dispatch'
                         and (r.compile_count > 0
                              or r.note.startswith('loaded:'))}
        return {
            'store': sorted(store_names),
            'catalog': sorted(catalog_names),
            'only_in_store': sorted(store_names - catalog_names),
            'only_in_catalog': sorted(catalog_names - store_names),
            'consistent': store_names == catalog_names,
        }

    def reset_stats(self):
        with self._lock:
            self._hits_memory = self._hits_disk = 0
            self._misses = self._rejects = 0
            self._persisted = self._persist_skips = 0
            self._invalidated = 0
            self._preload = None


class _FirstCall:
    """What `StoredJit._build` stores as the callable of a program it
    has just built: the program, for ONE call. That call is timed until
    it returns (the executable's first execution: its load, its first
    transfer), booked as the build's `first_call` phase with the
    build's one `program_built` event, and the entry is put back as the
    program itself — so a call of a signature already built finds the
    executable and pays nothing for this."""

    __slots__ = ('program', '_entries', '_key', '_build')

    def __init__(self, program, entries, key, build):
        self.program = program
        self._entries, self._key, self._build = entries, key, build

    def __call__(self, *args):
        t0 = time.perf_counter()
        try:
            return self.program(*args)
        finally:
            secs = time.perf_counter() - t0
            self._entries[self._key] = (self._build.record, self.program)
            self._build.first_call(secs)


_ON_HOST: Dict[type, bool] = {}   # leaf type -> not a device array
_DTYPE_NAME: Dict[Any, str] = {}  # a leaf's dtype -> its name in a key


class StoredJit:
    """A jax.jit'd callable enrolled in the program store.

    First call per input signature resolves through the store: an
    executable already resident (compiled by another wrapper with the
    same key — e.g. a sibling serving replica) or persisted on disk is
    reused; otherwise the one AOT compile the plain call would have
    cost runs in the store, and its analysis lands in the program
    record. Any AOT failure falls back to the plain jitted call for
    that signature ('aot_unavailable')."""

    def __init__(self, store: ProgramStore, fn, name: Optional[str] = None,
                 name_fn: Optional[Callable] = None, kind: str = 'jit',
                 statics: Any = None, persist: bool = True,
                 donate_argnums=(), pool_io: Sequence[PoolIO] = ()):
        if name is None and name_fn is None:
            raise ValueError('StoredJit needs name= or name_fn=')
        self._store = store
        self._name = name
        self._name_fn = name_fn
        self._kind = kind
        self._span_resolve = f'{kind}.program_resolve'
        self._span_call = f'{kind}.program_call'
        self._persist = persist
        self._donate = tuple(donate_argnums)
        self._pool_io = tuple(pool_io)
        # callers pass the RAW function plus its donate_argnums and the
        # wrapper jits it here. Already-jitted callables are still
        # accepted (their direct-route donation is whatever they baked
        # in), and OPAQUE callables (class instances without .lower)
        # are deliberately NOT auto-jitted — they keep the plain-call
        # 'aot_unavailable' fallback, since tracing an arbitrary
        # callable can change its semantics.
        import types
        if isinstance(fn, (types.FunctionType, types.MethodType)) \
                and not hasattr(fn, 'lower'):
            self._fn = jax.jit(fn, donate_argnums=self._donate)
        else:
            self._fn = fn
        self._fn_token = code_token(fn)
        self._statics_token = describe_statics(statics)
        self._entries: Dict[Any, Any] = {}   # sig -> (record, callable)

    def _signature(self, args):
        """-> (key, leaves flattened, those of them that are not device
        arrays: each is a transfer the call makes)."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig, host = [], 0
        for leaf in leaves:
            kind = type(leaf)
            on_host = _ON_HOST.get(kind)
            if on_host is None:
                # once a type: `isinstance(x, jax.Array)` runs a Python
                # `__instancecheck__`, 0.2 us a leaf on every call
                on_host = _ON_HOST[kind] = not isinstance(leaf, jax.Array)
            host += on_host
            dt = getattr(leaf, 'dtype', None)
            if dt is not None:
                name = _DTYPE_NAME.get(dt)
                if name is None:
                    # once a dtype: `str(np.dtype)` builds the name anew
                    # on every call, and was nine tenths of a leaf's walk
                    name = _DTYPE_NAME[dt] = str(dt)
                sig.append((tuple(getattr(leaf, 'shape', ())), name,
                            bool(getattr(leaf, 'weak_type', False))))
            else:
                sig.append(('py', type(leaf)))
        key = (treedef, tuple(sig))
        if self._pool_io:
            # a pool that has taken formats of its own since is another
            # program's argument: the key says which this one was built for
            key += tuple(tuple(io.formats()) for io in self._pool_io)
        hash(key)
        return key, len(leaves), host

    def _build(self, key, args):
        if self._name is not None:
            name = self._name
        else:
            try:
                name = self._name_fn(args)
            except Exception:  # paddle-lint: disable=swallowed-exception -- naming must never fail a call; kind:unnamed IS the visible trace
                name = f'{self._kind}:unnamed'   # naming must never fail
        record = self._store.catalog.record(name, kind=self._kind)
        if key is None:
            return record, self._fn
        # the one place a program comes into being: booked by phase
        with _telemetry.ProgramBuild(record) as build:
            ent = None
            if hasattr(self._fn, 'lower'):   # an opaque one has no AOT path
                formats = pool_formats(self._pool_io)
                try:
                    skey = store_key(name, self._fn_token,
                                     self._statics_token, args, formats)
                except Exception:
                    # unkeyable statics: this program is served by the
                    # plain jitted call — make "silently" false
                    _obs.count_suppressed('program_store.key')
                else:
                    ent = self._store.acquire(
                        skey, name, self._kind, record, self._fn, args,
                        persist=self._persist,
                        donate_argnums=self._donate, formats=formats,
                        build=build)
            if ent is not None:
                call = ent.callable
            else:
                call = self._fn
                record.note = 'aot_unavailable'
        if build.live:
            call = _FirstCall(call, self._entries, key, build)
        self._entries[key] = (record, call)
        return record, call

    def _resolve(self, args):
        """-> (record, callable, leaves, host leaves): the program for
        these arguments, built through the store if this wrapper has
        not met their signature, and the two counts of `_signature`."""
        try:
            key, leaves, host = self._signature(args)
        except Exception:
            # an unkeyable signature re-resolves the program EVERY call
            # — survivable, but it must be visible when it happens per
            # step instead of once
            _obs.count_suppressed('program_store.signature')
            key, leaves, host = None, 0, 0
        entry = self._entries.get(key) if key is not None else None
        if entry is None:
            entry = self._build(key, args)
        return (*entry, leaves, host)

    def resolve(self, *args):
        """(record, callable) of the program for these arguments,
        built through the store (memory -> disk -> compile) if this
        wrapper has not met their signature — and not called: a caller
        with several programs over one set of arguments has them all
        compiled by the time it first runs one."""
        record, call = self._resolve(args)[:2]
        if isinstance(call, _FirstCall):    # built, and not yet run
            call = call.program
        return record, call

    def __call__(self, *args):
        """The host's part of a call by cause, as two spans named by the
        wrapper's kind (`serving.program_resolve`: the signature and
        the lookup, with the leaves flattened and those that are host
        arrays; `serving.program_call`: the executable's call until it
        returns). `host_seconds` covers both, on two clock reads of its
        own: it is booked with observability off too."""
        t0 = time.perf_counter()
        with _obs.span(self._span_resolve) as sp:
            record, call, leaves, host = self._resolve(args)
            sp.set(leaves=leaves, host_leaves=host)
        with _obs.span(self._span_call):
            out = call(*args)
        dt = time.perf_counter() - t0
        with self._store.catalog._lock:
            record.invocations += 1
            record.host_seconds += dt
        return out

    # the wrapped object still answers AOT introspection (TrainStep's
    # memory_analysis does `self._jitted.lower(...)`); the lowering
    # cache makes that free after the wrapper's own compile
    def __getattr__(self, name):
        return getattr(self._fn, name)


def compile_cache_dir() -> str:
    """Where jax's persistent compilation cache lives for this process:
    `JAX_COMPILATION_CACHE_DIR` when the environment sets it (the
    operator, a test harness or an orchestrating parent placed the
    cache from outside), otherwise `<checkout>/.jax_cache` — a FIXED,
    git-ignored path: a cache directory that moves from run to run
    (a mkdtemp store, say) never hits."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, '.jax_cache')


def ensure_compile_cache() -> str:
    """THE one place that enables jax's persistent compilation cache;
    every process that starts on the chip (chip_smoke children,
    serving/replica_main, `bench.py --phase`) and `ProgramStore
    .configure` go through it. When `JAX_COMPILATION_CACHE_DIR` is set
    jax already read it at import and this function changes nothing —
    no code sets another directory. Returns the directory in use."""
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update('jax_compilation_cache_dir', path)
        # jax memoizes "is the cache used" at the FIRST compile of the
        # process — enabling it after any compile would silently never
        # cache. Reset so the next compile re-reads the directory.
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    return path


_store: Optional[ProgramStore] = None
_store_lock = _concurrency.Lock('store._store_lock')


def get_store() -> ProgramStore:
    global _store
    with _store_lock:
        if _store is None:
            _store = ProgramStore()
            d = _store.directory
            if d:   # flag/env-configured: engage the full persistent
                _store.configure(d)   # tier incl. the compile cache
        return _store


def configure(directory: Optional[str]) -> ProgramStore:
    """Point the process-wide store at `directory` (None/'' = memory
    only). The env/flag `FLAGS_program_store_dir` is the declarative
    form; this is the programmatic one (examples' --program-store)."""
    return get_store().configure(directory)
