"""paddle.jit — to_static + donated jitted TrainStep.

Upstream: python/paddle/jit/ (ProgramTranslator → static graph). The
TPU-native design needs no custom IR: a Layer is *functionalized* — its
parameter/buffer pytree is pulled out (`functional_state`), the forward is
re-run with traced values bound in (`functional_call`) under
`autograd.functional_scope()` (tape off, ops stay pure jax), and the whole
training step is one `jax.jit` with params/opt-state/buffers donated, so
XLA updates weights in place in HBM. RNG inside the trace comes from
`Generator.trace_scope` keyed by the step counter — dropout is
deterministic per step and replays identically on recompilation.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import export as _jax_export
import numpy as np

from .. import autograd, framework
from .. import observability as _obs
from .. import programs as _programs
from ..programs import ProgramDeserializeError
from ..nn.layer import Layer
from ..tensor import Tensor

_tree = jax.tree_util


class InputSpec:
    """Shape/dtype spec (upstream: paddle.static.InputSpec); None dims are
    dynamic-batch buckets — each concrete size triggers one compilation."""

    def __init__(self, shape, dtype='float32', name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f'InputSpec(shape={self.shape}, dtype={self.dtype})'


def functional_state(layer: Layer):
    """Pull (params, buffers) as flat {name: raw jax array} dicts."""
    params = {n: p.value for n, p in layer.named_parameters()
              if not p.stop_gradient}
    frozen = {n: p.value for n, p in layer.named_parameters()
              if p.stop_gradient}
    buffers = {n: b.value for n, b in layer.named_buffers()}
    return params, frozen, buffers


def _bind(layer: Layer, params, frozen, buffers):
    """Swap traced values into the live tensors; returns restore info."""
    saved = []
    pmap = dict(layer.named_parameters())
    bmap = dict(layer.named_buffers())
    for name, val in {**params, **frozen}.items():
        t = pmap[name]
        saved.append((t, t._data, t._node))
        t._data = val
        t._node = None
    for name, val in buffers.items():
        t = bmap[name]
        saved.append((t, t._data, t._node))
        t._data = val
        t._node = None
    return saved, bmap


def _unbind(saved):
    for t, data, node in saved:
        t._data = data
        t._node = node


def functional_call(layer: Layer, params, frozen, buffers, args, kwargs,
                    rng_key=None):
    """Run layer's forward with the given state bound in, purely.

    Returns (output pytree of raw values, new buffer dict) — buffer
    mutations (BN running stats) are captured as outputs.
    """
    return functional_method(layer, '__call__', params, frozen, buffers,
                             args, kwargs, rng_key=rng_key)


def functional_method(layer: Layer, method: str, params, frozen, buffers,
                      args, kwargs, rng_key=None):
    """Like functional_call but invokes an arbitrary method of the layer
    (e.g. an encoder-decoder model's `encode` during generation)."""
    saved, bmap = _bind(layer, params, frozen, buffers)
    try:
        ctx = framework.default_generator.trace_scope(rng_key) \
            if rng_key is not None else _null_ctx()
        with ctx, autograd.functional_scope():
            wrapped_args = _tree.tree_map(
                lambda v: Tensor(v) if not isinstance(v, Tensor) else v, args)
            out = getattr(layer, method)(*wrapped_args, **kwargs)
        out_vals = _tree.tree_map(
            lambda t: t.value if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))
        new_buffers = {n: bmap[n]._data for n in buffers}
        return out_vals, new_buffers
    finally:
        _unbind(saved)


@contextlib.contextmanager
def _null_ctx():
    yield


class StaticLayer:
    """A Layer (or function) compiled to one XLA program per (input shape,
    static-kwargs) combination (the product of @to_static). Tensor/array
    kwargs are traced; python-value kwargs are compile-time constants
    keyed into the jit cache."""

    def __init__(self, fn_or_layer, input_spec=None):
        self._target = fn_or_layer
        self._input_spec = input_spec
        self._is_layer = isinstance(fn_or_layer, Layer)
        self._jit_cache: Dict[Any, Any] = {}

    def _check_spec(self, args):
        if not self._input_spec:
            return
        for i, (spec, a) in enumerate(zip(self._input_spec, args)):
            shape = tuple(np.shape(a))
            if len(shape) != len(spec.shape) or any(
                    s is not None and s != d
                    for s, d in zip(spec.shape, shape)):
                raise ValueError(
                    f'input {i} shape {shape} does not match InputSpec '
                    f'{spec.shape} (None dims are dynamic)')

    def _get_jitted(self, static_kwargs):
        try:
            key = tuple(sorted(
                (k, type(v).__name__, v) for k, v in static_kwargs.items()))
            hash(key)
        except TypeError:
            raise TypeError(
                f'to_static kwargs must be Tensors/arrays (traced) or '
                f'hashable python values (compile-time constants); got '
                f'{ {k: type(v).__name__ for k, v in static_kwargs.items()} }')
        f = self._jit_cache.get(key)
        if f is not None:
            return f
        if self._is_layer:
            def fn(params, frozen, buffers, rkey, args, tkwargs):
                kw = {k: Tensor(v) for k, v in tkwargs.items()}
                kw.update(static_kwargs)
                return functional_call(self._target, params, frozen,
                                       buffers, args, kw, rng_key=rkey)
        else:
            def fn(rkey, args, tkwargs):
                with framework.default_generator.trace_scope(rkey), \
                        autograd.functional_scope():
                    wrapped = _tree.tree_map(lambda v: Tensor(v), args)
                    kw = {k: Tensor(v) for k, v in tkwargs.items()}
                    kw.update(static_kwargs)
                    out = self._target(*wrapped, **kw)
                return _tree.tree_map(
                    lambda t: t.value if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
        target_name = getattr(self._target, '__name__',
                              type(self._target).__name__)
        f = _programs.get_store().wrap_jit(
            fn, name=f'to_static:{target_name}', kind='to_static',
            statics={'target': target_name,
                     'src': _programs.code_token(self._target),
                     'static_kwargs': repr(key)})
        self._jit_cache[key] = f
        # executable-cache telemetry: compile count/seconds ride the
        # jax.monitoring listeners (observability.telemetry); the
        # python-side cache growth is recorded here
        _obs.note_jit_cache_entry('to_static')
        return f

    def __call__(self, *args, **kwargs):
        self._check_spec(args)
        arg_vals = _tree.tree_map(
            lambda v: v.value if isinstance(v, Tensor) else jnp.asarray(v),
            args, is_leaf=lambda v: isinstance(v, Tensor))
        traced_kw = {k: (v.value if isinstance(v, Tensor)
                         else jnp.asarray(v))
                     for k, v in kwargs.items()
                     if isinstance(v, (Tensor, jax.Array, np.ndarray))}
        static_kw = {k: v for k, v in kwargs.items() if k not in traced_kw}
        jitted = self._get_jitted(static_kw)
        key = framework.next_rng_key()
        if self._is_layer:
            params, frozen, buffers = functional_state(self._target)
            out_vals, new_bufs = jitted(params, frozen, buffers, key,
                                        arg_vals, traced_kw)
            bmap = dict(self._target.named_buffers())
            for n, v in new_bufs.items():
                bmap[n]._data = v
        else:
            out_vals = jitted(key, arg_vals, traced_kw)
        return _tree.tree_map(Tensor, out_vals)

    # passthroughs so a converted Layer still looks like one
    def __getattr__(self, name):
        return getattr(self._target, name)


def to_static(function=None, input_spec=None, full_graph=True, **kwargs):
    """Convert a Layer or function to a compiled static form."""
    def deco(f):
        if isinstance(f, Layer):
            return StaticLayer(f, input_spec)
        wrapper = StaticLayer(f, input_spec)
        functools.update_wrapper(wrapper, f,
                                 assigned=('__name__', '__doc__'),
                                 updated=())
        return wrapper
    return deco(function) if function is not None else deco


class TrainStep:
    """One donated, jitted training step (upstream analogue: the
    to_static-converted train loop body; SURVEY.md §3 'Jitted train step').

    step(params, opt_state, buffers, key, lr, batch) compiles once per batch
    shape; params/opt_state/buffers are donated so XLA aliases them in HBM.
    """

    @_obs.telemetry.constructing('train.step_init')
    def __init__(self, layer: Layer, loss_fn: Callable, optimizer,
                 extra_metrics: Optional[Callable] = None):
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._opt_state = None
        self._step_key_root = framework.default_generator.root_key
        self._n_calls = 0
        self.compile_count = 0

        def loss_and_grads(params, buffers, frozen, key, batch):
            self.compile_count += 1  # python-level: counts traces, not runs
            _obs.note_jit_cache_entry('train_step')  # one entry per trace

            def loss_of(pv):
                inputs, labels = batch
                out, new_bufs = functional_call(
                    self.layer, pv, frozen, buffers,
                    inputs if isinstance(inputs, tuple) else (inputs,), {},
                    rng_key=key)
                with autograd.functional_scope():
                    wrapped_out = _tree.tree_map(Tensor, out)
                    wrapped_lab = _tree.tree_map(
                        lambda v: Tensor(v) if not isinstance(v, Tensor)
                        else v, labels)
                    loss_t = self.loss_fn(wrapped_out, wrapped_lab)
                loss_v = loss_t.value if isinstance(loss_t, Tensor) else loss_t
                return loss_v, new_bufs
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            return loss, grads, new_bufs

        def step_fn(params, opt_state, buffers, frozen, key, lr, batch):
            loss, grads, new_bufs = loss_and_grads(
                params, buffers, frozen, key, batch)
            new_params, new_opt = self.optimizer.apply_gradients(
                grads, params, opt_state, lr)
            return loss, new_params, new_opt, new_bufs

        # the persistent key must see what the avals cannot: the layer
        # and loss bodies and the optimizer's baked-in hyperparameters
        # (two Adams with different betas share every input aval)
        step_statics = {
            'layer': type(layer).__qualname__,
            'layer_src': _programs.code_token(type(layer)),
            'loss_src': _programs.code_token(loss_fn),
            'optimizer': _programs.describe_statics(optimizer),
        }
        self._offload = getattr(optimizer, '_offload', None) == 'host'
        if self._offload:
            # host-offloaded optimizer state: jit ONLY the grad step
            # (params persist in HBM, no donation); the update streams
            # per-leaf through optimizer.offload.OffloadEngine
            from ..optimizer.offload import OffloadEngine

            self._jitted_grads = _programs.get_store().wrap_jit(
                loss_and_grads,
                name='train_step_grads', kind='train',
                statics=step_statics, donate_argnums=(1,))
            self._engine = OffloadEngine(optimizer)
        # enrolled in the program store: the one AOT compile (or warm
        # disk load) serves the traffic AND yields cost/memory analysis
        # for top_programs(). The store owns the jit AND the donation:
        # params/opt-state/buffers are donated wherever the step is
        # compiled (direct, through the export artifact, warm-loaded).
        self._jitted = _programs.get_store().wrap_jit(
            step_fn,
            name='train_step', kind='train', statics=step_statics,
            donate_argnums=(0, 1, 2))

    @staticmethod
    def _as_batch(inputs, labels):
        return (
            _tree.tree_map(lambda v: v.value if isinstance(v, Tensor)
                           else jnp.asarray(v), inputs,
                           is_leaf=lambda v: isinstance(v, Tensor)),
            _tree.tree_map(lambda v: v.value if isinstance(v, Tensor)
                           else jnp.asarray(v), labels,
                           is_leaf=lambda v: isinstance(v, Tensor)))

    def lower(self, inputs, labels):
        """The step lowered at these batch shapes (`jax.stages.Lowered`):
        `.as_text()` is the StableHLO the compiler is given — where
        chip_smoke.py counts the Mosaic custom calls — and `.compile()`
        answers AOT introspection. Traces and lowers; runs nothing."""
        params, frozen, buffers = functional_state(self.layer)
        key = jax.random.fold_in(self._step_key_root, 0)
        if self._offload:
            # offload path: the jitted program is the grad step (slots
            # stream through one leaf at a time and never sit in HBM)
            return self._jitted_grads.lower(
                params, buffers, frozen, key,
                self._as_batch(inputs, labels))
        if self._opt_state is None:
            self._opt_state = self.optimizer.init_state(params)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        return self._jitted.lower(
            params, self._opt_state, buffers, frozen, key, lr,
            self._as_batch(inputs, labels))

    def memory_analysis(self, inputs, labels):
        """XLA's CompiledMemoryStats for the step at these batch shapes
        (peak_memory_in_bytes, temp/argument/output sizes)."""
        return self.lower(inputs, labels).compile().memory_analysis()

    def __call__(self, inputs, labels):
        # the span is the goodput ledger's `step_compute` source (first
        # call: the trace/compile inside is re-attributed to `compile`
        # by the ledger's nested-interval subtraction)
        with _obs.span('train.step'):
            # train.dispatch: gathering the state and the jitted call,
            # until it returns (the device runs on); train.writeback:
            # handing the new arrays to the live Layer
            with _obs.span('train.dispatch'):
                params, frozen, buffers = functional_state(self.layer)
                if self._opt_state is None and not self._offload:
                    self._opt_state = self.optimizer.init_state(params)
                key = jax.random.fold_in(self._step_key_root,
                                         self._n_calls)
                self._n_calls += 1
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                batch = self._as_batch(inputs, labels)
                if self._offload:
                    if self._opt_state is None:
                        self._opt_state = self._engine.init_state(params)
                    loss, grads, new_bufs = self._jitted_grads(
                        params, buffers, frozen, key, batch)
                    new_params, self._opt_state = self._engine.apply(
                        grads, params, self._opt_state, lr)
                else:
                    loss, new_params, self._opt_state, new_bufs = \
                        self._jitted(params, self._opt_state, buffers,
                                     frozen, key, lr, batch)
            with _obs.span('train.writeback'):
                pmap = dict(self.layer.named_parameters())
                for n, v in new_params.items():
                    pmap[n]._data = v
                    pmap[n]._node = None
                bmap = dict(self.layer.named_buffers())
                for n, v in new_bufs.items():
                    bmap[n]._data = v
            return Tensor(loss)


class TranslatedLayer:
    """The product of `jit.load(path)` without the original class
    (upstream: paddle.jit.TranslatedLayer from python/paddle/jit/api.py):
    a deserialized StableHLO program closed over restored state. Callable
    like the original (Static)Layer's inference forward."""

    def __init__(self, exported, params, frozen, buffers, manifest):
        self._exported = exported
        self._params = params
        self._frozen = frozen
        self._buffers = buffers
        self._manifest = manifest

    @property
    def input_spec(self):
        return [InputSpec(s['shape'], s['dtype'])
                for s in self._manifest.get('input_spec', [])]

    def named_parameters(self):
        for n, v in {**self._params, **self._frozen}.items():
            yield n, Tensor(v)

    def eval(self):
        return self

    def __call__(self, *args):
        vals = _tree.tree_map(
            lambda v: v.value if isinstance(v, Tensor) else jnp.asarray(v),
            args, is_leaf=lambda v: isinstance(v, Tensor))
        out = self._exported.call(self._params, self._frozen, self._buffers,
                                  *vals)
        return _tree.tree_map(Tensor, out)


def _export_platforms():
    # make the artifact portable across the surfaces this framework runs
    # on: the real chip and the CPU test mesh
    plats = {'tpu', 'cpu'}
    plats.add(jax.default_backend())
    return tuple(sorted(plats))


def save(layer, path, input_spec=None, **config):
    """Serialize a (Static)Layer as a self-contained inference artifact
    (upstream: paddle.jit.save, python/paddle/jit/api.py — Program +
    persistables). TPU-native form: `jax.export` StableHLO bytes
    (`<path>.pdmodel.stablehlo`) + parameters/buffers npz
    (`<path>.pdiparams.npz`). `jit.load(path)` rebuilds a callable from
    the serialized program alone — the original Python class is NOT
    needed. None dims in input_spec export as symbolic (dynamic) dims."""
    import json
    import os
    target = layer._target if isinstance(layer, StaticLayer) else layer
    if input_spec is None and isinstance(layer, StaticLayer):
        input_spec = layer._input_spec
    if not input_spec:
        raise ValueError('jit.save needs input_spec (shapes/dtypes of the '
                         'forward arguments) to trace the program')
    os.makedirs(os.path.dirname(path) or '.', exist_ok=True)
    arrays = {f'param::{n}': np.asarray(p.value)
              for n, p in target.named_parameters()}
    arrays.update({f'buffer::{n}': np.asarray(b.value)
                   for n, b in target.named_buffers()})
    np.savez(path + '.pdiparams.npz', **arrays)

    # the serialized program is the EVAL forward (a deployment artifact:
    # dropout off, BN in inference mode), matching upstream jit.save
    was_training = target.training
    target.eval()
    try:
        params, frozen, buffers = functional_state(target)

        def infer_fn(params, frozen, buffers, *args):
            out, _ = functional_call(target, params, frozen, buffers,
                                     args, {})
            return out

        arg_specs = []
        scope = None
        n_sym = 0
        for s in input_spec:
            dims = []
            has_sym = False
            for d in s.shape:
                if d is None:
                    dims.append(f'b{n_sym}')
                    n_sym += 1
                    has_sym = True
                else:
                    dims.append(str(d))
            if has_sym:
                # one shared scope so symbols across args can relate
                if scope is None:
                    scope = _jax_export.SymbolicScope()
                shape = _jax_export.symbolic_shape(', '.join(dims),
                                                  scope=scope)
            else:
                shape = tuple(int(d) for d in dims)
            arg_specs.append(jax.ShapeDtypeStruct(shape, s.dtype))
        abstract = lambda tree: _tree.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), tree)
        exported = _jax_export.export(
            jax.jit(infer_fn), platforms=_export_platforms())(
            abstract(params), abstract(frozen), abstract(buffers),
            *arg_specs)
        with open(path + '.pdmodel.stablehlo', 'wb') as f:
            f.write(exported.serialize())
    finally:
        if was_training:
            target.train()
    manifest = {
        'class': type(target).__name__,
        'format': 'stablehlo',
        # the exported program's calling convention splits state into
        # (trainable, frozen, buffers) dicts; load must rebuild the same
        # pytrees, so record the partition
        'trainable': sorted(params),
        'frozen': sorted(frozen),
        'input_spec': [
            {'shape': list(s.shape), 'dtype': str(s.dtype)}
            for s in input_spec],
    }
    with open(path + '.pdmodel.json', 'w') as f:
        json.dump(manifest, f)


def load(path, layer=None):
    """Load a `jit.save` artifact. Without `layer`, deserializes the
    StableHLO program and returns a `TranslatedLayer` — no Python class
    required (upstream paddle.jit.load semantics). With `layer`, restores
    state into it (a state-dict fast path)."""
    import json
    import os
    data = np.load(path + '.pdiparams.npz')
    if layer is not None:
        target = layer._target if isinstance(layer, StaticLayer) else layer
        sd = {}
        for k in data.files:
            kind, name = k.split('::', 1)
            sd[name] = data[k]
        target.set_state_dict(sd)
        return layer if isinstance(layer, StaticLayer) else StaticLayer(layer)
    hlo_path = path + '.pdmodel.stablehlo'
    if not os.path.exists(hlo_path):
        raise ValueError(
            f'{hlo_path} not found: this artifact predates program '
            f'serialization — pass the layer instance to restore into')
    with open(hlo_path, 'rb') as f:
        raw = f.read()
    try:
        exported = _jax_export.deserialize(bytearray(raw))
    except Exception as exc:
        # a truncated/garbage artifact used to raise a raw internal
        # exception; the typed error lets callers fall back (re-export,
        # restore-into-layer) instead of crashing
        _obs.emit('program_cache_reject', path=hlo_path,
                  reason='deserialize', error=type(exc).__name__)
        if _obs.enabled():
            _obs.get_registry().counter(
                'paddle_program_cache_rejects_total',
                'persisted entries rejected at load',
                ('reason',)).labels(reason='deserialize').inc()
        raise ProgramDeserializeError(
            hlo_path, f'{type(exc).__name__}: {exc}') from exc
    params, frozen, buffers = {}, {}, {}
    manifest = {}
    try:
        with open(path + '.pdmodel.json') as f:
            manifest = json.load(f)
    except OSError:
        pass
    frozen_names = set(manifest.get('frozen', []))
    for k in data.files:
        kind, name = k.split('::', 1)
        if kind == 'buffer':
            buffers[name] = jnp.asarray(data[k])
        elif name in frozen_names:
            frozen[name] = jnp.asarray(data[k])
        else:
            params[name] = jnp.asarray(data[k])
    return TranslatedLayer(exported, params, frozen, buffers, manifest)


def not_to_static(fn):
    fn.__jit_skip__ = True
    return fn


def enable_to_static(flag=True):
    pass  # always-on eager→jit conversion path


def ignore_module(modules):
    """Upstream: paddle.jit.ignore_module — marks modules whose calls
    to_static should not transcribe. The tape-based to_static here never
    transcribes python source, so this is a recorded no-op."""
    return None
