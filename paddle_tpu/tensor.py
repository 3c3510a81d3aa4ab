"""Tensor: the user-facing array type, backed by jax.Array.

TPU-native analogue of the reference's DenseTensor + eager Tensor
(upstream: paddle/phi/core/dense_tensor.h, python/paddle/tensor/).
Immutable jax arrays underneath; "in-place" APIs rebind the handle.
Every op flows through `apply_op`, which runs the pure jax function and,
when gradients are required, records a jax.vjp closure on the tape.
"""
from __future__ import annotations

import numbers
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd, framework
from . import _dispatch
from .dtype import convert_dtype, dtype_name

# set by paddle_tpu.amp at import: (raw_vals, op_name) -> raw_vals,
# implementing the auto_cast white/black-list policy at the op choke-point
_amp_cast_hook = None

# set by paddle_tpu.debug.enable_check_numerics: (out_pytree, op_name) -> None
_numerics_hook = None

_tree = jax.tree_util


def _is_tensor(x):
    return isinstance(x, Tensor)


_PRINT_OPTIONS = {'precision': 4, 'threshold': 40, 'edgeitems': 3,
                  'linewidth': 80}


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Repr formatting for Tensor (upstream paddle.set_printoptions;
    sci_mode accepted for signature parity — numpy picks the notation)."""
    for k, v in (('precision', precision), ('threshold', threshold),
                 ('edgeitems', edgeitems), ('linewidth', linewidth)):
        if v is not None:
            _PRINT_OPTIONS[k] = int(v)


class Tensor:
    __slots__ = ('_data', 'stop_gradient', 'grad', '_node', '_leaf_index',
                 'name', 'persistable', '_dist_spec', '_grad_hooks',
                 '__weakref__')

    def __init__(self, data, stop_gradient: bool = True, name: str = '',
                 _node=None, _leaf_index: int = 0):
        self._data = data
        self.stop_gradient = stop_gradient
        self.grad = None
        self._node = _node
        self._leaf_index = _leaf_index
        self.name = name
        self.persistable = False

    # -- raw value ---------------------------------------------------------
    @property
    def value(self):
        return self._data

    # -- metadata ----------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return jnp.dtype(self._data.dtype)

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    def numel(self):
        return self.size

    def dim(self):
        return self._data.ndim

    @property
    def place(self):
        try:
            dev = list(self._data.devices())[0]
            cls = (framework.TPUPlace if dev.platform == 'tpu'
                   else framework.CPUPlace)
            return cls(getattr(dev, 'id', 0))
        except Exception:  # paddle-lint: disable=swallowed-exception -- place probe on traced/abstract values; default place is correct there
            return framework.get_place()

    @property
    def is_leaf(self):
        return self._node is None

    # -- conversion --------------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def item(self):
        return self._data.item() if hasattr(self._data, 'item') else np.asarray(self._data).item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError('len() of a 0-d tensor')
        return self._data.shape[0]

    def __index__(self):
        return int(self.item())

    # numpy interop (lets np.asarray(tensor) work)
    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    # -- autograd ----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def _run_grad_hooks(self, g_val):
        """Apply registered hooks to the FULL gradient of one backward walk
        (never per-partial — a clipping hook must see the accumulated sum)."""
        hooks = getattr(self, '_grad_hooks', None)
        if hooks:
            g_t = Tensor(jnp.asarray(g_val, self.dtype))
            for h in list(hooks.values()):
                res = h(g_t)
                if res is not None:
                    g_t = res if isinstance(res, Tensor) else Tensor(res)
            g_val = g_t._data
        return g_val

    def _accumulate_grad(self, g_val):
        g_val = self._run_grad_hooks(g_val)
        if self.grad is None:
            self.grad = Tensor(jnp.asarray(g_val, self.dtype))
        else:
            self.grad = Tensor(self.grad._data + jnp.asarray(g_val, self.dtype))

    def register_hook(self, hook):
        """Register `hook(grad) -> grad | None`, run when this leaf's
        gradient arrives in backward (upstream Tensor.register_hook).
        Returns a handle with .remove()."""
        hooks = getattr(self, '_grad_hooks', None)
        if hooks is None:
            hooks = {}
            object.__setattr__(self, '_grad_hooks', hooks)
        hid = max(hooks, default=-1) + 1
        hooks[hid] = hook

        class _Handle:
            def remove(self_inner):
                hooks.pop(hid, None)
        return _Handle()

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):
        self.grad = None

    def detach(self):
        t = Tensor(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        return apply_op(lambda x: x + jnp.zeros((), x.dtype), self, _name='clone')

    def _rebind(self, result: 'Tensor'):
        """Adopt an op result in place (functional backing for mutating APIs)."""
        self._data = result._data
        self._node = result._node
        self._leaf_index = result._leaf_index
        if self._node is not None:
            self.stop_gradient = False
        return self

    # -- dtype/device movement --------------------------------------------
    def astype(self, dtype):
        dt = convert_dtype(dtype)
        return apply_op(lambda x: x.astype(dt), self, _name='astype')

    def cast(self, dtype):
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (a in ('cpu', 'tpu', 'gpu') or ':' in a):
                name, _, idx = a.partition(':')
                name = {'gpu': 'tpu', 'xla': 'tpu'}.get(name, name)
                place = (framework.CPUPlace if name == 'cpu' else framework.TPUPlace)(int(idx or 0))
                out = Tensor(jax.device_put(out._data, place.jax_device()),
                             stop_gradient=out.stop_gradient)
            else:
                out = out.astype(a)
        return out

    def cpu(self):
        return self.to('cpu')

    def cuda(self, *a, **k):  # reference-compat: accelerate place
        return self.to('tpu')

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, idx):
        return apply_op(lambda x, i: x[_unwrap_index(i)], self, _IndexBox(idx),
                        _name='getitem')

    def __setitem__(self, idx, value):
        if isinstance(value, Tensor):
            res = apply_op(
                lambda x, i, v: x.at[_unwrap_index(i)].set(v.astype(x.dtype)),
                self, _IndexBox(idx), value, _name='setitem')
        else:
            val = np.asarray(value)
            res = apply_op(
                lambda x, i: x.at[_unwrap_index(i)].set(jnp.asarray(val, x.dtype)),
                self, _IndexBox(idx), _name='setitem')
        self._rebind(res)

    # -- printing ----------------------------------------------------------
    def __repr__(self):
        try:
            vals = np.asarray(self._data)
            body = np.array2string(vals, precision=_PRINT_OPTIONS['precision'],
                                   threshold=_PRINT_OPTIONS['threshold'],
                                   edgeitems=_PRINT_OPTIONS['edgeitems'],
                                   max_line_width=_PRINT_OPTIONS['linewidth'])
        except Exception:  # paddle-lint: disable=swallowed-exception -- repr must never raise; <traced> is the honest rendering under tracing
            body = '<traced>'
        return (f'Tensor(shape={self.shape}, dtype={dtype_name(self.dtype)}, '
                f'place={self.place}, stop_gradient={self.stop_gradient},\n'
                f'       {body})')

    __str__ = __repr__

    def __hash__(self):
        return id(self)


class Parameter(Tensor):
    """Trainable leaf tensor (upstream: paddle/fluid/framework.py Parameter)."""
    __slots__ = ('trainable', 'optimize_attr', 'regularizer',
                 'initializer_info', '_lazy_init')

    def __init__(self, data, name: str = '', trainable: bool = True):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {'learning_rate': 1.0}
        self.regularizer = None
        self.persistable = True
        self._lazy_init = None

    def initialize(self):
        """Materialize a parameter created under LazyGuard (upstream
        lazy-init params run their recorded init op here). No-op for
        eagerly-created parameters."""
        if self._lazy_init is not None:
            init, shape, dt = self._lazy_init
            self._lazy_init = None
            val = init(shape, dt)
            self._data = val.value if isinstance(val, Tensor) else val
        return self

    @property
    def is_lazy(self):
        return self._lazy_init is not None


class _IndexBox:
    """Carries an arbitrary index expression through tree_flatten, exposing
    any Tensor components inside it as differentiable-op inputs (they are
    integer tensors, so they simply flow as non-differentiable leaves)."""

    def __init__(self, idx):
        self.idx = idx


def _unwrap_index(box):
    def unwrap(i):
        if isinstance(i, Tensor):
            return i._data
        if isinstance(i, (tuple,)):
            return tuple(unwrap(x) for x in i)
        if isinstance(i, list):
            return jnp.asarray(i) if i and not any(
                isinstance(x, (slice, type(None), type(Ellipsis))) for x in i
            ) else [unwrap(x) for x in i]
        return i
    return unwrap(box.idx if isinstance(box, _IndexBox) else box)


_tree.register_pytree_node(
    _IndexBox,
    lambda b: (tuple(_collect_tensors_in_index(b.idx)), b.idx),
    lambda idx, kids: _IndexBox(_restore_tensors_in_index(idx, list(kids))),
)


def _collect_tensors_in_index(idx):
    out = []

    def walk(i):
        if isinstance(i, Tensor):
            out.append(i)
        elif isinstance(i, (tuple, list)):
            for x in i:
                walk(x)
    walk(idx)
    return out


def _restore_tensors_in_index(idx, kids):
    def walk(i):
        if isinstance(i, Tensor):
            v = kids.pop(0)
            return v if isinstance(v, Tensor) else Tensor(v)
        if isinstance(i, tuple):
            return tuple(walk(x) for x in i)
        if isinstance(i, list):
            return [walk(x) for x in i]
        return i
    return walk(idx)


# ---------------------------------------------------------------------------
# The universal op dispatcher
# ---------------------------------------------------------------------------


def apply_op(fn: Callable, *args, _name: str = '', _cacheable: bool = True,
             **kwargs):
    """Run pure jax `fn` over (args, kwargs), unwrapping Tensors.

    Records a tape Node (with a forward-time jax.vjp) iff grad is enabled and
    some Tensor input requires grad. Returns Tensor-wrapped outputs mirroring
    fn's output pytree.

    Fast path: keyable calls (see paddle_tpu._dispatch) run through the
    dispatch cache — a jitted primal when no grad is needed, a jitted
    residual-returning forward whose reusable pullback feeds the tape
    when grad is on — so steady-state eager training stops re-tracing.
    `_cacheable=False` opts a call out (bodies that close over fresh
    arrays / per-call functions would only churn the cache).
    """
    leaves, treedef = _tree.tree_flatten((args, kwargs), is_leaf=_is_tensor)
    t_idx = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]
    tensors = [leaves[i] for i in t_idx]
    vals = [t._data for t in tensors]
    if _amp_cast_hook is not None:
        vals = _amp_cast_hook(vals, _name)

    record = autograd.is_grad_enabled() and any(
        not t.stop_gradient for t in tensors)

    primal_fn = None
    cached = None
    if _cacheable and t_idx and _dispatch.enabled():
        # key off the post-AMP-cast values: the cast is a pure function
        # of (op name, input dtypes, amp state) applied before dispatch,
        # so the cached executable composes with auto_cast unchanged
        cached = _dispatch.run(fn, _name, treedef, leaves, t_idx, vals,
                               record)
    elif t_idx:
        # disabled cache or explicit _cacheable=False opt-out: still a
        # slow-path dispatch, so it shows up in the telemetry
        _dispatch._note_fallback(_name)

    if cached is not None:
        out, vjp_fn, primal_fn = cached
    else:
        def pure(*vs):
            # Rebuild args with raw jax values in Tensor slots; fn receives
            # raw values wherever Tensors were passed.
            ls = list(leaves)
            for i, v in zip(t_idx, vs):
                ls[i] = v
            a, k = _tree.tree_unflatten(treedef, ls)
            return fn(*a, **k)

        primal_fn = pure
        if record:
            out, vjp_fn = jax.vjp(pure, *vals)
        else:
            out = pure(*vals)

    out_leaves, out_td = _tree.tree_flatten(out)
    node = None
    if record:
        # Snapshot inputs (InputRef) so later in-place rebinds of the live
        # Tensors can't sever or re-key the recorded graph. On the cached
        # path primal_fn is the entry's shared jitted primal, so tape
        # replay (paddle.grad create_graph / jacobian) also skips
        # re-tracing.
        node = autograd.Node(
            [autograd.InputRef(t) for t in tensors], vjp_fn, primal_fn,
            [(tuple(np.shape(l)), jnp.dtype(getattr(l, 'dtype', np.result_type(l))))
             for l in out_leaves],
            out_td, name=_name)
    wrapped = [
        Tensor(l,
               stop_gradient=(not record) or not jnp.issubdtype(
                   jnp.dtype(getattr(l, 'dtype', np.result_type(l))), jnp.inexact),
               _node=node, _leaf_index=i)
        if not isinstance(l, Tensor) else l
        for i, l in enumerate(out_leaves)
    ]
    result = _tree.tree_unflatten(out_td, wrapped)
    if _numerics_hook is not None:
        _numerics_hook(result, _name)
    return result


def to_jax(x):
    """Unwrap Tensor → jax value (pass-through otherwise)."""
    return x._data if isinstance(x, Tensor) else x


def wrap(x, stop_gradient=True):
    return Tensor(jnp.asarray(x), stop_gradient=stop_gradient)
