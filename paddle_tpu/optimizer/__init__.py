"""paddle.optimizer — functional pytree core with an eager facade.

Upstream: python/paddle/optimizer/*.py. Each optimizer defines a pure
per-leaf update rule; the same rule serves
  - the eager path (`step()` reads `.grad` off Parameters and rebinds), and
  - the jitted path (`init_state` / `apply_gradients` over raw pytrees,
    used by paddle_tpu.jit.TrainStep with donated buffers).
Multi-precision: bf16/fp16 params keep an fp32 master copy in the slot
state; updates run in fp32 and cast back (TPU-native replacement for the
reference's multi_precision / master-weight machinery).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import lr as lr  # noqa: F401  (paddle.optimizer.lr.*)
from .lr import LRScheduler
from ..nn.clip import ClipGradBase
from ..tensor import Parameter, Tensor

_tree = jax.tree_util


def _is_low_precision(dtype):
    return jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))



def _flatten_for_update(params, grads, slots):
    """Shared path-flattening for optimizer updates (fused and offload
    paths must derive leaf names identically): returns
    (treedef, names, flat_params, flat_grads, flat_slots)."""
    paths_p, treedef = _tree.tree_flatten_with_path(params)
    names = ['.'.join(str(getattr(e, 'key', e)) for e in path)
             for path, _ in paths_p]
    flat_p = [p for _, p in paths_p]
    flat_g = treedef.flatten_up_to(grads)
    flat_s = treedef.flatten_up_to(slots)
    return treedef, names, flat_p, flat_g, flat_s

class Optimizer:
    """Base optimizer. Subclasses implement `_init_slots` and `_rule`."""

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        if parameters is not None:
            parameters = list(parameters)
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._decay_mode = 'l2'
        if weight_decay is None:
            self._coeff = 0.0
        elif isinstance(weight_decay, (int, float)):
            self._coeff = float(weight_decay)
        else:  # L1Decay/L2Decay regularizer object
            self._coeff = float(getattr(weight_decay, '_coeff',
                                        getattr(weight_decay, 'coeff', 0.0)))
            if type(weight_decay).__name__ == 'L1Decay':
                self._decay_mode = 'l1'
        self._step_count = 0
        self._slots: Dict[int, dict] = {}  # id(param) -> slot dict

    # -- the pure core ------------------------------------------------------
    def _init_slots(self, p_value) -> dict:
        return {}

    def _rule(self, g, p, slots, lr, step):
        """Pure per-leaf update: (grad, fp32-param, slots, lr, step) ->
        (new fp32 param, new slots). g is fp32."""
        raise NotImplementedError

    def _decoupled_decay(self) -> bool:
        return False  # AdamW overrides

    def _leaf_init(self, p_value):
        slots = self._init_slots(p_value)
        if self._multi_precision and _is_low_precision(p_value.dtype):
            slots['master'] = p_value.astype(jnp.float32)
        return slots

    def _coeff_for(self, name):
        """Per-parameter decay coefficient (AdamW/Lamb exclusions)."""
        return self._coeff

    def _leaf_apply(self, g, p_value, slots, lr_value, step, name=None):
        low = 'master' in slots
        p32 = slots['master'] if low else p_value.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        coeff = self._coeff_for(name)
        if coeff and not self._decoupled_decay():
            reg = jnp.sign(p32) if self._decay_mode == 'l1' else p32
            g32 = g32 + coeff * reg
        new_p32, new_slots = self._rule(g32, p32, dict(slots), lr_value, step)
        if coeff and self._decoupled_decay():
            new_p32 = new_p32 - lr_value * coeff * p32
        if low:
            new_slots['master'] = new_p32
            return new_p32.astype(p_value.dtype), new_slots
        return new_p32.astype(p_value.dtype), new_slots

    # -- functional pytree API (jit path) -----------------------------------
    def init_state(self, params):
        """params: pytree of raw jax arrays -> opt state pytree."""
        slots = _tree.tree_map(self._leaf_init, params)
        return {'step': jnp.zeros((), jnp.int32), 'slots': slots}

    def apply_gradients(self, grads, params, state, lr_value):
        """Pure: (grads, params, state, lr) -> (new_params, new_state).
        Safe to call under jit; lr_value may be a traced scalar. Traced
        under the named scope `optimizer`, which is how a device trace
        tells the update from the forward and backward passes."""
        with jax.named_scope('optimizer'):
            if self._grad_clip is not None:
                grads = self._grad_clip.apply_pytree(grads)
            step = state['step'] + 1
            treedef, names, flat_p, flat_g, flat_s = _flatten_for_update(
                params, grads, state['slots'])
            new_p, new_s = [], []
            for g, p, s, nm in zip(flat_g, flat_p, flat_s, names):
                if g is None:
                    new_p.append(p)
                    new_s.append(s)
                    continue
                np_, ns_ = self._leaf_apply(g, p, s, lr_value, step,
                                            name=nm)
                new_p.append(np_)
                new_s.append(ns_)
            return (_tree.tree_unflatten(treedef, new_p),
                    {'step': step,
                     'slots': _tree.tree_unflatten(treedef, new_s)})

    # -- eager facade -------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError('set_lr cannot override an LRScheduler')
        self._learning_rate = float(value)

    @property
    def _params(self) -> List[Parameter]:
        if self._parameter_list is None:
            raise ValueError('optimizer constructed without parameters')
        return self._parameter_list

    def step(self):
        params_grads = [(p, p.grad) for p in self._params
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr_v = self.get_lr()
        self._step_count += 1
        for p, g in params_grads:
            slots = self._slots.get(id(p))
            if slots is None:
                slots = self._leaf_init(p.value)
            # per-param lr multiplier (ParamAttr learning_rate)
            mult = 1.0
            if isinstance(p, Parameter):
                mult = p.optimize_attr.get('learning_rate', 1.0)
            new_val, new_slots = self._leaf_apply(
                g.value, p.value, slots, lr_v * mult, self._step_count,
                name=getattr(p, 'name', None))
            p._data = new_val
            p._node = None
            self._slots[id(p)] = new_slots

    def clear_grad(self, set_to_zero=True):
        for p in self._params:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    # -- state dict ---------------------------------------------------------
    def state_dict(self):
        out = {'step': self._step_count, 'slots': []}
        for p in self._params:
            s = self._slots.get(id(p), None)
            out['slots'].append(
                None if s is None else
                {k: np.asarray(v) for k, v in s.items()})
        if isinstance(self._learning_rate, LRScheduler):
            out['LR_Scheduler'] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, sd):
        self._step_count = int(sd.get('step', 0))
        slots = sd.get('slots', [])
        for p, s in zip(self._params, slots):
            if s is not None:
                self._slots[id(p)] = {k: jnp.asarray(v) for k, v in s.items()}
        if 'LR_Scheduler' in sd and isinstance(self._learning_rate,
                                               LRScheduler):
            self._learning_rate.set_state_dict(sd['LR_Scheduler'])


class SGD(Optimizer):
    def _rule(self, g, p, slots, lr, step):
        return p - lr * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_slots(self, p):
        return {'velocity': jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        v = self._momentum * slots['velocity'] + g
        if self._nesterov:
            p = p - lr * (g + self._momentum * v)
        else:
            p = p - lr * v
        slots['velocity'] = v
        return p, slots


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {'moment': jnp.full(p.shape, self._init_acc, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        m = slots['moment'] + jnp.square(g)
        slots['moment'] = m
        return p - lr * g / (jnp.sqrt(m) + self._epsilon), slots


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slots(self, p):
        s = {'mean_square': jnp.zeros(p.shape, jnp.float32),
             'momentum': jnp.zeros(p.shape, jnp.float32)}
        if self._centered:
            s['mean_grad'] = jnp.zeros(p.shape, jnp.float32)
        return s

    def _rule(self, g, p, slots, lr, step):
        ms = self._rho * slots['mean_square'] + (1 - self._rho) * jnp.square(g)
        slots['mean_square'] = ms
        denom = ms
        if self._centered:
            mg = self._rho * slots['mean_grad'] + (1 - self._rho) * g
            slots['mean_grad'] = mg
            denom = ms - jnp.square(mg)
        upd = g / jnp.sqrt(denom + self._epsilon)
        if self._momentum:
            mom = self._momentum * slots['momentum'] + lr * upd
            slots['momentum'] = mom
            return p - mom, slots
        return p - lr * upd, slots


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 amsgrad=False, moment_dtype=None, offload=None, name=None):
        """moment_dtype: storage dtype for m/v (default fp32). 'bfloat16'
        halves optimizer HBM — how billion-param models fit one chip; the
        moment *update* still computes in fp32 either way.

        offload='host' keeps m/v (and masters) in pinned host memory and
        streams per-leaf updates through HBM (upstream: fleet sharding
        `offload`; see optimizer/offload.py). Honored by jit.TrainStep."""
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._amsgrad = amsgrad
        self._moment_dtype = jnp.dtype(moment_dtype) if moment_dtype \
            else jnp.float32
        if offload not in (None, 'host'):
            raise ValueError(f"offload must be None or 'host', got "
                             f"{offload!r}")
        self._offload = offload

    def _init_slots(self, p):
        s = {'moment1': jnp.zeros(p.shape, self._moment_dtype),
             'moment2': jnp.zeros(p.shape, self._moment_dtype)}
        if self._amsgrad:
            s['moment2_max'] = jnp.zeros(p.shape, self._moment_dtype)
        return s

    def _rule(self, g, p, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots['moment1'].astype(jnp.float32) + (1 - b1) * g
        v = b2 * slots['moment2'].astype(jnp.float32) \
            + (1 - b2) * jnp.square(g)
        slots['moment1'] = m.astype(self._moment_dtype)
        slots['moment2'] = v.astype(self._moment_dtype)
        t = step.astype(jnp.float32) if hasattr(step, 'astype') \
            else jnp.asarray(step, jnp.float32)
        lr_t = lr * jnp.sqrt(1 - jnp.power(b2, t)) / (1 - jnp.power(b1, t))
        if self._amsgrad:
            vm = jnp.maximum(slots['moment2_max'].astype(jnp.float32), v)
            slots['moment2_max'] = vm.astype(self._moment_dtype)
            v = vm
        return p - lr_t * m / (jnp.sqrt(v) + self._epsilon), slots


class AdamW(Adam):
    """Adam with decoupled weight decay (upstream: optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, amsgrad=False,
                 moment_dtype=None, offload=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         amsgrad, moment_dtype, offload)
        self._apply_decay_fn = apply_decay_param_fun

    def _decoupled_decay(self):
        return True

    def _coeff_for(self, name):
        # exclusion is per-leaf, so grad clipping stays one global pass
        if self._apply_decay_fn is not None and name is not None \
                and not self._apply_decay_fn(name):
            return 0.0
        return self._coeff


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn
        self._lamb_now = lamb_weight_decay

    def _init_slots(self, p):
        return {'moment1': jnp.zeros(p.shape, jnp.float32),
                'moment2': jnp.zeros(p.shape, jnp.float32)}

    def _coeff_for(self, name):
        # called once per leaf right before _rule (trace-time python), so
        # stashing the active decay here routes the exclusion into _rule
        self._lamb_now = 0.0 if (
            self._exclude_fn is not None and name is not None
            and self._exclude_fn(name)) else self._lamb_decay
        return 0.0

    def _rule(self, g, p, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots['moment1'] + (1 - b1) * g
        v = b2 * slots['moment2'] + (1 - b2) * jnp.square(g)
        slots['moment1'], slots['moment2'] = m, v
        t = jnp.asarray(step, jnp.float32)
        m_hat = m / (1 - jnp.power(b1, t))
        v_hat = v / (1 - jnp.power(b2, t))
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon) + self._lamb_now * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, slots


class Adadelta(Optimizer):
    """Upstream: optimizer/adadelta.py — accumulates squared grads and
    squared updates; the effective step needs no external lr scale
    (lr multiplies anyway, matching paddle)."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._rho, self._epsilon = rho, epsilon

    def _init_slots(self, p):
        return {'avg_squared_grad': jnp.zeros(p.shape, jnp.float32),
                'avg_squared_update': jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        rho, eps = self._rho, self._epsilon
        sg = rho * slots['avg_squared_grad'] + (1 - rho) * jnp.square(g)
        upd = g * jnp.sqrt(slots['avg_squared_update'] + eps) \
            / jnp.sqrt(sg + eps)
        su = rho * slots['avg_squared_update'] + (1 - rho) * jnp.square(upd)
        slots['avg_squared_grad'] = sg
        slots['avg_squared_update'] = su
        return p - lr * upd, slots


class Adamax(Optimizer):
    """Upstream: optimizer/adamax.py — Adam with an infinity-norm second
    moment."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {'moment': jnp.zeros(p.shape, jnp.float32),
                'inf_norm': jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots['moment'] + (1 - b1) * g
        u = jnp.maximum(b2 * slots['inf_norm'], jnp.abs(g))
        slots['moment'] = m
        slots['inf_norm'] = u
        t = jnp.asarray(step, jnp.float32)
        return p - (lr / (1 - jnp.power(b1, t))) * m \
            / (u + self._epsilon), slots


class NAdam(Adam):
    """Adam with Nesterov momentum and the Dozat momentum-decay schedule
    mu_t = beta1*(1 - 0.5*0.96^(t*psi)) (matches torch.optim.NAdam; the
    running mu product lives in a scalar slot per leaf)."""

    def __init__(self, *args, momentum_decay=0.004, **kwargs):
        super().__init__(*args, **kwargs)
        self._momentum_decay = momentum_decay

    def _init_slots(self, p):
        s = super()._init_slots(p)
        s['mu_product'] = jnp.ones((), jnp.float32)
        return s

    def _rule(self, g, p, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        psi = self._momentum_decay
        m = b1 * slots['moment1'].astype(jnp.float32) + (1 - b1) * g
        v = b2 * slots['moment2'].astype(jnp.float32) \
            + (1 - b2) * jnp.square(g)
        slots['moment1'] = m.astype(self._moment_dtype)
        slots['moment2'] = v.astype(self._moment_dtype)
        t = jnp.asarray(step, jnp.float32)
        mu_t = b1 * (1 - 0.5 * jnp.power(0.96, t * psi))
        mu_t1 = b1 * (1 - 0.5 * jnp.power(0.96, (t + 1) * psi))
        mu_prod = slots['mu_product'] * mu_t
        slots['mu_product'] = mu_prod
        m_hat = mu_t1 * m / (1 - mu_prod * mu_t1) \
            + (1 - mu_t) * g / (1 - mu_prod)
        v_hat = v / (1 - jnp.power(b2, t))
        return p - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon), slots


class RAdam(Adam):
    """Rectified Adam (upstream: incubate/radam): falls back to
    unadapted SGD-with-momentum while the variance rectifier is
    untrustworthy (rho_t <= 4)."""

    def _rule(self, g, p, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots['moment1'].astype(jnp.float32) + (1 - b1) * g
        v = b2 * slots['moment2'].astype(jnp.float32) \
            + (1 - b2) * jnp.square(g)
        slots['moment1'] = m.astype(self._moment_dtype)
        slots['moment2'] = v.astype(self._moment_dtype)
        t = jnp.asarray(step, jnp.float32)
        rho_inf = 2.0 / (1 - b2) - 1
        rho_t = rho_inf - 2 * t * jnp.power(b2, t) / (1 - jnp.power(b2, t))
        m_hat = m / (1 - jnp.power(b1, t))
        r = jnp.sqrt(jnp.maximum(
            (rho_t - 4) * (rho_t - 2) * rho_inf
            / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-9),
            0.0))
        # threshold 5 and eps-on-sqrt(v) match the torch/paddle
        # implementations (the paper's nominal cutoff is 4)
        adaptive = lr * r * m_hat * jnp.sqrt(1 - jnp.power(b2, t)) \
            / (jnp.sqrt(v) + self._epsilon)
        plain = lr * m_hat
        return p - jnp.where(rho_t > 5.0, adaptive, plain), slots


class Rprop(Optimizer):
    """Resilient backprop (upstream: optimizer/rprop.py) — per-weight
    step sizes grown/shrunk by gradient sign agreement; gradients'
    magnitudes are ignored."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision)
        self._eta_minus, self._eta_plus = etas
        self._lr_min, self._lr_max = learning_rate_range
        try:
            self._lr0 = float(learning_rate)
        except (TypeError, ValueError):
            self._lr0 = 1e-2  # scheduler-driven: seed step sizes modestly

    def _init_slots(self, p):
        return {'prev_grad': jnp.zeros(p.shape, jnp.float32),
                'step_size': jnp.full(p.shape, self._lr0, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        sign = jnp.sign(g * slots['prev_grad'])
        factor = jnp.where(sign > 0, self._eta_plus,
                           jnp.where(sign < 0, self._eta_minus, 1.0))
        size = jnp.clip(slots['step_size'] * factor, self._lr_min,
                        self._lr_max)
        # on sign flip, skip the update and zero the remembered grad
        g_eff = jnp.where(sign < 0, 0.0, g)
        slots['prev_grad'] = g_eff
        slots['step_size'] = size
        return p - size * jnp.sign(g_eff), slots


class ASGD(Optimizer):
    """Averaged SGD (upstream: optimizer/asgd.py): steps with the mean
    of the last `batch_num` gradients. The ring buffer of gradients is
    optimizer state, exactly like upstream (paddle allocates a
    [batch_num, *shape] accumulator per parameter — mind the HBM cost
    for large batch_num)."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision)
        self._batch_num = max(int(batch_num), 1)

    def _init_slots(self, p):
        if self._batch_num == 1:
            return {}
        return {'grad_ring': jnp.zeros((self._batch_num,) + tuple(p.shape),
                                       jnp.float32),
                'grad_sum': jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, step):
        if self._batch_num == 1:
            return p - lr * g, slots
        n = self._batch_num
        t = step  # 1-based
        idx = (t - 1) % n
        old = slots['grad_ring'][idx]
        ssum = slots['grad_sum'] - old + g
        slots['grad_ring'] = slots['grad_ring'].at[idx].set(g)
        slots['grad_sum'] = ssum
        denom = jnp.minimum(t, n).astype(jnp.float32)
        return p - lr * ssum / denom, slots


# regularizer shims (upstream: python/paddle/regularizer.py)
class L2Decay:
    def __init__(self, coeff=0.0):
        self._coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self._coeff = float(coeff)
