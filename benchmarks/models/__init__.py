"""Adapters from a configuration file to the program's model classes:
one module per `model_class`, found by name. Each gives `build(cfg,
**extra)` (the program's model, parameters not yet made), `name_map(cfg)`
(program parameter name -> (canonical stacked leaf, layer index or None))
and `reference` (the plain reference's module name)."""
from __future__ import annotations

import importlib


def adapter(model_class):
    return importlib.import_module(f'{__name__}.{model_class}')


def fill(model, weights, name_map):
    """Give the program's model the seeded weights: one jitted call cuts
    the stacked canonical leaves into the program's per-layer leaves;
    the parameters (built under LazyGuard, so never initialised twice)
    then take them as they are."""
    import jax

    def cut(w):
        return {name: (w[canon] if layer is None else w[canon][layer])
                for name, (canon, layer) in name_map.items()}
    leaves = jax.jit(cut)(weights)
    params = dict(model.named_parameters())
    missing = set(params) ^ set(leaves)
    if missing:
        raise KeyError(f'name map and model disagree on {sorted(missing)[:6]}')
    for name, p in params.items():
        v = leaves[name]
        if tuple(p.shape) != tuple(v.shape):
            raise ValueError(f'{name}: model has {tuple(p.shape)}, '
                             f'weights have {tuple(v.shape)}')
        p._data = v
        p._lazy_init = None
    return model
