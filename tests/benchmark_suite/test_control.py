"""`correct` has been shown to fail.

The control: the plain reference in the nearest precision below the
configuration's, put in the program's place, comes out NOT correct
against the toy cells' limits (the chip-size readings are in PERF.md).
And a run whose timed path is broken underneath — a step that returns
its state unchanged, a token altered where it is produced — sees
`correct` come out false."""
import pytest

import _toy


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return _toy.make_root(tmp_path_factory.mktemp('toy'))


def _limits(root, cell):
    import json
    import os
    with open(os.path.join(root, 'benchmarks', 'limits', f'{cell}.json')) as f:
        return json.load(f)


def test_training_control_fp8_fails_a_limit(root):
    out, lines = _toy.run_toy(root, 'toy-train', seed=21, control='fp8')
    assert out['correct'] is True           # the sound program passes
    lim = _limits(root, 'toy-train')
    norm = eval(_toy.logged(
        lines, 'control fp8 first_grad_norm_worst_leaf_gap')[0])[0]
    sketch = eval(_toy.logged(
        lines, 'control fp8 first_grad_sketch_worst_leaf_gap')[0])[0]
    # the sketch is the number fp8 moves in first order: it fails its
    # limit with room; the norm moves only in second order
    assert sketch > 3 * lim['grad_sketch_gap'], sketch
    assert sketch > 5 * norm, (sketch, norm)


@pytest.mark.parametrize('cell', ['toy-docs', 'toy-chat'])
def test_serving_control_fp8_fails_the_limit(root, cell):
    out, lines = _toy.run_toy(root, cell, seed=22, seconds=3.0,
                              control='fp8')
    assert out['correct'] is True
    gap = float(_toy.logged(lines, 'control fp8 served_logit_gap_widest')[0])
    assert gap > _limits(root, cell)['served_gap'], gap


_FROZEN_STEP = '''
import paddle_tpu.optimizer as _o
def _frozen(self, grads, params, state, lr_value):
    return params, state        # a step that returns its state unchanged
_o.Optimizer.apply_gradients = _frozen
'''

_ALTERED_TOKEN = '''
import numpy as _np
import paddle_tpu.serving.engine as _e
_fetch = _e._from_device
def _altered(x):
    v = _np.array(_fetch(x))
    if v.dtype.kind == "i" and v.ndim == 2:
        v[:, -1] = (v[:, -1] + 1) % 8192    # one token of each block altered
    return v
_e._from_device = _altered
'''


def test_frozen_train_step_is_not_correct(root):
    out, lines = _toy.run_toy(root, 'toy-train', seed=23, patch=_FROZEN_STEP)
    assert out['correct'] is False
    assert any('NOT CORRECT' in ln for ln in lines)


@pytest.mark.parametrize('cell', ['toy-chat', 'toy-docs'])
def test_altered_served_token_is_not_correct(root, cell):
    out, lines = _toy.run_toy(root, cell, seed=24, seconds=3.0,
                              patch=_ALTERED_TOKEN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)
