"""`nlp/deepseek_v3.py::_own_tokens_attention` at the level of arrays: a
block of queries is scored against the keys up to its own last row and
no further, and the result is what ONE unblocked causal call gives. No
model is built here."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nlp import deepseek_v3
from paddle_tpu.ops.pallas import _attention_xla

BLOCK = 8


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(deepseek_v3, 'PREFILL_QUERY_BLOCK', BLOCK)


def _qkv(s, batch=2, heads=3, qk=12, vd=12, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, s, heads, qk), jnp.float32),
            jax.random.normal(keys[1], (batch, s, heads, qk), jnp.float32),
            jax.random.normal(keys[2], (batch, s, heads, vd), jnp.float32))


@pytest.mark.parametrize('s, padded, gain, vd', [
    (32, False, 1.0, 12),       # a whole number of blocks
    (27, False, 1.0, 12),       # the last block is short
    (27, True, 1.0, 12),        # a caller's mask hides padding keys
    (24, False, 1.7, 12),       # a configuration's gain on the logits
    (29, True, 0.6, 5),         # v narrower than qk, and all at once
    (BLOCK, False, 1.3, 12),    # one block: the one call
], ids=['whole_blocks', 'short_last_block', 'padding_mask', 'gain',
        'narrow_v_all_at_once', 'one_block'])
def test_blocks_over_a_prefix_of_the_keys_give_the_unblocked_result(
        s, padded, gain, vd):
    q, k, v = _qkv(s, vd=vd)
    mask = None
    if padded:          # the last 5 keys of the second sequence are padding
        mask = jnp.ones((2, 1, 1, s), bool).at[1, ..., s - 5:].set(False)
    ref = jax.jit(lambda q, k, v: _attention_xla(
        q * gain, k, v, mask=mask, causal=True))(q, k, v)
    got = jax.jit(lambda q, k, v: deepseek_v3._own_tokens_attention(
        q, k, v, mask, gain))(q, k, v)
    assert got.shape == (2, s, 3, vd)
    rows = slice(None) if mask is None else slice(0, s - 5)
    # a padded query row sees its own padding alone in neither form's
    # favour: compare the rows that are tokens
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref)[0],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got)[1, rows],
                               np.asarray(ref)[1, rows],
                               rtol=2e-6, atol=2e-6)


def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.extend(tuple(v.aval.shape) for v in eqn.outvars
                   if hasattr(v.aval, 'shape'))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    _shapes(sub, out)
    return out


@pytest.mark.parametrize('s', [40, 43], ids=['whole_blocks', 'short_last'])
def test_no_scores_lie_above_a_blocks_own_last_row(s):
    """Heads 3, qk 12, v 11: no other axis is as short as a block or as
    long as a prefix. Every array whose last two axes are (queries,
    keys) belongs to ONE block: block `i` has keys `min((i + 1) *
    BLOCK, s)`, never more; nothing is `s x s`; and the pairs scored
    are what the model tells the serving engine (`own_tokens_pairs`)."""
    q, k, v = _qkv(s, batch=1, vd=11)
    shapes = _shapes(jax.make_jaxpr(
        lambda *a: deepseek_v3._own_tokens_attention(*a, None))(
            q, k, v).jaxpr, [])
    ends = [min(first + BLOCK, s) for first in range(0, s, BLOCK)]
    blocks = {(min(BLOCK, s - first), first + min(BLOCK, s - first))
              for first in range(0, s, BLOCK)}
    scores = {sh[-2:] for sh in shapes
              if len(sh) == 4 and sh[:2] == (1, 3) and sh[-2] <= BLOCK
              and sh[-1] not in (1, 11, 12)}
    assert scores == blocks
    assert max(keys for _, keys in scores) == s
    assert not [sh for sh in shapes if len(sh) >= 2 and sh[-2:] == (s, s)]
    assert sum(math.prod(sh) for sh in scores) \
        == deepseek_v3.own_tokens_pairs(s) \
        == sum((last - first) * last
               for first, last in zip(range(0, s, BLOCK), ends))
    # the whole bucket unblocked, by the same walk, IS s x s
    assert deepseek_v3.own_tokens_pairs(BLOCK) == BLOCK * BLOCK
