"""`nlp/xing4.py` served: the engine's own prefill program and then
decode through the latent cache, at every position against the plain
float32 reference (`benchmarks/reference/xing4.py`), through
`InferenceEngine` with both decode programs, what a decode round's span
carries, and where the scopes place the residual path. The helpers, the
tolerance and its reason are `tests/test_xing4.py`'s (a file of its own
so that no worker of the suite carries both)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_state
from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                        DeepseekV3ForCausalLM)
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.serving import InferenceEngine, SamplingParams

from test_xing4 import (BLOCK, BUCKET, MAX_LEN, TOL, _cfg, _ids, _model,
                        _ref_logits, _weights)


@pytest.fixture(scope='module')
def tiny():
    """One dense and one expert layer, four sublayers."""
    cfg = _cfg(num_hidden_layers=2)
    w = _weights(cfg)
    return cfg, w, _model(cfg, w)


# ---------------------------------------------------------------------------
# (a) prefill by bucket, then decode through the cache, at every position
# ---------------------------------------------------------------------------
N_NEW = 3 * BLOCK + 1


def _engine(model, **extra):
    kw = dict(num_slots=2, max_length=MAX_LEN, decode_block=BLOCK,
              buckets=[BUCKET, 32], eos_token_id=-1)
    kw.update(extra)
    return InferenceEngine(model, **kw)


def test_prefill_program_then_decode_logits_at_every_position(
        tiny, n_prompt=BUCKET + 11):
    """The engine's own prefill program on a prompt right-padded to its
    bucket (attention over its own tokens), the last prompt token
    forwarded again at its slot, then one token at a time over the rows
    held (absorbed): the LOGITS at every position against the
    reference's full forward, positions 26-39, past the 16 YaRN
    stretches."""
    cfg, w, model = tiny
    eng = _engine(model)
    fwd = jax.jit(cached_forward(model, *functional_state(model)))
    ids = _ids((1, n_prompt + N_NEW), 3 + n_prompt)
    ref = _ref_logits(cfg, w, ids)
    bucket = eng.pool.bucket_for(n_prompt)
    padded = np.zeros((1, bucket), 'int32')
    padded[:, :n_prompt] = ids[:, :n_prompt]
    cache = jax.jit(eng._prefill_fn)(eng._params, eng._frozen, eng._buffers,
                                     jnp.asarray(padded))
    assert [tuple(leaf.shape) for leaf in cache[0]] == [
        (1, MAX_LEN, 16), (1, MAX_LEN, 4)]
    k_slot = jnp.arange(MAX_LEN)
    worst = 0.0
    for t in range(n_prompt - 1, n_prompt + N_NEW):
        pos = jnp.full((1,), t, jnp.int32)
        mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
        lg, cache = fwd(jnp.asarray(ids[:, t:t + 1]), cache, pos, pos, mask)
        worst = max(worst, np.abs(np.asarray(lg)[0, 0] - ref[0, t]).max())
    assert worst < TOL


# ---------------------------------------------------------------------------
# (b) through InferenceEngine: both decode programs, the span, the scopes
# ---------------------------------------------------------------------------
def _served_gap(cfg, w, prompt, toks):
    """How far a served token's reference logit lies below the
    reference's best at its position: the benchmark's comparison."""
    lg = _ref_logits(cfg, w, prompt + toks[:-1])[0, len(prompt) - 1:]
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


@pytest.fixture(scope='module')
def served(tiny):
    """max_length 64: rounds attend over 32 latent rows while every
    active position allows it, then over 64. One request stays inside
    the half program, one crosses over, one starts past it."""
    cfg, w, model = tiny
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model)
    gaps = []
    for n_prompt, n_new in ((3, 12), (20, 24), (30, 12)):
        prompt = np.random.RandomState(n_prompt).randint(
            3, 128, n_prompt).tolist()
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert h.error is None and len(h.tokens) == n_new
        gaps.append(_served_gap(cfg, w, prompt, list(h.tokens)))
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    return eng, gaps, rounds


def test_through_the_engine_with_both_decode_programs(served):
    eng, gaps, rounds = served
    assert max(gaps) < TOL
    assert {a['rows'] for a in rounds} == {32, 64}
    assert eng._counts['prefills'] == 3
    assert not eng.pool.stands_at_one_position


def test_decode_round_carries_the_counts_the_roofline_reads(served):
    """What `benchmarks/readers/mhc_decode_roofline.py` needs is on
    every round: the latent entry's and the expert layer's come from
    the parts this model is built of, `residual_streams` from the
    model."""
    eng, _, rounds = served
    assert rounds and eng.pool.latent_layers == (0, 1)
    for a in rounds:
        assert a['residual_streams'] == 4
        assert a['latent_layers'] == 2 and a['latent_row_bytes'] == 160
        assert a['expert_layer_substeps'] == BLOCK and a['experts'] == 8
        assert 0 < a['experts_touched'] <= 8 * a['expert_layer_substeps']
        assert 0 < a['needed_rows'] <= a['read_rows'] == 2 * 2 * a['rows']
        assert a['active'] == 1


def test_scopes_place_the_residual_path_under_mhc(served):
    """`mhc` is in the vocabulary and on both programs, OUTERMOST over
    its maps and mixes (so `decode_scope_share` counts them under it and
    not under `norm`), and the blocks inside a hyper-connection keep
    their own outermost scopes."""
    assert 'mhc' in programs.SCOPES
    table = programs.scope_table()
    # (a prefill returns rows and no logits, so the compiler drops the
    # LAST layer's MLP — here the one expert layer — from it)
    moe = {'moe/router', 'moe/experts', 'moe/shared'}
    for prog, more in (('serving.decode_block', moe),
                       (f'serving.prefill_{BUCKET}', set())):
        ops = [op for op, *_ in table[prog].values()]
        paths = [programs.scope_path(op) for op in ops]
        found = {p[0] for p in paths if p}
        assert {'mhc', 'attention', 'norm', 'mlp'} | more <= found
        under = [op for op, p in zip(ops, paths) if p and p[0] == 'mhc']
        assert any('/mhc/maps/' in op for op in under)
        assert any('/mhc/mix/' in op for op in under)
        assert all(p == ('mhc',) for p in map(programs.scope_path, under))
    decode = [op for op, *_ in table['serving.decode_block'].values()]
    assert any('latent_absorb' in op for op in decode)
    assert programs.scope_path('jit(f)/mhc/maps/exp') == ('mhc',)


def test_a_model_with_one_stream_says_nothing_of_streams():
    # a model with one stream says nothing
    log = obs.get_event_log()
    log.clear()
    paddle.seed(0)
    other = InferenceEngine(
        DeepseekV3ForCausalLM(DeepseekV3Config.tiny(
            num_hidden_layers=1)).eval(), num_slots=2,
        max_length=MAX_LEN, decode_block=BLOCK, buckets=[BUCKET])
    other.submit([5, 6, 7], SamplingParams(max_new_tokens=5,
                                           eos_token_id=-1))
    other.run()
    last = [e['attrs'] for e in log.events()
            if e['name'] == 'serving.decode_round'][-1]
    assert 'residual_streams' not in last and 'latent_layers' in last
