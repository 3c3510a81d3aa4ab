"""`nlp/xing4.py` served: the engine's own prefill program and then
decode through the latent cache, at every position against the plain
float32 reference (`benchmarks/reference/xing4.py`), through
`InferenceEngine` with both decode programs, what a decode round's span
carries, and where the scopes place the residual path. The family, the
tolerance and its reason are `tests/test_xing4.py`'s, the shared cases
`tests/family_harness.py`'s (a file of its own so that no worker of the
suite carries both)."""
import pytest

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                        DeepseekV3ForCausalLM)
from paddle_tpu.serving import InferenceEngine

import family_harness as H
from family_harness import BLOCK, BUCKET, MAX_LEN
from test_xing4 import FAM

built, tiny = H.fixtures(FAM)


# ---------------------------------------------------------------------------
# (a) prefill by bucket, then decode through the cache, at every position
# ---------------------------------------------------------------------------
# positions 26-39, past the 16 YaRN stretches
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, (BUCKET + 11,), 3 * BLOCK + 1,
                          entry=[(1, MAX_LEN, 16), (1, MAX_LEN, 4)])


# ---------------------------------------------------------------------------
# (b) through InferenceEngine: both decode programs, the span, the scopes
# ---------------------------------------------------------------------------
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)


@pytest.fixture(scope='module')
def served(tiny):
    """max_length 64: rounds attend over 32 latent rows while every
    active position allows it, then over 64. One request stays inside
    the half program, one crosses over, one starts past it."""
    cfg, w, model = tiny
    log = H.cleared_log()
    eng = H.engine(model)
    H.one_at_a_time(FAM, cfg, w, eng, ((3, 12), (20, 24), (30, 12)))
    return eng, H.rounds(log)


def test_through_the_engine_with_both_decode_programs(served):
    eng, rounds = served
    assert {a['rows'] for a in rounds} == {32, 64}
    assert eng._counts['prefills'] == 3
    assert not eng.pool.stands_at_one_position


def test_decode_round_carries_the_counts_the_roofline_reads(served):
    """What `benchmarks/readers/mhc_decode_roofline.py` needs is on
    every round: the latent entry's and the expert layer's come from
    the parts this model is built of, `residual_streams` from the
    model."""
    eng, rounds = served
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
    log = H.cleared_log()
    paddle.seed(0)
    other = InferenceEngine(
        DeepseekV3ForCausalLM(DeepseekV3Config.tiny(
            num_hidden_layers=1)).eval(), num_slots=2,
        max_length=MAX_LEN, decode_block=BLOCK, buckets=[BUCKET])
    other.submit([5, 6, 7], H.greedy(5))
    other.run()
    last = H.rounds(log)[-1]
    assert 'residual_streams' not in last and 'latent_layers' in last


# bf16 expert leaves through both expert kernels, interpreted (PR 49)
test_the_expert_kernels_serve_the_loops_tokens = \
    H.expert_kernel_serves_the_loops_tokens(FAM)
