"""`nlp/lfm2.py` served: the engine's own prefill program and the
hand-off of a state, continuous batching over reseated slots, both
decode programs, the decode and expert kernels interpreted, what an
engine refuses a state, and what a decode round's span and the pool's
book carry — against the plain float32 reference. The family, its
tolerance and its reason are `tests/test_lfm2.py`'s, the shared cases
`tests/family_harness.py`'s (a file of its own so that no worker of the
suite carries both)."""
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import generation, lfm2

import family_harness as H
from family_harness import BLOCK, BUCKET
from test_lfm2 import FAM

built, tiny = H.fixtures(FAM)
LENGTHS = (1, 2, 3, BUCKET - 1, BUCKET)
N_NEW = 3 * BLOCK


# ---------------------------------------------------------------------------
# (d) prefill by bucket, then decode: the hand-off of a state
# ---------------------------------------------------------------------------
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, LENGTHS, N_NEW)
test_through_router_and_engine_prompts_shorter_than_their_bucket = \
    H.through_router_shorter_than_bucket(FAM, LENGTHS, N_NEW)


def _state_at_the_buckets_end(mp):
    """The prefill that does not know the prompt's length: the padding
    is folded into the state."""
    mp.setattr(lfm2, 'folded_tokens', lambda s: s)


def _last_token_twice(mp):
    """The state as of the prompt's END: the decode block's re-forward
    of the last prompt token then folds it in a second time."""
    real = generation.folded_tokens
    mp.setattr(lfm2, 'folded_tokens', lambda s: real(s) + (
        0 if generation._routing.folded is None else 1))


test_a_faulty_hand_off_fails_the_tolerance = H.faulty_hand_off(
    FAM, [(_state_at_the_buckets_end, 3), (_last_token_twice, 4)],
    LENGTHS, N_NEW)


# ---------------------------------------------------------------------------
# (e) continuous batching: more requests than slots, slots reseated
# ---------------------------------------------------------------------------
test_more_requests_than_slots_every_one_against_the_reference = \
    H.more_requests_than_slots(FAM)
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)
test_a_reseated_slot_holds_the_new_requests_state_whole = \
    H.reseated_slot(FAM, 'state_layers')


# ---------------------------------------------------------------------------
# (f) both decode programs
# ---------------------------------------------------------------------------
def _each_program_traced_once(eng, rounds):
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1


test_both_decode_programs_agree_with_the_reference = \
    H.both_decode_programs(FAM, _each_program_traced_once, num_slots=1)


# ---------------------------------------------------------------------------
# (g) what cannot share or rewind a state is refused, with its reason
# ---------------------------------------------------------------------------
test_engine_modes_that_cannot_hold_a_state_are_refused = H.modes_refused(
    FAM, 'recurrent slot state', 'no rows to page')
test_a_draft_model_with_a_state_is_refused_too = H.as_a_draft_refused(
    FAM, 'Lfm2MoeForCausalLM keeps recurrent')


# ---------------------------------------------------------------------------
# (i) what a decode round's span carries
# ---------------------------------------------------------------------------
def test_decode_round_carries_state_and_counts_one_attention_layer(tiny):
    cfg, _, model = tiny
    log = H.cleared_log()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_slot_state_bytes_total')
    H.through_the_router(model, H.prompts((5, 19, 11)), 14)
    rounds = H.rounds(log)
    assert rounds
    leaf = 3 * 32 * 4                   # conv_L_cache x hidden x float32
    for a in rounds:
        assert (a['attn_layers'], a['state_layers']) == (1, 4)
        assert a['state_bytes'] == a['active'] * 4 * leaf * 2 * BLOCK
        assert a['rows'] in (32, 64)
        # ONE attention layer of the five: rows of one layer only
        assert a['read_rows'] == 2 * a['rows']
        assert 0 < a['needed_rows'] <= a['real_rows'] + a['active']
        assert a['expert_layer_substeps'] == BLOCK * 4
        assert a['experts'] == cfg['num_experts']
    assert reg.value('paddle_serving_slot_state_bytes_total') - before \
        == sum(a['state_bytes'] for a in rounds)


def _the_one_attention_layer_is_bounded(cfg, eng, rounds, calls):
    """4 query heads a KV head. On the ONE attention layer the decoding
    slot's length rounded up to the tile, and one tile of the slot that
    is not decoding; a conv layer reads no row."""
    assert eng._bounded_tiles(64).tolist() == [16]
    assert eng._bounded_tiles(32).tolist() == [16]
    assert len(calls) == 2                  # a call a program, traced
    walked = set()
    for a in rounds:
        tiles = -(-a['needed_rows'] // 16)
        walked.add(tiles)
        assert a['read_rows'] == tiles * 16 + 16
        assert a['needed_rows'] <= a['read_rows'] <= 2 * a['rows']
    assert walked == {1, 2, 3, 4}


test_decode_through_the_kernel_agrees_with_the_reference = \
    H.decode_through_the_kernel(FAM, _the_one_attention_layer_is_bounded)


# (four picks of eight experts, a state beside K and V)
test_the_expert_kernel_serves_the_loops_tokens = \
    H.expert_kernel_serves_the_loops_tokens(FAM)


def test_a_model_without_state_carries_none_of_it():
    eng, a, _ = H.llama_round()
    assert not {'attn_layers', 'state_layers', 'state_bytes'} & set(a)
    assert eng.pool.stats()['state_layers'] == 0


def test_pool_books_state_apart_from_rows(tiny):
    _, _, model = tiny
    eng = H.engine(model)
    pool = eng.pool
    assert pool.state_layers == (0, 2, 3, 4)
    assert pool.state_bytes == 4 * 3 * 32 * 4
    kv = 2 * 64 * 2 * 8 * 4             # K and V, 64 rows, 2 heads x 8
    assert pool.row_bytes == kv + pool.state_bytes
    assert pool.stats()['state_bytes'] == pool.state_bytes
    # (booked by entry whatever leaves an entry of state is made of: PR 43)
    assert pool.stats()['entry_bytes']['state'] == 2 * pool.state_bytes
    assert pool.stats()['entry_layouts']['state'] == 'default'
    assert list(eng._layer_rows) == [64]
    # a bf16 pool keeps its state leaves float32
    half = H.engine(model, dtype='bfloat16').pool.rows
    assert half[1][0].dtype == jnp.bfloat16 and half[0].dtype == jnp.float32


def test_conv_scopes_are_on_the_decode_and_prefill_programs(tiny):
    _, _, model = tiny
    H.through_the_router(model, H.prompts((5,)), 6)
    # (a prefill returns rows and state, no logits: no `lm_head` there)
    for prog, more in (('serving.decode_block', {'lm_head', 'sample'}),
                       (f'serving.prefill_{BUCKET}', set())):
        found = H.scopes_found(prog)
        assert {'conv', 'state_write', 'attention', 'kv_write', 'mlp',
                'moe/router', 'moe/experts', 'norm'} | more <= found
        assert 'moe/shared' not in found
    assert programs.scope_path(
        'jit(f)/while/body/conv/state_write/dynamic_slice') \
        == ('conv', 'state_write')
