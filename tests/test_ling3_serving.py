"""`nlp/ling3.py` served: the engine's own prefill program and the
hand-off of a state entry of two leaves beside a latent entry,
continuous batching over reseated slots, both decode programs, what an
engine refuses a state AND a latent entry, what a decode round's
span, a prefill's span and the pool's book carry, and the decode block
with the one-token recurrence as the kernel (PR 44) — against the plain
float32 reference. The family, its tolerance and its reason are
`tests/test_ling3.py`'s, the shared cases `tests/family_harness.py`'s (a
file of its own so that no worker of the suite carries both)."""
import copy

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import generation, ling3

import family_harness as H
from family_harness import BLOCK, BUCKET, MAX_LEN
from test_ling3 import FAM as BOTH
from test_ling3 import chunks_of_sixteen_tokens  # noqa: F401  (autouse)

# the cases that build an engine run on the first preset (a share held,
# chunks of 16); the other goes once through the router: the model-level
# cases of `tests/test_ling3.py` hold both
FAM = copy.copy(BOTH)
FAM.presets = BOTH.presets[:1]
built, tiny = H.fixtures(FAM)
LENGTHS = (1, 2, 3, BUCKET - 1, BUCKET, BUCKET + 9)
N_NEW = 3 * BLOCK

# one KDA layer's entry at the tiny preset: 4 heads x 8 x 8 float32 and
# the convolutions' last 3 inputs of 3 x 32 channels
STATE_LEAF, CONV_LEAF = 4 * 8 * 8 * 4, 3 * 96 * 4


# ---------------------------------------------------------------------------
# (a) prefill by bucket (one chunk, two chunks), then decode: the hand-off
# ---------------------------------------------------------------------------
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, LENGTHS, N_NEW)
test_through_router_and_engine_prompts_shorter_than_their_bucket = \
    H.through_router_shorter_than_bucket(FAM, LENGTHS, N_NEW)


def _state_at_the_buckets_end(mp):
    """The prefill that does not know the prompt's length: the padding
    is folded into the state."""
    mp.setattr(ling3, 'folded_tokens', lambda s: s)


def _last_token_twice(mp):
    """The state as of the prompt's END: the decode block's re-forward
    of the last prompt token then folds it in a second time."""
    real = generation.folded_tokens
    mp.setattr(ling3, 'folded_tokens', lambda s: real(s) + (
        0 if generation._routing.folded is None else 1))


def test_the_other_preset_through_the_router():
    """Latent first, two dense layers, every expert held."""
    cfg, w, model = BOTH.build('tiny_latent_first')
    served = H.prompts((3, BUCKET + 5), seed=4)
    log = H.cleared_log()
    toks, eng = H.through_the_router(model, served, N_NEW)
    H.within_tol(BOTH, cfg, w, served, toks)
    assert eng.pool.state_layers == (1, 2) and eng.pool.latent_layers == (0,)
    assert all('picks' not in a for a in H.rounds(log))
    assert [e['attrs']['kda_chunks'] for e in log.events()
            if e['name'] == 'serving.prefill'] == [1, 2]


test_a_faulty_hand_off_fails_the_tolerance = H.faulty_hand_off(
    FAM, [(_state_at_the_buckets_end, 3), (_last_token_twice, 4)],
    LENGTHS, N_NEW)


# ---------------------------------------------------------------------------
# (b) continuous batching: more requests than slots, slots reseated
# ---------------------------------------------------------------------------
test_more_requests_than_slots_every_one_against_the_reference = \
    H.more_requests_than_slots(FAM)
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)
test_a_reseated_slot_holds_the_new_requests_state_whole = \
    H.reseated_slot(FAM, 'state_layers')


# ---------------------------------------------------------------------------
# (c) both decode programs
# ---------------------------------------------------------------------------
def _each_program_traced_once(eng, rounds):
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1
    for a in rounds:        # ONE latent layer: its rows alone are read
        assert a['read_rows'] == a['rows']


test_both_decode_programs_agree_with_the_reference = \
    H.both_decode_programs(FAM, _each_program_traced_once, num_slots=1)


# ---------------------------------------------------------------------------
# (d) a state AND a latent entry: both lists of refusals apply
# ---------------------------------------------------------------------------
test_engine_modes_that_cannot_hold_a_state_are_refused = H.modes_refused(
    FAM, 'recurrent slot state', 'no rows to page')
test_a_draft_model_with_a_state_is_refused_too = H.as_a_draft_refused(
    FAM, 'Ling3ForCausalLM keeps recurrent')


def test_the_latent_entry_alone_would_be_refused_pages_too(tiny):
    """The state's refusal comes first; without a state layer the same
    model is refused by what reasons BY HEAD."""
    from paddle_tpu.serving import engine as E
    _, _, model = tiny
    entries = jax.eval_shape(lambda: model.init_cache(1, MAX_LEN))
    assert generation.state_layers(entries) == (0, 1)
    assert generation.latent_layers(entries) == (2,)
    assert generation.ring_layers(entries, MAX_LEN) == ()
    with pytest.raises(ValueError, match='latent rows.*no head axis'):
        E._refuse_modes(model, {'kv_page_size / kv_pages': True},
                        'latent rows (cache entries with rows and no '
                        'heads)', E._LATENT_REFUSALS)


# ---------------------------------------------------------------------------
# (e) what a decode round's span, a prefill's span and the pool carry
# ---------------------------------------------------------------------------
def test_decode_round_carries_state_latent_and_held_share_counts(tiny):
    """Experts 4-11 of 16 — routing groups 1 and 2 whole — are held:
    the round says what the active slots picked and what landed here."""
    cfg, _, model = tiny
    assert cfg['expert_share'] == {'routed': 16, 'first': 4}
    log = H.cleared_log()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_slot_state_bytes_total')
    H.through_the_router(model, H.prompts((5, 19, 11)), 14)
    rounds = H.rounds(log)
    assert rounds
    for a in rounds:
        assert (a['attn_layers'], a['state_layers']) == (1, 2)
        # on the CPU `kda_step` runs both recurrences: none is the kernel
        assert a['state_kernel_layers'] == 0
        # BOTH leaves of every KDA layer's entry, read and written
        assert a['state_bytes'] == a['active'] * 2 \
            * (STATE_LEAF + CONV_LEAF) * 2 * BLOCK
        assert a['latent_layers'] == 1
        assert a['latent_row_bytes'] == (16 + 4) * 4
        assert a['rows'] in (32, 64) and a['read_rows'] == 2 * a['rows']
        assert 0 < a['needed_rows'] <= a['real_rows'] + a['active']
        assert a['expert_layer_substeps'] == BLOCK * 2
        assert a['experts'] == cfg['num_experts'] == 8
        assert a['picks'] == a['active'] * 3 * 2 * BLOCK
        assert 0 <= a['picks_held'] <= a['picks']
    assert sum(a['picks_held'] for a in rounds) > 0
    assert reg.value('paddle_serving_slot_state_bytes_total') - before \
        == sum(a['state_bytes'] for a in rounds)
    prefills = [e['attrs'] for e in log.events()
                if e['name'] == 'serving.prefill']
    # chunks of 16 tokens scanned a KDA layer, beside the latent layer's
    # pairs
    assert [(a['bucket'], a['kda_chunks']) for a in prefills] \
        == [(16, 1), (32, 2), (16, 1)]
    assert all(a['attn_pairs_scored'] == a['bucket'] ** 2 for a in prefills)


def test_pool_books_a_state_of_two_leaves_beside_latent_rows(tiny):
    _, _, model = tiny
    eng = H.engine(model)
    pool = eng.pool
    assert pool.state_layers == (0, 1) and pool.latent_layers == (2,)
    assert pool.stands_at_one_position and pool.ring_layers == ()
    assert pool.state_bytes == 2 * (STATE_LEAF + CONV_LEAF)
    latent = MAX_LEN * (16 + 4) * 4
    assert pool.row_bytes == latent + pool.state_bytes
    assert pool.latent_row_bytes == (16 + 4) * 4
    stats = pool.stats()
    assert stats['state_bytes'] == pool.state_bytes
    assert stats['entry_bytes'] == {
        'state': 2 * pool.state_bytes,
        f'{MAX_LEN}xlatent(16+4)': 2 * latent}
    assert stats['entry_layouts'] == {
        'state': 'default', f'{MAX_LEN}xlatent(16+4)': 'default'}
    assert list(eng._layer_rows) == [64]
    # four leaves of state and two of rows; a state leaf never asks a
    # layout of its own
    assert len(pool.formats) == 6 and pool.asks('tpu')[:4] == [None] * 4
    # a bf16 pool keeps its state leaves float32
    half = H.engine(model, dtype='bfloat16').pool.rows
    assert half[2][0].dtype == jnp.bfloat16
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(half[0])} \
        == {jnp.dtype('float32')}
    # seating, slicing and copying a slot map over every leaf
    row = pool.row(1)
    assert tuple(row[0]['S'].shape) == (1, 4, 8, 8)
    assert tuple(row[0]['conv'].shape) == (1, 3, 96)
    assert tuple(row[2][0].shape) == (1, MAX_LEN, 16)
    pool.set_row(0, jax.tree_util.tree_map(lambda x: x + 1.0, row))
    pool.copy_slot(0, 1)
    for leaf in jax.tree_util.tree_leaves(pool.rows):
        assert float(jnp.abs(leaf[0] - leaf[1]).max()) == 0.0
        assert float(leaf[1].min()) == 1.0


def test_through_the_kernel_the_round_says_so_and_the_tokens_hold(
        tiny, kda_interpreted):
    """The decode block with both KDA layers' recurrences as the
    kernel: the served tokens are the reference's, and every round says
    2 of its 2 state layers ran it."""
    cfg, w, model = tiny
    served = H.prompts((5, 19, 11))
    log = H.cleared_log()
    toks, eng = H.through_the_router(model, served, 14)
    H.within_tol(FAM, cfg, w, served, toks)
    # asked by the engine for its count, then by each decode program as
    # it is traced: every slot's leaf, never a prefill's
    assert set(kda_interpreted) == {(2, 4, 8, 8)}
    rounds = H.rounds(log)
    assert rounds and all(
        (a['state_layers'], a['state_kernel_layers']) == (2, 2)
        for a in rounds)


@pytest.mark.parametrize('kernel', [False, True])
def test_kda_scopes_are_on_the_decode_and_prefill_programs(
        tiny, kernel, request):
    if kernel:
        request.getfixturevalue('kda_interpreted')
    _, _, model = tiny
    H.through_the_router(model, H.prompts((5,)), 6)
    # (a prefill returns rows and state, no logits: no `lm_head` there,
    # and it never takes the absorbed path)
    for prog, more in (('serving.decode_block',
                        {'lm_head', 'sample', 'latent_absorb'}),
                       (f'serving.prefill_{BUCKET}', set())):
        table = programs.scope_table()[prog]
        paths = [programs.scope_path(op) for op, *_ in table.values()]
        found = {s for p in paths for s in p}
        assert {'kda', 'state_write', 'attention', 'kv_write', 'mlp',
                'moe/router', 'moe/experts', 'moe/shared', 'norm'} | more \
            <= found
        # the state's update lies inside `kda`, the rows' write inside
        # `attention`
        assert all(p[0] == 'kda' for p in paths if 'state_write' in p)
        assert all(p[0] == 'attention' for p in paths if 'kv_write' in p)
        kernels = [p for (op, *_), p in zip(table.values(), paths)
                   if 'kda_decode_step' in op]
        # the kernel (interpreted: many ops under its name) is the
        # decode block's alone, under `kda` with the state's write
        assert bool(kernels) == (kernel and 'decode' in prog)
        assert all(p == ('kda', 'state_write') for p in kernels)
    assert programs.scope_path(
        'jit(f)/while/body/kda/state_write/add') == ('kda', 'state_write')


# bf16 expert leaves through both expert kernels, interpreted (PR 49)
test_the_expert_kernels_serve_the_loops_tokens = \
    H.expert_kernel_serves_the_loops_tokens(FAM)
