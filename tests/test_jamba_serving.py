"""`nlp/jamba.py` served: the engine's own prefill program and the
hand-off of a state entry of two leaves beside K and V by head,
continuous batching over reseated slots, both decode programs, the
decode kernel interpreted over twenty-to-one (here four-to-one) query
heads on ONE K,V head, what an engine refuses a state, and what a decode
round's span, a prefill's span and the pool's book carry — against the
plain float32 reference. The family, its tolerance and its reason are
`tests/test_jamba.py`'s, the shared cases `tests/family_harness.py`'s (a
file of its own so that no worker of the suite carries both)."""
import copy

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import generation, jamba
from paddle_tpu.nlp.jamba import JambaConfig, JambaForCausalLM

import family_harness as H
from family_harness import BLOCK, BUCKET, MAX_LEN
from test_jamba import FAM as BOTH
from test_jamba import chunks_of_sixteen_tokens  # noqa: F401  (autouse)

# the cases that build an engine run on the first preset (one K,V head,
# the attention layer second of five); the other goes once through the
# router: the model-level cases of `tests/test_jamba.py` hold both
FAM = copy.copy(BOTH)
FAM.presets = BOTH.presets[:1]
built, tiny = H.fixtures(FAM)
LENGTHS = (1, 3, BUCKET - 1, BUCKET, BUCKET + 9)
N_NEW = 3 * BLOCK

# one Mamba layer's entry at the tiny preset: 8 states x 64 channels
# float32 and the convolution's last 3 inputs of 64 channels
STATE_LEAF, CONV_LEAF = 8 * 64 * 4, 3 * 64 * 4


# ---------------------------------------------------------------------------
# (a) prefill by bucket (one chunk, two chunks), then decode: the hand-off
# ---------------------------------------------------------------------------
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, LENGTHS, N_NEW)
test_through_router_and_engine_prompts_shorter_than_their_bucket = \
    H.through_router_shorter_than_bucket(FAM, LENGTHS, N_NEW)


def _state_after_the_pads(mp):
    """The prefill that does not know the prompt's length: the padding
    is folded into the state."""
    mp.setattr(jamba, 'folded_tokens', lambda s: s)


def _conv_state_one_token_late(mp):
    """`h` as of the prompt less its last token, the convolution's
    inputs as of its END: the decode block's re-forward of the last
    prompt token then convolves it with itself."""
    real = jamba.short_conv_silu
    mp.setattr(jamba, 'short_conv_silu', lambda x, w, state, folded, bias:
               real(x, w, state, folded + (x.shape[1] > 1), bias))


def test_the_other_preset_through_the_router():
    """The attention layer last, two K,V heads."""
    cfg, w, model = BOTH.build('tiny_attention_last')
    served = H.prompts((3, BUCKET + 5), seed=4)
    log = H.cleared_log()
    toks, eng = H.through_the_router(model, served, N_NEW)
    H.within_tol(BOTH, cfg, w, served, toks)
    assert eng.pool.state_layers == (0, 1) and eng.pool.latent_layers == ()
    assert [e['attrs']['ssm_chunks'] for e in log.events()
            if e['name'] == 'serving.prefill'] == [1, 2]


test_a_faulty_hand_off_fails_the_tolerance = H.faulty_hand_off(
    FAM, [(_state_after_the_pads, 3), (_conv_state_one_token_late, 4)],
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
# (c) both decode programs, and the decode kernel
# ---------------------------------------------------------------------------
def _each_program_traced_once(eng, rounds):
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1
    for a in rounds:        # ONE attention layer: its rows alone are read
        assert a['read_rows'] == a['rows']


test_both_decode_programs_agree_with_the_reference = \
    H.both_decode_programs(FAM, _each_program_traced_once, num_slots=1)


def _the_one_attention_layer_is_bounded(cfg, eng, rounds, calls):
    """Four query heads on the ONE K,V head. On the one attention layer
    the decoding slot's length rounded up to the tile, and one tile of
    the slot that is not decoding; a Mamba layer reads no row."""
    assert eng._bounded_tiles(64).tolist() == [16]
    assert eng._bounded_tiles(32).tolist() == [16]
    assert len(calls) == 2                  # a call a program, traced
    # the leaves as held: one head of 8, and four queries on it
    assert {tuple(args[1].shape[-2:]) for args in calls} == {(1, 8)}
    assert {args[0].shape[-2] for args in calls} == {4}
    walked = set()
    for a in rounds:
        tiles = -(-a['needed_rows'] // 16)
        walked.add(tiles)
        assert a['read_rows'] == tiles * 16 + 16
        assert a['needed_rows'] <= a['read_rows'] <= 2 * a['rows']
    assert walked == {1, 2, 3, 4}


test_decode_through_the_kernel_agrees_with_the_reference = \
    H.decode_through_the_kernel(FAM, _the_one_attention_layer_is_bounded)


# ---------------------------------------------------------------------------
# (d) what cannot share or rewind a state is refused, with its reason
# ---------------------------------------------------------------------------
test_engine_modes_that_cannot_hold_a_state_are_refused = H.modes_refused(
    FAM, 'recurrent slot state', 'no rows to page')
test_a_draft_model_with_a_state_is_refused_too = H.as_a_draft_refused(
    FAM, 'JambaForCausalLM keeps recurrent')


# ---------------------------------------------------------------------------
# (e) what a decode round's span, a prefill's span and the pool carry
# ---------------------------------------------------------------------------
def test_decode_round_carries_state_and_counts_one_attention_layer(tiny):
    _, _, model = tiny
    log = H.cleared_log()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_slot_state_bytes_total')
    H.through_the_router(model, H.prompts((5, 19, 11)), 14)
    rounds = H.rounds(log)
    assert rounds
    for a in rounds:
        assert (a['attn_layers'], a['state_layers']) == (1, 4)
        # BOTH leaves of every Mamba layer's entry, read and written
        assert a['state_bytes'] == a['active'] * 4 \
            * (STATE_LEAF + CONV_LEAF) * 2 * BLOCK
        assert 'state_kernel_layers' not in a       # XLA's step: no kernel
        assert 'latent_layers' not in a and 'experts' not in a
        assert a['rows'] in (32, 64)
        # ONE attention layer of the five: rows of one layer only
        assert a['read_rows'] == 2 * a['rows']
        assert 0 < a['needed_rows'] <= a['real_rows'] + a['active']
    assert reg.value('paddle_serving_slot_state_bytes_total') - before \
        == sum(a['state_bytes'] for a in rounds)
    prefills = [e['attrs'] for e in log.events()
                if e['name'] == 'serving.prefill']
    # chunks of 16 tokens scanned a Mamba layer
    assert [(a['bucket'], a['ssm_chunks']) for a in prefills] \
        == [(16, 1), (32, 2), (16, 1)]
    # ... by XLA's scan in every one of them: no kernel on the CPU
    assert [a['ssm_kernel_layers'] for a in prefills] == [0, 0, 0]
    assert all('kda_chunks' not in a for a in prefills)


@pytest.mark.parametrize('preset,widths,layers', [
    ('tiny', dict(hidden_size=64), 4),              # d_inner 128, 8 states
    ('tiny_attention_last', dict(hidden_size=128), 2),
    ('tiny', dict(), 0),                            # d_inner 64: half a lane
    ('tiny', dict(hidden_size=64, mamba_d_state=4), 0)])
def test_the_prefill_span_counts_the_layers_the_kernel_takes(
        monkeypatch, preset, widths, layers):
    """With the gate forced as `benchmarks/aot.py::force_kernels_on`
    forces it, `serving.prefill` would say every Mamba layer of a model
    whose state is whole lanes and sublanes, and no chunk of XLA's scan;
    a state of another shape stays `mamba_scan`'s, gate or no gate, and
    a call of one token is no scan at all."""
    from paddle_tpu.ops import pallas
    model = JambaForCausalLM(getattr(JambaConfig, preset)(**widths))
    assert model.scan_chunks(32) == {'ssm_chunks': 2, 'ssm_kernel_layers': 0}
    monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
    assert model.scan_chunks(32) == {
        'ssm_chunks': 0 if layers else 2, 'ssm_kernel_layers': layers}
    assert model.scan_chunks(1) == {'ssm_chunks': 1, 'ssm_kernel_layers': 0}


def test_a_model_without_such_a_scan_says_nothing_of_its_chunks():
    _, a, log = H.llama_round()
    assert 'state_bytes' not in a
    assert all('ssm_chunks' not in e['attrs']
               and 'ssm_kernel_layers' not in e['attrs']
               for e in log.events() if e['name'] == 'serving.prefill')


def test_pool_books_a_state_of_two_leaves_lanes_whole_beside_k_and_v(tiny):
    _, _, model = tiny
    eng = H.engine(model)
    pool = eng.pool
    assert pool.state_layers == (0, 2, 3, 4) and pool.latent_layers == ()
    assert pool.stands_at_one_position and pool.ring_layers == ()
    assert pool.state_bytes == 4 * (STATE_LEAF + CONV_LEAF)
    kv = 2 * MAX_LEN * 1 * 8 * 4        # K and V, 64 rows, ONE head of 8
    assert pool.row_bytes == kv + pool.state_bytes
    stats = pool.stats()
    assert stats['state_bytes'] == pool.state_bytes
    assert stats['entry_bytes'] == {
        'state': 2 * pool.state_bytes, f'{MAX_LEN}x1x(8+8)': 2 * kv}
    assert stats['entry_layouts']['state'] == 'default'
    assert list(eng._layer_rows) == [64]
    # eight leaves of state and two of rows; a state leaf never asks a
    # layout of its own: it is held lanes-whole as it is made, the
    # channels minor on both leaves (on a TPU `[.., 64, 8]` would pad
    # its 8 states to 128 lanes)
    assert len(pool.formats) == 10
    assert [a is None for a in pool.asks('tpu')] == [True, True] \
        + [False, False] + [True] * 6
    row = pool.row(1)
    assert tuple(row[0]['h'].shape) == (1, 8, 64)
    assert tuple(row[0]['conv'].shape) == (1, 3, 64)
    assert tuple(row[1][0].shape) == (1, MAX_LEN, 1, 8)
    # a bf16 pool keeps its state leaves float32
    half = H.engine(model, dtype='bfloat16').pool.rows
    assert half[1][0].dtype == jnp.bfloat16
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(half[0])} \
        == {jnp.dtype('float32')}
    # seating, slicing and copying a slot map over every leaf
    pool.set_row(0, jax.tree_util.tree_map(lambda x: x + 1.0, row))
    pool.copy_slot(0, 1)
    for leaf in jax.tree_util.tree_leaves(pool.rows):
        assert float(jnp.abs(leaf[0] - leaf[1]).max()) == 0.0
        assert float(leaf[1].min()) == 1.0


def test_the_published_widths_state_is_booked_unpadded():
    """The book at the published widths, from shapes alone: 26 entries
    of `[16, 5120]` and `[3, 5120]` float32 a slot, 9.65 MiB — not the
    67 MiB of a state held `[5120, 16]` in 128 lanes."""
    from paddle_tpu.nlp.jamba import JambaConfig, JambaForCausalLM
    import paddle_tpu as paddle
    with paddle.LazyGuard():
        model = JambaForCausalLM(JambaConfig())
    cache = jax.eval_shape(lambda: model.init_cache(2, 5120))
    state = [i for i in generation.state_layers(cache)]
    assert len(state) == 26 and len(cache) == 28
    assert {(tuple(cache[i]['h'].shape), tuple(cache[i]['conv'].shape))
            for i in state} == {((2, 16, 5120), (2, 3, 5120))}
    assert {tuple(leaf.shape) for i in (7, 21) for leaf in cache[i]} \
        == {(2, 5120, 1, 128)}
    a_slot = sum(leaf.size * 4 for i in state
                 for leaf in cache[i].values()) // 2
    assert a_slot == 10_117_120
    assert all(leaf.shape[-1] % 128 == 0
               for leaf in jax.tree_util.tree_leaves(cache))


def test_ssm_scopes_are_on_the_decode_and_prefill_programs(tiny):
    _, _, model = tiny
    H.through_the_router(model, H.prompts((5,)), 6)
    # (a prefill returns rows and state, no logits: no `lm_head` there)
    for prog, more in (('serving.decode_block', {'lm_head', 'sample'}),
                       (f'serving.prefill_{BUCKET}', set())):
        table = programs.scope_table()[prog]
        paths = [programs.scope_path(op) for op, *_ in table.values()]
        found = {s for p in paths for s in p}
        assert {'ssm', 'state_write', 'attention', 'kv_write', 'mlp',
                'norm'} | more <= found
        assert not {'conv', 'kda', 'moe/experts', 'moe/router'} & found
        # the state's update lies inside `ssm` — which is then the
        # OUTERMOST scope of every op of the mixer —, the rows' write
        # inside `attention`
        assert all(p[0] == 'ssm' for p in paths if 'state_write' in p)
        assert all(p[0] == 'attention' for p in paths if 'kv_write' in p)
        assert all(p[0] == 'ssm' for p in paths if 'ssm' in p)
    assert programs.scope_path(
        'jit(f)/while/body/ssm/state_write/mul') == ('ssm', 'state_write')
