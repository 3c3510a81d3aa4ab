"""`nlp/mimo_v2.py` served: the engine's own prefill program and the
hand-off of a ring, continuous batching over reseated slots, both decode
programs, the decode kernel interpreted, what an engine refuses a ring,
and what a decode round's span and the pool's book carry — against the
plain float32 reference. The family, its tolerance and its reason are
`tests/test_mimo_v2.py`'s, the shared cases `tests/family_harness.py`'s
(a file of its own so that no worker of the suite carries both)."""
import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import generation, mimo_v2
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.serving import InferenceEngine

import family_harness as H
from family_harness import BLOCK, BUCKET
from test_mimo_v2 import FAM, WINDOW

built, tiny = H.fixtures(FAM)
LENGTHS = (1, 2, WINDOW - 1, WINDOW, WINDOW + 1, BUCKET - 1, BUCKET)
N_NEW = 3 * WINDOW + 2      # the ring wraps three times


# ---------------------------------------------------------------------------
# (e) prefill by bucket, then decode: the hand-off of a ring
# ---------------------------------------------------------------------------
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, LENGTHS, N_NEW)
test_through_router_and_engine_prompts_shorter_than_their_bucket = \
    H.through_router_shorter_than_bucket(FAM, LENGTHS, N_NEW)


def test_the_reforward_of_the_last_prompt_token_rewrites_its_row_alike(tiny):
    """The prefill seats the ring as of token `s - 2`; the decode
    block's re-forward of token `s - 1` writes row `(s - 1) mod window`.
    A prefill that had written that token too would have put the SAME
    values there (to the rounding of a forward of one token against a
    forward of sixteen: observed 2.3e-6 on values of 3): harmless,
    unlike a state's second fold."""
    eng, prefill, fwd = H.programs_of(tiny[2])
    state = (eng._params, eng._frozen, eng._buffers)
    ids = jnp.asarray(H.ids((1, BUCKET), 2))
    upto = {n: prefill(*state, ids, jnp.int32(n)) for n in (9, 10)}
    pos = jnp.full((1,), 8, jnp.int32)
    mask = (jnp.arange(64)[None, :] <= pos[:, None])[:, None, None, :]
    for n in (9, 10):       # the ring with and without token 8 in it
        _, after = fwd(ids[:, 8:9], upto[n], pos, pos, mask)
        for i in eng.pool.ring_layers:
            for got, want in zip(after[i], upto[10][i]):
                assert np.abs(np.asarray(got - want)).max() < 1e-5
    ring = eng.pool.ring_layers[0]
    assert np.abs(np.asarray(upto[9][ring][0]
                             - upto[10][ring][0])).max() > 0.1


def _ring_at_the_buckets_end(mp):
    """The prefill that does not know the prompt's length: the padding
    is written into the ring, over rows the window still needs."""
    mp.setattr(generation, 'folded_tokens', lambda s: s)


def _ring_never_written_by_prefill(mp):
    mp.setattr(generation, 'folded_tokens', lambda s: 0 if s > 1 else 1)


test_a_faulty_hand_off_fails_the_tolerance = H.faulty_hand_off(
    FAM, [(_ring_at_the_buckets_end, 3), (_ring_never_written_by_prefill, 4)],
    LENGTHS, N_NEW)


# ---------------------------------------------------------------------------
# (f) continuous batching: more requests than slots, slots reseated
# ---------------------------------------------------------------------------
test_more_requests_than_slots_every_one_against_the_reference = \
    H.more_requests_than_slots(FAM)
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)
test_a_reseated_slot_holds_the_new_requests_ring_whole = \
    H.reseated_slot(FAM, 'ring_layers')


# ---------------------------------------------------------------------------
# (g) both decode programs
# ---------------------------------------------------------------------------
def _a_ring_is_read_whole_by_both(eng, rounds):
    n_ring = len(eng.pool.ring_layers)
    n_full = len(eng.pool.row_spec) - n_ring
    for a in rounds:        # one slot: a ring is WINDOW rows whichever
        assert a['read_rows'] == n_full * a['rows'] + n_ring * WINDOW
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1


test_both_decode_programs_agree_with_the_reference = \
    H.both_decode_programs(FAM, _a_ring_is_read_whole_by_both, num_slots=1)


def _full_layers_without_a_sink_are_bounded(cfg, eng, rounds, calls):
    """K wider than V. On a full layer WITHOUT a sink the decoding
    slot's length rounded up to the tile and one tile of the slot that
    is not decoding; a full layer with a sink (`tiny_window_first`) and
    every ring keep XLA and are read whole."""
    sinks = cfg['add_full_attention_sink_bias']
    layers = range(len(eng.pool.row_spec))
    full = [i for i in layers if i not in eng.pool.ring_layers]
    want = [0 if sinks or i in eng.pool.ring_layers else 16 for i in layers]
    assert eng._bounded_tiles(64).tolist() == want
    assert eng._bounded_tiles(32).tolist() == want
    assert len(calls) == (0 if sinks else 2 * len(full))
    rings = 2 * len(eng.pool.ring_layers) * WINDOW
    for a in rounds:
        if sinks:
            assert a['read_rows'] == 2 * len(full) * a['rows'] + rings
            continue
        length = (a['needed_rows'] - a['needed_rows_window']) // len(full)
        assert a['read_rows'] \
            == len(full) * (-(-length // 16) * 16 + 16) + rings
        assert a['needed_rows'] <= a['read_rows']


test_decode_through_the_kernel_agrees_with_the_reference = \
    H.decode_through_the_kernel(FAM, _full_layers_without_a_sink_are_bounded)


def test_attended_rows_leaves_a_ring_whole(tiny):
    """The half program's mask has 32 columns: a full layer's leaves are
    sliced to it, a ring's 4 rows are not its business."""
    sliced = H.attended_rows_under_the_half_mask(tiny[2], mimo_v2)
    assert [(rows, {leaf[1] for leaf in out}) for rows, out in sliced] \
        == [(64, {32})] * 2                     # the two FULL layers only


# ---------------------------------------------------------------------------
# (h) what cannot share or rewind a ring is refused, with its reason
# ---------------------------------------------------------------------------
test_engine_modes_that_cannot_hold_a_ring_are_refused = H.modes_refused(
    FAM, 'MiMoV2ForCausalLM keeps a ring of a window', 'one page geometry')
test_a_draft_model_with_a_ring_is_refused_too = H.as_a_draft_refused(
    FAM, 'MiMoV2ForCausalLM keeps a ring')


def test_a_window_as_long_as_the_slot_is_no_ring():
    """`init_cache` gives a window layer min(window, max_length) rows: at
    max_length 4 nothing is shorter than the slot, nothing is a ring,
    and the plain prefill serves it."""
    paddle.seed(1)
    model = MiMoV2ForCausalLM(MiMoV2Config.tiny()).eval()
    eng = InferenceEngine(model, num_slots=1, max_length=WINDOW,
                          decode_block=1, buckets=[2])
    assert eng.pool.ring_layers == () and not eng.pool.stands_at_one_position
    assert generation.ring_layers(model.init_cache(1, 64), 64) == (1, 2)


# ---------------------------------------------------------------------------
# (j) what a decode round's span carries
# ---------------------------------------------------------------------------
def test_decode_round_carries_the_ring_and_the_share(tiny):
    cfg, _, model = tiny
    log = H.cleared_log()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_moe_picks_held_total')
    _, eng = H.through_the_router(model, H.prompts((5, 19, 11)), 14)
    rounds = H.rounds(log)
    assert rounds
    for a in rounds:
        # two full layers at the round's rows, two rings of 4, two slots
        assert a['read_rows'] == 2 * (2 * a['rows'] + 2 * WINDOW)
        # a ring entry needs at most the window of a slot
        assert 0 < a['needed_rows_window'] <= a['active'] * 2 * WINDOW
        assert a['needed_rows_window'] < a['needed_rows'] \
            <= 2 * a['real_rows'] + 4 * a['active'] + a['needed_rows_window']
        assert a['expert_layer_substeps'] == BLOCK * 3
        assert a['experts'] == 4            # the experts HELD a layer
        assert a['experts_touched'] <= BLOCK * 3 * 4
        # 2 picks a token on each of 3 expert layers in each sub-step
        assert a['picks'] == a['active'] * 2 * 3 * BLOCK
        assert 0 <= a['picks_held'] <= a['picks']
    held, made = (sum(a[k] for a in rounds) for k in ('picks_held', 'picks'))
    assert 0.05 < held / made < 0.6         # 4 of 16, give or take the bias
    assert reg.value('paddle_serving_moe_picks_held_total') - before == held
    late = [a for a in rounds if a['real_rows'] >= a['active'] * 2 * WINDOW]
    assert late and all(a['needed_rows_window'] == a['active'] * 2 * WINDOW
                        for a in late)


def test_a_model_that_holds_every_expert_carries_no_picks(built):
    cfg, _, model = built
    log = H.cleared_log()
    H.through_the_router(model, H.prompts((5,)), 6)
    share = cfg['n_routed_experts'] < cfg['expert_share']['routed']
    for a in H.rounds(log):
        assert ('picks' in a) == ('picks_held' in a) == share
        assert 'needed_rows_window' in a


def test_a_model_with_one_geometry_carries_what_it_carried():
    eng, a, _ = H.llama_round()
    assert not {'needed_rows_window', 'picks', 'picks_held'} & set(a)
    assert a['read_rows'] == 2 * a['rows'] * len(eng.pool.row_spec)
    stats = eng.pool.stats()
    assert stats['ring_layers'] == 0 and len(stats['entry_bytes']) == 1


def test_pool_books_bytes_by_entry_geometry(tiny):
    _, _, model = tiny
    pool = H.engine(model).pool
    assert pool.ring_layers == (1, 2) and pool.state_layers == ()
    full = 2 * 64 * 1 * (12 + 8) * 4        # slots x rows x heads x (K + V)
    ring = 2 * WINDOW * 2 * (12 + 8) * 4
    assert pool.stats()['entry_bytes'] == {'64x1x(12+8)': 2 * full,
                                           '4x2x(12+8)': 2 * ring}
    assert pool.pool_bytes == 2 * full + 2 * ring
    assert pool.rows[1][0].shape == (2, WINDOW, 2, 12)
    assert pool.rows[1][1].shape == (2, WINDOW, 2, 8)
    assert pool.rows[0][0].shape == (2, 64, 1, 12)


def test_the_pool_of_the_timed_size():
    """32 slots x 4096 at the published widths: 128 rows x 8 heads on a
    window layer, 4096 x 4 on a full one, K 192 and V 128 wide."""
    conf = MiMoV2Config(num_hidden_layers=7, vocab_size=64,
                        hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0],
                        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1])
    with paddle.LazyGuard():
        model = MiMoV2ForCausalLM(conf)
    cache = jax.eval_shape(lambda: model.init_cache(32, 4096, 'float32'))
    assert [(k.shape, v.shape) for k, v in cache[:2]] == [
        ((32, 4096, 4, 192), (32, 4096, 4, 128)),
        ((32, 128, 8, 192), (32, 128, 8, 128))]
    assert sum(leaf.size * 4 for entry in cache for leaf in entry) \
        == 1_551_892_480
    assert generation.ring_layers(cache, 4096) == (1, 2, 3, 4, 5)


def test_scopes_are_on_the_decode_and_prefill_programs(tiny):
    _, _, model = tiny
    H.through_the_router(model, H.prompts((5,)), 6)
    table = programs.scope_table()
    for prog, more in (('serving.decode_block', {'lm_head', 'sample'}),
                       (f'serving.prefill_{BUCKET}', set())):
        found = H.scopes_found(prog)
        assert {'attention', 'kv_write', 'mlp', 'moe/router', 'moe/experts',
                'norm'} | more <= found
        assert 'moe/shared' not in found
        # the ring's write is under `kv_write`: its `p mod rows`
        assert any('kv_write' in programs.scope_path(op)
                   and op.endswith('/rem')
                   for op, *_ in table[prog].values())


# bf16 expert leaves through both expert kernels, interpreted (PR 49)
test_the_expert_kernels_serve_the_loops_tokens = \
    H.expert_kernel_serves_the_loops_tokens(FAM)
