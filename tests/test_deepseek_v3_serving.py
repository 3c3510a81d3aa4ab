"""`nlp/deepseek_v3.py` served: the engine's own prefill program and
then decode through the latent cache, through `Router` and
`InferenceEngine` at every prompt length, continuous batching, both
decode programs, the engine's modes served or refused, what a decode
round's span and the pool's book carry, what a prefill may build, and
the decode kernel interpreted — against the plain float32 reference. The
family, its tolerance and its reason are `tests/test_deepseek_v3.py`'s,
the shared cases `tests/family_harness.py`'s (a file of its own so that
no worker of the suite carries both)."""
import hashlib
import math

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import deepseek_v3
from paddle_tpu.serving import InferenceEngine

import family_harness as H
from family_harness import BLOCK, BUCKET, MAX_LEN
from test_deepseek_v3 import FAM
from test_own_tokens_attention import _shapes

built, tiny = H.fixtures(FAM)


# ---------------------------------------------------------------------------
# (a) prefill by bucket, then decode, at every position
# ---------------------------------------------------------------------------
LENGTHS = (1, 2, BUCKET - 1, BUCKET, BUCKET + 11)
N_NEW = 3 * BLOCK + 1

# (the path over a prompt's own tokens; latent rows past the prompt's end
# are garbage a mask hides)
test_prefill_program_then_decode_logits_at_every_position = \
    H.prefill_then_decode(FAM, LENGTHS, N_NEW, presets=('tiny',),
                          entry=[(1, MAX_LEN, 16), (1, MAX_LEN, 4)])


@pytest.fixture(scope='module')
def served(tiny):
    """One run through `Router(ReplicaSet(model, 1))` that several tests
    read: the prompts of (d), its events, its engine."""
    _, _, model = tiny
    log = H.cleared_log()
    prompts = H.prompts(LENGTHS)
    toks, eng = H.through_the_router(model, prompts, N_NEW)
    rounds = H.rounds(log)
    return prompts, toks, eng, rounds


def test_through_router_and_engine_every_prompt_length(tiny, served):
    cfg, w, _ = tiny
    prompts, toks, eng, _ = served
    H.within_tol(FAM, cfg, w, prompts, toks)
    assert eng._counts['prefills'] == len(LENGTHS)
    assert eng._counts['chunked_prefills'] == 0
    # the plain prefill program: a latent row is hidden by position
    assert not eng.pool.stands_at_one_position


def test_the_other_preset_through_the_router():
    cfg, w, model = FAM.build('tiny_wide_v')
    prompts = H.prompts((3, BUCKET + 5), seed=4)
    log = H.cleared_log()
    toks, _ = H.through_the_router(model, prompts, N_NEW)
    H.within_tol(FAM, cfg, w, prompts, toks)
    # a whole prefill over its own tokens says what its attention
    # computes a layer beside what a causal mask lets through (PR 41):
    # a bucket under one block of queries is scored whole
    prefills = [e['attrs'] for e in log.events()
                if e['name'] == 'serving.prefill']
    assert [(a['attn_pairs_scored'], a['attn_pairs_causal'])
            for a in prefills] == [
        (a['bucket'] ** 2, len(p) * (len(p) + 1) // 2)
        for a, p in zip(prefills, prompts)]


# ---------------------------------------------------------------------------
# (b) continuous batching; both decode programs
# ---------------------------------------------------------------------------
test_more_requests_than_slots_every_one_against_the_reference = \
    H.more_requests_than_slots(FAM)
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)


def _every_layers_rows_latent_as_k_and_v(eng, rounds):
    for a in rounds:
        assert a['read_rows'] == 2 * len(eng.pool.latent_layers) * a['rows']


test_both_decode_programs_agree_with_the_reference = \
    H.both_decode_programs(FAM, _every_layers_rows_latent_as_k_and_v)


# ---------------------------------------------------------------------------
# (c) the engine's modes: served against the reference, or refused
# ---------------------------------------------------------------------------
def test_prefix_cache_serves_latent_rows(tiny):
    """Latent rows can be shared up to a position: a retained row is
    copied (`copy_slot` maps over any leaf) and the suffix prefilled
    against it — the absorbed path."""
    cfg, w, model = tiny
    shared = H.prompts((20,), seed=6)[0]
    prompts = [shared + tail for tail in H.prompts((3, 7, 1, 9), seed=7)]
    toks, eng = H.through_the_router(model, prompts, 9, prefix_cache=True)
    H.within_tol(FAM, cfg, w, prompts, toks)
    # a hit's retained row IS the row its suffix is prefilled against
    assert eng.prefix_cache.stats()['hits'] >= 2


def test_chunked_prefill_serves_latent_rows(tiny):
    cfg, w, model = tiny
    prompts = H.prompts((27, 5, 30, 17), seed=8)
    toks, eng = H.through_the_router(model, prompts, 9,
                                     prefill_chunk_tokens=8)
    H.within_tol(FAM, cfg, w, prompts, toks)
    assert eng._counts['chunked_prefills'] == 3


def test_speculation_serves_latent_rows(tiny):
    """A verify of k+1 rows is a call against rows held, and a rejected
    draft's latent rows lie above the live position, where the mask
    hides them until they are overwritten."""
    cfg, w, model = tiny
    prompts = H.prompts((5, 19, 11), seed=9)
    toks, eng = H.through_the_router(model, prompts, 11,
                                     draft_model=H.llama(),
                                     num_draft_tokens=3)
    H.within_tol(FAM, cfg, w, prompts, toks)
    assert eng._counts['spec_rounds'] > 0
    plain, _ = H.through_the_router(model, prompts, 11)
    assert toks == plain


@pytest.mark.parametrize('extra,names', [
    (dict(kv_page_size=8), 'kv_page_size / kv_pages.*no head axis'),
    (dict(kv_pages=9), 'kv_page_size / kv_pages'),
    (dict(kv_quant='int8'), 'kv_quant.*no heads'),
])
def test_engine_modes_that_reason_by_head_are_refused(tiny, extra, names):
    _, _, model = tiny
    with pytest.raises(ValueError, match='DeepseekV3ForCausalLM keeps '
                       'latent rows.*' + names):
        H.engine(model, **extra)


def test_a_draft_model_with_latent_rows_is_refused_the_paged_pool(tiny):
    _, _, model = tiny
    with pytest.raises(ValueError, match='latent rows'):
        InferenceEngine(H.llama(), num_slots=2, max_length=MAX_LEN,
                        draft_model=model, kv_page_size=8)


# ---------------------------------------------------------------------------
# (d) what a decode round's span and the pool's book carry
# ---------------------------------------------------------------------------
def test_decode_round_carries_the_latent_counts(tiny, served):
    _, _, eng, rounds = served
    assert rounds
    for a in rounds:
        # three latent layers, (16 + 4) float32 numbers a row a layer
        assert a['latent_layers'] == 3 and a['latent_row_bytes'] == 240
        # a latent entry is a row entry: two slots, three layers
        assert a['read_rows'] == 2 * 3 * a['rows']
        assert 0 < a['needed_rows'] <= 3 * (a['real_rows'] + 2 * a['active'])
        assert a['expert_layer_substeps'] == BLOCK * 2
        assert a['experts'] == 8
        assert not {'needed_rows_window', 'state_bytes', 'picks'} & set(a)
    stats = eng.pool.stats()
    assert stats['latent_layers'] == 3 and stats['latent_row_bytes'] == 240
    assert stats['state_layers'] == stats['ring_layers'] == 0
    assert stats['entry_bytes'] == {
        f'{MAX_LEN}xlatent(16+4)': 2 * MAX_LEN * 240}
    assert stats['entry_layouts'] == {f'{MAX_LEN}xlatent(16+4)': 'default'}
    assert stats['row_bytes'] == MAX_LEN * 240


def test_the_gauge_reads_the_newest_engines_latent_row_bytes(tiny):
    _, _, model = tiny
    reg = obs.get_registry()
    H.engine(model)
    assert reg.value('paddle_serving_pool_latent_row_bytes') == 240
    InferenceEngine(H.llama(), num_slots=2, max_length=64)
    assert reg.value('paddle_serving_pool_latent_row_bytes') == 0


def test_a_model_without_a_latent_entry_carries_what_it_carried():
    eng, a, log = H.llama_round()
    assert not {'latent_layers', 'latent_row_bytes'} & set(a)
    assert [set(e['attrs']) for e in log.events()
            if e['name'] == 'serving.prefill'] == [
        {'request_id', 'bucket', 'slot', 'prompt_len'}]
    stats = eng.pool.stats()
    assert stats['latent_layers'] == 0 and stats['latent_row_bytes'] == 0


# ---------------------------------------------------------------------------
# (e) what a prefill may build; the scopes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('as_draft', [False, True],
                         ids=['the_model', 'a_latent_draft'])
def test_a_prefill_scores_a_block_of_queries_against_the_keys_up_to_its_end(
        tiny, monkeypatch, as_draft):
    """max_length 256, bucket 48, blocks of 16 queries: the three
    differ (and differ from the hidden size, 64). Block `i` of the
    prefill is scored against the keys up to its own last row, `(i + 1)
    x 16` of them (PR 41), so the largest array with keys in its last
    axis is heads x block x bucket, the last block's; nothing is bucket
    x max_length (the absorbed path over the slab) nor bucket x bucket
    (the own-tokens path unblocked). So for the draft's whole prefill,
    where the draft keeps latent rows: every whole prefill has one body
    (`engine._whole_prefill`)."""
    _, _, model = tiny
    ids = jnp.zeros((1, 48), jnp.int32)
    if as_draft:
        eng = H.engine(H.llama(), max_length=256, buckets=[48],
                       draft_model=model, num_draft_tokens=2)
        assert eng.draft_pool.latent_layers
        prefill, state = eng._draft_prefill_fn, eng._draft_state
    else:
        eng = H.engine(model, max_length=256, buckets=[48])
        prefill = eng._prefill_fn
        state = (eng._params, eng._frozen, eng._buffers)

    def shapes(block):
        monkeypatch.setattr(deepseek_v3, 'PREFILL_QUERY_BLOCK', block)
        # a function of its own each time: jax remembers a trace by the
        # function traced, and the block size is no argument of it
        return _shapes(jax.make_jaxpr(lambda *args: prefill(*args))(
            *state, ids).jaxpr, [])
    blocked = shapes(16)
    scores = [s for s in blocked
              if len(s) == 4 and s[-1] in (16, 32, 48, 256)]
    assert {(1, 4, 16, 16), (1, 4, 16, 32), (1, 4, 16, 48)} <= set(scores)
    assert max(math.prod(s) for s in scores) == 4 * 16 * 48
    assert not [s for s in blocked
                if len(s) >= 4 and s[-2:] in ((48, 256), (48, 48))]
    # unblocked, the same walk does find bucket x bucket
    assert (1, 4, 48, 48) in shapes(48)


def test_scopes_are_on_the_decode_and_prefill_programs(served):
    table = programs.scope_table()
    for prog, more in (('serving.decode_block',
                        {'lm_head', 'sample', 'latent_absorb'}),
                       (f'serving.prefill_{BUCKET}', set())):
        paths = [programs.scope_path(op) for op, *_ in table[prog].values()]
        found = {s for p in paths for s in p}
        assert {'attention', 'kv_write', 'mlp', 'moe/router', 'moe/experts',
                'moe/shared', 'norm'} | more <= found
        # nested: the OUTERMOST scope of the absorbed products and of
        # the rows' write stays `attention`
        for inner in ('latent_absorb', 'kv_write'):
            assert all(p[0] == 'attention' for p in paths if inner in p)
    # a whole prefill never takes the absorbed path
    assert 'latent_absorb' not in {
        s for op, *_ in table[f'serving.prefill_{BUCKET}'].values()
        for s in programs.scope_path(op)}


# ---------------------------------------------------------------------------
# (f) decode attention through the kernel (PR 38): the engine with it
# interpreted
# ---------------------------------------------------------------------------
# sha256 (first 16 hex digits) of the StableHLO text of this family's own
# programs at the tiny presets (2 slots x 64, block 4, bucket 16), taken
# on the PARENT of PR 38 (commit 90423b8) by `_own_program_texts` below;
# the four decode blocks' re-taken AT PR 45, which handed every decode
# program the tokens of the block before and one more flag a slot (one
# `where` outside the scan, `tests/test_family_programs.py`)
_PARENT_OWN_PROGRAMS = {
    ('tiny', 'decode'): '4891dea3bdafb776',
    ('tiny', 'decode_half'): '0f4381c8005777c6',
    ('tiny', 'prefill'): 'feba32510e9fd5d1',
    ('tiny', 'chunk'): '338c89c25e5b4dce',
    ('tiny_wide_v', 'decode'): 'ac6607434d99a455',
    ('tiny_wide_v', 'decode_half'): 'f7d46387584dc6b7',
    ('tiny_wide_v', 'prefill'): '683ac209cf3b7b56',
    ('tiny_wide_v', 'chunk'): '0af2fdcdd9919cab',
}


def _own_program_texts(eng):
    texts = H.program_texts(eng)
    row = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, v.dtype),
                                 eng.pool.row_spec)
    texts['chunk'] = jax.jit(eng._chunk_prefill_fn).lower(
        eng._params, eng._frozen, eng._buffers, row,
        jnp.zeros((1, 16), jnp.int32), jnp.int32(3))
    return {name: hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
            for name, lowered in texts.items()}


def test_on_the_cpu_this_familys_programs_are_the_parents_too(built):
    """Where the kernel does not take the call — here the CPU — every
    program is the parent's, byte for byte: both decode blocks, the
    whole prefill, a chunk against rows held."""
    cfg, _, model = built
    preset = 'tiny' if cfg['v_head_dim'] == 8 else 'tiny_wide_v'
    for name, digest in _own_program_texts(H.engine(model)).items():
        assert digest == _PARENT_OWN_PROGRAMS[preset, name], name


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel wherever its conditions hold but the backend's:
    `interpret=True` for the model's dispatch and the engine's count."""
    import functools
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(pallas, 'latent_decode_kernel', functools.partial(
        pallas.latent_decode_kernel, interpret=True))


WIDE_LEN = 768      # whole program: 3 tiles of 256; half: 3 of 128


@pytest.fixture(scope='module')
def wide():
    """`tiny` with a latent of 128, whole lanes, and positions for
    `WIDE_LEN` rows: a call the kernel takes."""
    cfg = FAM.cfg('tiny', kv_lora_rank=128, max_position_embeddings=WIDE_LEN)
    w = FAM.weights(cfg, seed=9)
    return cfg, w, FAM.model(cfg, w)


def test_with_the_kernel_only_one_query_a_slot_leaves_the_einsums(
        wide, interpreted):
    """A latent of whole lanes and the kernel interpreted: the two
    decode blocks are other programs than the einsums'; a chunk against
    rows held and the whole prefill are the very programs they are
    without it."""
    _, _, model = wide
    kw = dict(max_length=256, buckets=[16])
    with_kernel = _own_program_texts(H.engine(model, **kw))
    with pytest.MonkeyPatch.context() as mp:
        from paddle_tpu.ops import pallas
        mp.setattr(pallas, 'latent_decode_kernel', lambda *a: None)
        without = _own_program_texts(H.engine(model, **kw))
    assert {n for n in without if with_kernel[n] != without[n]} \
        == {'decode', 'decode_half'}


def test_both_decode_programs_agree_with_the_reference_through_the_kernel(
        wide, interpreted):
    """`test_both_decode_programs_agree_with_the_reference` with the
    kernel interpreted, 2 slots x 768: the half program's rounds walk
    tiles of 128 rows, the whole program's of 256. One request at a
    time, so a round's `read_rows` is exact: over the three latent
    layers, the decoding slot's length rounded up to the tile, and ONE
    tile of the slot that is not decoding (whatever stale position it
    holds) — not `slots x rows`."""
    cfg, w, model = wide
    log = H.cleared_log()
    eng = H.engine(model, max_length=WIDE_LEN, buckets=[16, 320, 640])
    assert eng._bounded_tiles(WIDE_LEN).tolist() == [256] * 3
    assert eng._bounded_tiles(WIDE_LEN // 2).tolist() == [128] * 3
    H.one_at_a_time(FAM, cfg, w, eng, ((3, 12), (250, 24), (370, 16),
                                       (600, 12)), WIDE_LEN)
    rounds = H.rounds(log)
    assert {a['rows'] for a in rounds} == {WIDE_LEN // 2, WIDE_LEN}
    walked = set()
    for a in rounds:
        assert a['active'] == 1 and a['needed_rows'] % 3 == 0
        tile = 256 if a['rows'] == WIDE_LEN else 128
        length = a['needed_rows'] // 3
        tiles = -(-length // tile)
        walked.add((tile, tiles))
        assert a['read_rows'] == 3 * (tiles * tile + tile)
        assert a['needed_rows'] <= a['read_rows'] < 2 * 3 * a['rows']
    # one, two and three tiles of each size were walked
    assert walked >= {(128, 1), (128, 2), (128, 3), (256, 2), (256, 3)}


def test_through_router_and_engine_every_prompt_length_through_the_kernel(
        wide, interpreted):
    """`test_through_router_and_engine_every_prompt_length` with the
    kernel interpreted: two slots decoding side by side at lengths that
    differ, each bounded by its own."""
    cfg, w, model = wide
    log = H.cleared_log()
    lengths = (1, 2, BUCKET, 127, 128, 129, 300)
    prompts = H.prompts(lengths)
    toks, eng = H.through_the_router(model, prompts, N_NEW,
                                     max_length=WIDE_LEN,
                                     buckets=[BUCKET, 160, 320])
    H.within_tol(FAM, cfg, w, prompts, toks, WIDE_LEN)
    assert eng._counts['prefills'] == len(lengths)
    rounds = H.rounds(log)
    assert any(a['active'] == 2 for a in rounds)
    for a in rounds:
        tile = int(eng._bounded_tiles(a['rows'])[0])
        assert a['needed_rows'] <= a['read_rows'] \
            <= a['needed_rows'] + 3 * 2 * tile
        assert a['read_rows'] % (3 * tile) == 0


def test_decode_round_reads_slots_x_rows_where_the_einsums_run(wide):
    """The same engine on the CPU, the kernel not interpreted: what a
    round reads is what it was, every row of every slot."""
    _, _, model = wide
    log = H.cleared_log()
    eng = H.engine(model, max_length=WIDE_LEN, buckets=[16])
    assert not eng._bounded_tiles(WIDE_LEN).any()
    eng.submit([5, 6, 7], H.greedy(6))
    eng.run()
    rounds = H.rounds(log)
    assert rounds and all(a['read_rows'] == 2 * 3 * a['rows']
                          for a in rounds)


def test_a_model_without_a_latent_entry_is_asked_nothing(interpreted):
    eng = InferenceEngine(H.llama(), num_slots=2, max_length=256,
                          decode_block=BLOCK, buckets=[BUCKET])
    assert not eng._bounded_tiles(256).any()
    assert eng._read_rows(256) == 2 * 256 * len(eng.pool.row_spec)


# bf16 expert leaves through both expert kernels, interpreted (PR 49)
test_the_expert_kernels_serve_the_loops_tokens = \
    H.expert_kernel_serves_the_loops_tokens(FAM)
