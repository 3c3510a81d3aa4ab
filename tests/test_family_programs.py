"""The programs of the families a new family must leave alone: a tiny
engine of each (2 slots x 64, block 4, bucket 16) lowers to the
StableHLO it lowered to before the family that asks came — pinned when
`nlp/mimo_v2.py` brought a ring (PR 32) and `nlp/deepseek_v3.py` a
latent entry (PR 37) — and a model that keeps K and V only takes none
of `nlp/lfm2.py`'s state path (PR 30); `nlp/ling3.py` (PR 43: a state
entry of two leaves, a group-limited router, a gate on latent attention)
left all of them as they were, and its own tiny engine's programs are
pinned here for the family after it; the kernel for its one-token
recurrence (PR 44) is asked by `kda_mix` alone and leaves its prefill and
every other family's programs as they were; `nlp/jamba.py` (PR 46: a
diagonal state of two leaves beside K and V of ONE head, and `qk_norm=`
on `AfmoeAttention`, which it alone turns off: afmoe and lfm2 build that
class with the default) left afmoe's, lfm2's, mimo_v2's and everyone
else's programs at their digests, and its own are pinned here. One place: a new family adds its
tiny engine to `_FAMILIES`, its pins, and what its pool must not book for
the others."""
import hashlib

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.nlp import generation
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.jamba import JambaConfig, JambaForCausalLM
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.nlp.ling3 import Ling3Config, Ling3ForCausalLM
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.serving import InferenceEngine

from family_harness import program_texts

_FAMILIES = {'gpt': (GPTForCausalLM, GPTConfig),
             'llama': (LlamaForCausalLM, LlamaConfig),
             'afmoe': (AfmoeForCausalLM, AfmoeConfig),
             'jamba': (JambaForCausalLM, JambaConfig),
             'lfm2': (Lfm2MoeForCausalLM, Lfm2MoeConfig),
             'ling3': (Ling3ForCausalLM, Ling3Config),
             'mimo_v2': (MiMoV2ForCausalLM, MiMoV2Config)}

# sha256 (first 16 hex digits) of the StableHLO text of each program, by
# the very code of `family_harness.program_texts`: the first twelve
# prefills' taken on the PARENT of PR 32 (commit 25ee4df), mimo_v2's on
# the PARENT of PR 37 (commit bbd1fb5); the decode programs' re-taken AT
# PR 36, which made the slot state one buffer that they unpack, and AT
# PR 45, which handed them the tokens of the block before and one more
# flag a slot (one `where` outside the scan: `tests/test_serving.py`
# spells it; they are the engine's own functions on `_decode_args()`,
# the pins of `tests/test_pool_layout.py`); jax 0.9.0, which the repository is
# written for (the verify skill); ling3's taken AT PR 43, which brought
# it, jamba's AT PR 46
_PARENT_PROGRAMS = {
    ('afmoe', 'decode'): '06d4c6cd626f8f1c',
    ('afmoe', 'decode_half'): '9ff2fd3dad3881d1',
    ('afmoe', 'prefill'): '6782a117cd64283e',
    ('gpt', 'decode'): '26d1a9e871109e8a',
    ('gpt', 'decode_half'): '43ad4e7e3a35cce5',
    ('gpt', 'prefill'): '365eec42133d1ab2',
    ('jamba', 'decode'): 'fc2f502b42e211f1',
    ('jamba', 'decode_half'): '4d36c83e4ab82144',
    ('jamba', 'prefill'): '8e3724ef9358419d',
    ('lfm2', 'decode'): '2b23d551b6d5dcf5',
    ('lfm2', 'decode_half'): '0f8bfc49e25b9946',
    ('lfm2', 'prefill'): '1a02dff7d8263eae',
    ('ling3', 'decode'): '859ac4250da37164',
    ('ling3', 'decode_half'): 'd6c532db63081802',
    ('ling3', 'prefill'): '5d6e70a47ddf9ca9',
    ('llama', 'decode'): '0b50d9ed7ed665f2',
    ('llama', 'decode_half'): 'b5a72e2c6a853df8',
    ('llama', 'prefill'): '8b4c79aa8dc443ef',
    ('mimo_v2', 'decode'): '6ac560b685af1312',
    ('mimo_v2', 'decode_half'): '497fca16f53523e5',
    ('mimo_v2', 'prefill'): '83c5267b24fdfe6b',
}


def _tiny_engine(family):
    cls, conf = _FAMILIES[family]
    paddle.seed(0)
    return InferenceEngine(cls(conf.tiny()).eval(), num_slots=2,
                           max_length=64, decode_block=4, buckets=[16])


def _digests(eng):
    return {name: hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
            for name, lowered in program_texts(eng).items()}


@pytest.mark.parametrize('family, without', [
    # what mimo_v2's ring (PR 32) must leave alone ...
    ('afmoe', 'ring_layers'), ('gpt', 'ring_layers'),
    ('lfm2', 'ring_layers'), ('llama', 'ring_layers'),
    # ... and what deepseek_v3's latent entry (PR 37)
    ('afmoe', 'latent_layers'), ('gpt', 'latent_layers'),
    ('lfm2', 'latent_layers'), ('llama', 'latent_layers'),
    ('mimo_v2', 'latent_layers'),
    # ... and what ling3's state of two leaves beside latent rows (PR 43):
    # its own programs, for the family after it
    ('ling3', 'ring_layers'),
    # ... and what jamba's `qk_norm=` and `ssm_chunks` (PR 46): every
    # program above; its own, for the family after it
    ('jamba', 'ring_layers'), ('jamba', 'latent_layers')])
def test_the_other_families_programs_are_the_parents(family, without):
    eng = _tiny_engine(family)
    assert getattr(eng.pool, without) == ()
    for name, digest in _digests(eng).items():
        assert digest == _PARENT_PROGRAMS[family, name], (family, name)


@pytest.mark.parametrize('family', sorted(_FAMILIES))
def test_through_the_kv_kernel_only_the_decode_blocks_are_other_programs(
        family, monkeypatch):
    """`ops.pallas.kv_decode_kernel` lifted off its backend condition
    (PR 40; tiles of 16 rows, the toy length has no whole lanes): the
    families that ask it — float32 queries over K and V by head, in
    their cached branch — get other decode blocks and the same prefill;
    gpt and llama never ask, and every program of theirs is the
    parent's. (Lowered here and not through the program store.)"""
    from paddle_tpu.ops import pallas, pallas_kernels
    kv_interpreted = []
    real = pallas.kv_decode_kernel

    def asked(*args, **kw):
        kv_interpreted.append(args)
        return real(*args, interpret=True, **kw)
    monkeypatch.setattr(pallas, 'kv_decode_kernel', asked)
    monkeypatch.setattr(pallas_kernels, '_mla_row_tile',
                        lambda rows: 16 if rows % 16 == 0 else None)
    changed = {name for name, digest in _digests(_tiny_engine(family)).items()
               if digest != _PARENT_PROGRAMS[family, name]}
    # (ling3's one attending layer is latent: never K and V by head;
    # jamba's four query heads on ONE K,V head are the kernel's)
    assert changed == ({'decode', 'decode_half'}
                       if family in ('afmoe', 'jamba', 'lfm2', 'mimo_v2')
                       else set())
    assert bool(kv_interpreted) == bool(changed)


@pytest.mark.parametrize('family', sorted(_FAMILIES))
def test_through_the_kda_kernel_only_ling3s_decode_blocks_are_other_programs(
        family, kda_interpreted):
    """`ops.pallas.kda_step_kernel` lifted off its backend and
    whole-lane conditions (PR 44; interpreted: the toy head of 8 has no
    whole lanes): `kda_mix` alone asks it, for a call of one token — so
    ling3 gets other decode blocks and the same prefill (more tokens:
    the chunked scan), and every program of the five other families is
    the parent's. (Lowered here and not through the program store.)"""
    eng = _tiny_engine(family)
    changed = {name for name, digest in _digests(eng).items()
               if digest != _PARENT_PROGRAMS[family, name]}
    assert changed == ({'decode', 'decode_half'} if family == 'ling3'
                       else set())
    # each decode block asks once a KDA layer: its whole leaf, every slot
    assert set(kda_interpreted) == ({(2, 4, 8, 8)} if changed else set())
    assert eng._state_kernel_layers == (2 if family == 'ling3' else None)


@pytest.mark.parametrize('family', ['afmoe', 'gpt', 'llama'])
def test_a_model_without_state_keeps_the_programs_it_had(family):
    """The state path is picked by what the cache holds: a model with K
    and V only has no state booked, every layer counted as attending,
    and the plain prefill — ids alone, no length."""
    eng = _tiny_engine(family)
    assert eng.pool.state_layers == () and eng.pool.state_bytes == 0
    assert len(eng._layer_rows) == len(eng.pool.row_spec)
    assert eng._prefill_jit._fn_token \
        == programs.code_token(eng._prefill_fn) \
        != programs.code_token(eng._state_prefill_fn)
    state = (eng._params, eng._frozen, eng._buffers)
    slab = jax.eval_shape(eng._prefill_fn, *state,
                          jnp.zeros((1, 16), jnp.int32))
    assert not generation.state_layers(slab)
