"""How a served family is held to its plain float32 reference: one
place. A family is a model class under `paddle_tpu/nlp/` with an adapter
(`benchmarks/models/<ModelClass>.py`) and a plain reference
(`benchmarks/reference/<name>.py`, read here, never edited).

**A new family supplies**, in `tests/test_<family>.py`, one `Family`:
the adapter's name, its config class, its tiny presets, what the
published file says beside the preset (`cfg_adds`), its weight recipe
where a plain draw at std 0.3 would hide a fault (`draw`: mimo's sinks at
std 2, lfm2's random taps, ...), `one_position` where a ring or a state
stands at ONE position (its prefill takes the prompt's length; prefix
cache, chunks, pages, speculation are refused); its departures from the
published mathematics, its faulty hand-offs, and the checks that read
what ITS decode rounds carry. TOL stays 2e-4 unless its docstring shows
other margins. Every family's engine is 2 slots x `MAX_LEN` 64, blocks of
`BLOCK` 4, buckets `BUCKET` 16 and 32; `engine(model, **extra)` takes what
differs.

**It gets**, by binding them in its files (`test_x = H.case(FAM, ...)`;
`--dist loadfile` spreads files, so a family's cases are collected from
ITS files: what builds no engine in `test_<family>.py`, what serves in
`test_<family>_serving.py`), each over every preset unless told:
`full_forward` (eagerly, or `H.paths`: over its own tokens and against
rows held), `left_padded_forward`, `each_departure`, `generate_greedy`,
`generate_refuses`; `prefill_then_decode`,
`through_router_shorter_than_bucket`, `faulty_hand_off`,
`more_requests_than_slots`, `ahead_serves_the_serial_tokens`,
`reseated_slot`, `both_decode_programs`,
`decode_through_the_kernel`, `expert_kernel_serves_the_loops_tokens`,
`modes_refused`, `as_a_draft_refused`. `tests/test_family_programs.py`
pins the programs of the families it must leave alone: it adds its tiny
engine there. What is the family's own (a closed form, a loop, its
pool's book, its scopes, its presets and refusals) it writes in its
files with the helpers below.

**The reference is compiled once a configuration** (`Family.ref_logits`):
`jax.jit` of `logits_of(hidden_states(...))` with the weights as an
argument, every row alone and right-padded to `REF_LEN`; logits of the
same (weights, row) are kept, so a family's eleven departures are held to
ONE reference. All five references are causal and route token by token:
what follows a position does not reach it. A family whose reference is
NOT indifferent to what follows passes `ref_len=` its row's length
(`jax.jit` then compiles per length and keeps each) and says so beside
its `Family`; no TOL is widened to make padding pass. Observed on this
tree (PR 42, float32 on the CPU), beside TOL 2e-4 and the departures' bar
50 x TOL = 1e-2: the padded reference against the eager one at the exact
length, ids (2, 40) / the program against the padded reference, largest
over `full_forward`, `prefill_then_decode` and the sound `each_departure`
/ the mildest departure:

    afmoe        6.2e-6   6.0e-6   2.3    (rope_on_full)
    lfm2         1.3e-5   8.8e-6   1.1    (bf16_operands)
    mimo_v2      9.1e-6   7.4e-6   6.4e-2 (one_theta)
    deepseek_v3  2.0e-5   4.2e-5   1.5e-1 (bf16_operands)
    xing4        1.8e-5   2.2e-5   1.1e-2 (res_map_transposed)

Every faulty hand-off (mimo_v2's two, lfm2's two) still leaves a served
token more than 50 x TOL under the reference's best.
"""
import dataclasses
import functools
import importlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.nlp import afmoe
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import pallas
from paddle_tpu.serving import (InferenceEngine, ReplicaSet, Router,
                                SamplingParams)

from benchmarks.models import adapter, fill
from benchmarks.reference import common as C

TOL = 2e-4
BUCKET, BLOCK, MAX_LEN = 16, 4, 64
REF_LEN = 64


def draw(shapes, seed, std=0.3):
    """std 0.3: logits of a few units, so a departure is not lost in
    them; a selection bias of 0.3 beside sigmoid scores changes picks."""
    return C.make_weights(shapes, seed, 'float32', std=std)


@dataclasses.dataclass(eq=False)
class Family:
    model_class: str                    # the adapter's name
    config: type
    presets: tuple
    cfg_adds: object = None             # conf -> what the file says beside
    draw: object = None                 # (R, cfg, seed, **kw) -> weights
    over: dict = dataclasses.field(default_factory=dict)   # of every build
    one_position: bool = False

    def __post_init__(self):
        self.adapter = adapter(self.model_class)
        self.R = importlib.import_module(
            'benchmarks.reference.' + self.adapter.reference)
        self._weights, self._built, self._compiled, self._logits = \
            {}, {}, {}, {}

    def cfg(self, preset='tiny', **over):
        conf = getattr(self.config, preset)(**over)
        cfg = {k: getattr(conf, k, None) for k in self.adapter._KEYS}
        cfg.update(self.cfg_adds(conf) if self.cfg_adds else {})
        return cfg

    def weights(self, cfg, seed=7, **kw):
        """Drawn once a (configuration, seed): the reference's logits
        are kept by the weights they were computed from."""
        key = (_key(cfg), seed, _key(kw))
        if key not in self._weights:
            self._weights[key] = self.draw(self.R, cfg, seed, **kw) \
                if self.draw else draw(self.R.param_shapes(cfg), seed)
        return self._weights[key]

    def model(self, cfg, w):
        return fill(self.adapter.build(cfg), w,
                    self.adapter.name_map(cfg)).eval()

    def build(self, preset=None, **over):
        """-> (cfg, weights, model) of a preset, built once a process:
        no shared case changes a model it did not build itself."""
        preset = preset or self.presets[0]
        over = {**self.over, **over}
        key = (preset, _key(over))
        if key not in self._built:
            cfg = self.cfg(preset, **over)
            w = self.weights(cfg)
            self._built[key] = cfg, w, self.model(cfg, w)
        return self._built[key]

    def ref_logits(self, cfg, w, ids, ref_len=REF_LEN):
        """The reference's logits [rows, positions, vocab]: one compile
        a configuration (and `ref_len`), every row through alone."""
        key = _key(cfg)
        if key not in self._compiled:
            R = self.R
            self._compiled[key] = jax.jit(lambda wt, row: R.logits_of(
                cfg, wt, R.hidden_states(cfg, wt, row)))
        fn, out = self._compiled[key], []
        for row in np.atleast_2d(np.asarray(ids, 'int32')):
            memo = (key, id(w), ref_len, row.tobytes())
            if memo not in self._logits:
                padded = np.zeros((1, ref_len), 'int32')
                padded[0, :len(row)] = row
                self._logits[memo] = w, np.asarray(
                    fn(w, jnp.asarray(padded)))[0, :len(row)]
            out.append(self._logits[memo][1])
        return np.stack(out)

    def served_gap(self, cfg, w, prompt, toks, ref_len=REF_LEN):
        """How far a served token's reference logit lies below the
        reference's best at its position: the benchmark's comparison."""
        prompt, toks = list(prompt), list(toks)
        lg = self.ref_logits(cfg, w, prompt + toks[:-1],
                             ref_len)[0, len(prompt) - 1:]
        return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def _key(cfg):
    return json.dumps(cfg, sort_keys=True, default=str)


def fixtures(fam):
    """-> (`built`: every preset in turn, `tiny`: the first), for the
    family's own cases."""
    @pytest.fixture(scope='module', params=fam.presets)
    def built(request):
        return fam.build(request.param)

    @pytest.fixture(scope='module')
    def tiny():
        return fam.build()
    return built, tiny


def _per_preset(fam, presets=None):
    return pytest.mark.parametrize('preset', presets or fam.presets)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def ids(shape, seed=0):
    return np.random.RandomState(seed).randint(3, 128, shape).astype('int32')


def prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, 128, n).tolist() for n in lengths]


def _geometry(extra):
    return {**dict(num_slots=2, max_length=MAX_LEN, decode_block=BLOCK,
                   buckets=[BUCKET, 32], eos_token_id=-1), **extra}


def engine(model, **extra):
    return InferenceEngine(model, **_geometry(extra))


def greedy(n_new):
    return SamplingParams(max_new_tokens=n_new, eos_token_id=-1)


def through_the_router(model, prompts, n_new, **extra):
    router = Router(ReplicaSet(model, 1, **_geometry(extra)))
    hs = [router.submit(p, greedy(n_new)) for p in prompts]
    router.run()
    assert all(h.error is None and len(h.tokens) == n_new for h in hs)
    return [list(h.tokens) for h in hs], router.replicas[0].engine


def within_tol(fam, cfg, w, served, toks, ref_len=REF_LEN):
    """Every served request's tokens are the reference's greedy ones,
    to TOL of its logits."""
    for prompt, got in zip(served, toks):
        assert fam.served_gap(cfg, w, prompt, got, ref_len) < TOL, \
            len(prompt)


def one_at_a_time(fam, cfg, w, eng, requests, ref_len=REF_LEN):
    """Each (prompt length, new tokens) served alone, so a round's
    counts are one slot's, and held to the reference."""
    for n_prompt, n_new in requests:
        prompt = prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, greedy(n_new))
        eng.run()
        assert h.error is None and len(h.tokens) == n_new
        assert fam.served_gap(cfg, w, prompt, h.tokens, ref_len) < TOL


#: what a `serving.decode_round` or `serving.settle` says of the block
#: it FETCHED
_FETCHED = {'discarded', 'experts_touched', 'expert_layer_substeps',
            'expert_kernel_substeps', 'experts', 'picks', 'picks_held'}


def rounds(log):
    """Per decode ROUND, the counts its spans carried. A round's block
    is dispatched under ONE `serving.decode_round` span (the block's own
    counts: `rows`, `read_rows`, ...) and fetched under that one, a later
    one or a `serving.settle` (the routing counts, `discarded`) — not
    its own whenever the engine ran ahead (ISSUE 45) — and rounds are
    fetched in the order they were dispatched: the k-th dispatch's
    counts and the k-th fetch's are one round's."""
    spans = [e for e in log.events() if e.get('ph') == 'X' and e['name']
             in ('serving.decode_round', 'serving.settle')]
    dispatched = [{k: v for k, v in e['attrs'].items() if k not in _FETCHED}
                  for e in spans if e['name'] == 'serving.decode_round']
    fetched = [{k: v for k, v in e['attrs'].items() if k in _FETCHED}
               for e in spans if 'discarded' in e['attrs']]
    assert len(dispatched) == len(fetched), (len(dispatched), len(fetched))
    return [dict(d, **f) for d, f in zip(dispatched, fetched)]


def cleared_log():
    log = obs.get_event_log()
    log.clear()
    return log


def llama(seed=3):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig.tiny()).eval()


def llama_round():
    """One request through an engine of a model that keeps K and V
    only: -> (the engine, its last decode round's attrs, the log)."""
    log = cleared_log()
    eng = InferenceEngine(llama(), num_slots=2, max_length=MAX_LEN,
                          decode_block=BLOCK, buckets=[BUCKET])
    eng.submit([5, 6, 7], greedy(6))
    eng.run()
    return eng, rounds(log)[-1], log


def scopes_found(program):
    """The scopes on the ops of a compiled program the store holds."""
    return {s for op, *_ in programs.scope_table()[program].values()
            for s in programs.scope_path(op)}


def refused(make_config, cases):
    for bad, what in cases:
        with pytest.raises(ValueError, match=what):
            make_config(**bad)


def program_texts(eng):
    """The engine's own functions, lowered: both decode blocks and the
    whole prefill (with the prompt's length where a slot stands at one
    position)."""
    state = (eng._params, eng._frozen, eng._buffers)
    dec = eng._decode_args()
    ids = jnp.zeros((1, 16), jnp.int32)
    one = eng.pool.stands_at_one_position
    pre = (ids, jnp.int32(5)) if one else (ids,)
    prefill = eng._state_prefill_fn if one else eng._prefill_fn
    return {
        'decode': jax.jit(eng._decode_block_fn).lower(*dec),
        'decode_half': jax.jit(eng._decode_block_half_fn).lower(*dec),
        'prefill': jax.jit(prefill).lower(*state, *pre)}


def paths(model, tokens, own=True, held=True, **kwargs):
    """-> the logits of a plain forward (`own`: attention over the
    call's own tokens) and of the whole sequence in ONE call against
    rows held (`held`: a traced slot, so a latent family's absorbed path
    over the cache it has just written), one compile for the two."""
    state = functional_state(model)
    cache = model.init_cache(tokens.shape[0], tokens.shape[1] + 8)

    def both(ids, cache, zero):
        out = []
        if own:
            out.append(functional_call(model, *state, (ids,),
                                       dict(kwargs))[0])
        if held:
            out.append(functional_call(
                model, *state, (ids,),
                dict(cache=cache, use_cache=True, position_offset=zero,
                     cache_offset=zero))[0][0])
        return out
    return [np.asarray(o) for o in jax.jit(both)(
        jnp.asarray(tokens), cache, jnp.zeros((), jnp.int32))]


def attended_rows_under_the_half_mask(model, module):
    """One token forwarded under the half program's mask of 32 columns
    over a cache of 64 rows: -> per call of `generation.attended_rows`
    from `module`, (rows of the first leaf handed in, shapes of the
    leaves handed back)."""
    from unittest import mock
    from paddle_tpu.nlp import generation
    sliced = []

    def spy(a, b, mask):
        out = generation.attended_rows(a, b, mask)
        sliced.append((a.shape[1], tuple(tuple(x.shape) for x in out)))
        return out
    with mock.patch.object(module, '_attended_rows', spy):
        pos = jnp.zeros((1,), jnp.int32)
        mask = (jnp.arange(32)[None, :] <= pos[:, None])[:, None, None, :]
        model(paddle.to_tensor(ids((1, 1))), cache=model.init_cache(1, 64),
              use_cache=True, position_offset=pos, cache_offset=pos,
              attention_mask=mask)
    return sliced


@functools.cache
def cached_fwd(model):
    """`(ids, cache, position_offset, cache_offset, mask) -> (logits,
    cache)` jitted once a model: a compile a shape of `ids`, the offsets
    traced (eagerly, a model's forward is some forty small compiles a
    new shape AND offset)."""
    return jax.jit(cached_forward(model, *functional_state(model)))


def own_path(model, tokens):
    return paths(model, tokens, held=False)


def eagerly(model, tokens):
    return [model(paddle.to_tensor(tokens)).numpy()]


# departures three and more families share
def route_bias_in_weight(scores, bias, k, route_norm, route_scale, eps):
    w, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def bias_in_weight(model, mp):
    mp.setattr(afmoe, 'route', route_bias_in_weight)


def bf16_operands(model, mp, norms=('input_layernorm',
                                    'post_attention_layernorm'),
                  final='norm'):
    """What a single bf16 pass makes of the float32 activations: every
    norm's output, the operand of every projection, rounded."""
    def rounded(norm):
        real = norm.forward
        norm.forward = lambda x: real(x).astype('bfloat16').astype('float32')
    for layer in model.model.layers:
        for name in norms:
            rounded(getattr(layer, name))
    rounded(getattr(model.model, final))


# ---------------------------------------------------------------------------
# the shared cases, model-level
# ---------------------------------------------------------------------------
def full_forward(fam, outputs=eagerly, shape=(2, 40)):
    @_per_preset(fam)
    def test(preset):
        cfg, w, model = fam.build(preset)
        tokens = ids(shape)
        ref = fam.ref_logits(cfg, w, tokens)
        assert np.abs(ref).max() > 3.0          # logits of a few units
        err = max(np.abs(got - ref).max() for got in outputs(model, tokens))
        assert err < TOL
    return test


def left_padded_forward(fam):
    """A [B, S] padding mask: attention masks the pads, and a state
    starts from the zeros before a sequence."""
    def test():
        cfg, w, model = fam.build()
        tokens = ids((1, 12), 4)
        padded = np.concatenate([np.zeros((1, 5), 'int32'), tokens], axis=1)
        keep = np.concatenate([np.zeros((1, 5)), np.ones((1, 12))], axis=1)
        off = paddle.to_tensor(np.array([-5], 'int32'))
        got = model(paddle.to_tensor(padded), attention_mask=keep,
                    position_offset=off).numpy()[0, 5:]
        assert np.abs(got - fam.ref_logits(cfg, w, tokens)[0]).max() \
            < TOL
    return test


def each_departure(fam, departures, outputs=own_path, shape=(2, 40),
                   reweigh=None, **over):
    """The sound model agrees with the reference on weights of their
    own (seed 11), and each single departure does not, by 50 x TOL: ONE
    reference for them all, and one compile a case (`full_forward` is
    where a family's forward runs eagerly)."""
    def name(d):
        return 'sound' if d is None else d.__name__.strip('_')

    @pytest.mark.parametrize('departure', [None] + list(departures),
                             ids=name)
    def test(departure, monkeypatch, fresh_dispatch):
        cfg = fam.cfg(**over)
        w = fam.weights(cfg, seed=11)
        if reweigh:
            w = reweigh(cfg, w)
        tokens = ids(shape, 5)
        ref = fam.ref_logits(cfg, w, tokens)
        model = fam.model(cfg, w)
        if departure is not None:
            departure(model, monkeypatch)
        err = max(np.abs(got - ref).max() for got in outputs(model, tokens))
        if departure is None:
            assert err < TOL
        else:
            assert err > 50 * TOL, (name(departure), err)
    return test


def generate_greedy(fam, n_new):
    @_per_preset(fam)
    def test(preset):
        cfg, w, model = fam.build(preset)
        tokens = ids((2, 9), 8)
        out, _ = model.generate(paddle.to_tensor(tokens),
                                max_new_tokens=n_new, eos_token_id=-1)
        for row, got in zip(tokens, out.numpy()):
            assert fam.served_gap(cfg, w, row, got) < TOL
        if fam.one_position:
            # all-ones mask: nothing is padded, nothing refused
            same, _ = model.generate(
                paddle.to_tensor(tokens), max_new_tokens=n_new,
                eos_token_id=-1, attention_mask=np.ones((2, 9), 'int32'))
            assert (same.numpy() == out.numpy()).all()
    return test


def generate_refuses(fam, why):
    """What stands at one position cannot serve a padded batch nor move
    back after a rejected draft."""
    def test():
        _, _, model = fam.build()
        tokens = ids((2, 9), 8)
        keep = np.ones((2, 9), 'int32')
        keep[1, :4] = 0
        with pytest.raises(ValueError, match='no padded prompts.*' + why):
            model.generate(paddle.to_tensor(tokens), max_new_tokens=4,
                           attention_mask=keep)
        with pytest.raises(NotImplementedError, match='moved back'):
            model.speculative_generate(llama(),
                                       paddle.to_tensor(tokens[:1]))
    return test


# ---------------------------------------------------------------------------
# the shared cases, engine-level
# ---------------------------------------------------------------------------
@functools.cache
def programs_of(model):
    """-> (an engine, its own prefill program, `cached_fwd`), compiled
    once a model for every length walked."""
    eng = engine(model)
    prefill = eng._state_prefill_fn if eng.pool.stands_at_one_position \
        else eng._prefill_fn
    return eng, jax.jit(prefill), cached_fwd(model)


def prefill_then_decode(fam, lengths, n_new, presets=None, entry=None):
    """The engine's own prefill program on a prompt right-padded to its
    bucket, the last prompt token forwarded again at its slot, then one
    token at a time over what the slot holds: the LOGITS at every
    position against the reference's full forward."""
    @pytest.mark.parametrize('n_prompt', lengths)
    @_per_preset(fam, presets)
    def test(preset, n_prompt):
        cfg, w, model = fam.build(preset)
        eng, prefill, fwd = programs_of(model)
        assert eng.pool.stands_at_one_position == fam.one_position
        tokens = ids((1, n_prompt + n_new), 3 + n_prompt)
        ref = fam.ref_logits(cfg, w, tokens)
        padded = np.zeros((1, eng.pool.bucket_for(n_prompt)), 'int32')
        padded[:, :n_prompt] = tokens[:, :n_prompt]
        length = (jnp.int32(n_prompt),) if fam.one_position else ()
        cache = prefill(eng._params, eng._frozen, eng._buffers,
                        jnp.asarray(padded), *length)
        if entry:
            assert [tuple(leaf.shape) for leaf in cache[0]] == entry
        k_slot = jnp.arange(MAX_LEN)
        worst = 0.0
        for t in range(n_prompt - 1, n_prompt + n_new):
            pos = jnp.full((1,), t, jnp.int32)
            mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
            lg, cache = fwd(jnp.asarray(tokens[:, t:t + 1]), cache, pos,
                            pos, mask)
            worst = max(worst,
                        np.abs(np.asarray(lg)[0, 0] - ref[0, t]).max())
        assert worst < TOL
    return test


def through_router_shorter_than_bucket(fam, lengths, n_new):
    @_per_preset(fam)
    def test(preset):
        cfg, w, model = fam.build(preset)
        served = prompts(lengths)
        toks, eng = through_the_router(model, served, n_new)
        within_tol(fam, cfg, w, served, toks)
        assert eng._counts['prefills'] == len(lengths)
        assert eng._counts['chunked_prefills'] == 0
    return test


def faulty_hand_off(fam, faults, lengths, n_new):
    """`faults`: (fault(monkeypatch), a slot count of its own — the
    program store keys a program by the engine's geometry, not by what a
    test patched, and must trace the faulty prefill anew)."""
    @pytest.mark.parametrize(
        'fault,slots', faults,
        ids=lambda f: getattr(f, '__name__', '').strip('_'))
    def test(fault, slots, monkeypatch):
        cfg, w, model = fam.build()
        fault(monkeypatch)
        served = prompts(lengths)
        toks, _ = through_the_router(model, served, n_new, num_slots=slots)
        gaps = [fam.served_gap(cfg, w, p, t) for p, t in zip(served, toks)]
        assert max(gaps) > 50 * TOL, gaps
    return test


def more_requests_than_slots(fam):
    """Continuous batching: two slots, seven requests, each slot seated
    over what the last request left in it."""
    @_per_preset(fam)
    def test(preset):
        cfg, w, model = fam.build(preset)
        served = prompts((5, 19, 1, 11, 16, 2, 27), seed=2)
        eng = engine(model)
        hs = [eng.submit(p, greedy(6 + 3 * i)) for i, p in enumerate(served)]
        eng.run()
        assert all(h.error is None for h in hs)
        within_tol(fam, cfg, w, served, [h.tokens for h in hs])
        assert eng._counts['prefills'] == 7 and eng.pool.num_slots == 2
    return test


class SerialEngine(InferenceEngine):
    """The engine held to the serial order, every round settled before
    the next is dispatched: what a run-ahead engine's tokens are compared
    with (test-only; production has no switch, ISSUE 45)."""

    def _may_run_ahead(self):
        return False


def ahead_serves_the_serial_tokens(fam):
    """Two slots kept busy by five requests: whenever nobody can be
    seated or ends by length the engine dispatches a block BEFORE it has
    fetched the one in flight, its pending tokens never leaving the
    device — and every request's tokens are those of the same engine
    settling each round first, and the reference's to TOL."""
    def test():
        cfg, w, model = fam.build()
        served = prompts((5, 19, 3, 11, 16), seed=4)
        news = (24, 17, 8, 13, 20)

        def serve(cls):
            eng = cls(model, **_geometry({}))
            hs = [eng.submit(p, greedy(n)) for p, n in zip(served, news)]
            eng.run()
            assert all(h.error is None and len(h.tokens) == n
                       for h, n in zip(hs, news))
            assert not eng._rounds and not eng.has_work
            return [list(h.tokens) for h in hs], eng.stats()
        log = cleared_log()
        toks, stats = serve(InferenceEngine)
        flags = [a['ahead'] for a in rounds(log)]
        serial, serial_stats = serve(SerialEngine)
        assert toks == serial
        within_tol(fam, cfg, w, served, toks)
        # the rounds are the same rounds, in another order of the calls
        assert stats['decode_rounds'] == serial_stats['decode_rounds']
        assert stats['tokens'] == serial_stats['tokens'] == sum(news)
        assert serial_stats['rounds_ahead'] == 0
        assert stats['rounds_ahead'] == sum(flags) >= 6
        assert stats['blocks_discarded'] == 0       # nobody's EOS
    return test


def reseated_slot(fam, layers):
    """After a long request the slot's `layers` (the pool's
    `ring_layers`, `state_layers`) are its garbage; the next prefill
    seats every leaf of them whole, and the short request then served
    from that slot is the one served from a fresh engine."""
    def held(eng):
        return [np.asarray(leaf) for i in getattr(eng.pool, layers)
                for leaf in jax.tree_util.tree_leaves(eng.pool.rows[i])]

    def test():
        _, _, model = fam.build()
        long_one, short_one = prompts((27, 3), seed=5)
        eng = engine(model, num_slots=1)
        a = eng.submit(long_one, greedy(20))
        eng.run()
        assert all(np.abs(leaf).min() > 0 for leaf in held(eng))
        b = eng.submit(short_one, greedy(10))
        eng.run()
        fresh = engine(model, num_slots=1)
        c = fresh.submit(short_one, greedy(10))
        fresh.run()
        assert a.error is None and list(b.tokens) == list(c.tokens)
        for got, want in zip(held(eng), held(fresh)):
            assert np.abs(got - want).max() < 1e-5
    return test


def modes_refused(fam, why, paged):
    """What cannot share or rewind what stands at one position is
    refused, with its reason: `why` the family's, `paged` what it says
    of pages."""
    @pytest.mark.parametrize('extra,names', [
        (dict(prefix_cache=True), 'prefix_cache.*END of its donor'),
        (dict(prefix_cache=0.5), 'prefix_cache'),
        (dict(prefill_chunk_tokens=16), 'prefill_chunk_tokens.*chunk'),
        (dict(kv_page_size=8), 'kv_page_size.*' + paged),
        (dict(kv_pages=40), 'kv_pages'),
        (dict(kv_quant='int8'), 'kv_quant.*int8'),
        (dict(draft_model='llama'), 'draft_model.*moved back'),
    ], ids=['prefix_cache', 'prefix_cache_fraction', 'chunked_prefill',
            'paged', 'kv_pages', 'int8_kv', 'speculative'])
    def test(extra, names):
        if extra.get('draft_model') == 'llama':
            extra = dict(draft_model=llama())
        with pytest.raises(ValueError, match=why + '.*' + names):
            engine(fam.build()[2], **extra)
    return test


def as_a_draft_refused(fam, why):
    def test():
        with pytest.raises(ValueError, match=why + '.*draft_model'):
            InferenceEngine(llama(), num_slots=2, max_length=MAX_LEN,
                            draft_model=fam.build()[2])
    return test


def expert_kernel_serves_the_loops_tokens(fam):
    """bf16 expert leaves: through the engine the interpreted kernels
    give the greedy tokens the loop gives. `decode`: at the model's own
    block of 256 rows every call is one block wide — `moe_decode_experts`
    in the rounds, every expert-layer sub-step of every round booked as
    the kernel's, in the span and in the counter, the prefills on the
    loop. `prefill`: at a block of 8 rows both buckets are wider than a
    block — `moe_grouped_experts` in every prefill, and
    `serving.prefill` reads `expert_kernel_layers` = the expert layers.
    On the CPU as it is, none of either."""
    @pytest.mark.parametrize('block_rows', [256, 8],
                             ids=['decode', 'prefill'])
    def test(block_rows, monkeypatch, fresh_programs):
        monkeypatch.setattr(afmoe, 'BLOCK_ROWS', block_rows)
        cfg = fam.cfg()
        w = {name: v.astype(jnp.bfloat16) if 'experts_' in name else v
             for name, v in fam.weights(cfg).items()}
        served, log, reg = prompts((5, 19, 11)), cleared_log(), \
            obs.get_registry()
        family = 'paddle_serving_moe_expert_kernel_substeps_total'

        def prefills():
            return [e['attrs']['expert_kernel_layers'] for e in log.events()
                    if e['name'] == 'serving.prefill']
        before = reg.value(family)
        model = fam.model(cfg, w)
        layers = sum(isinstance(l, afmoe.AfmoeSparseMLP)
                     for l in model.sublayers())
        base, _ = through_the_router(model, served, 14)
        assert all(a['expert_kernel_substeps'] == 0 for a in rounds(log))
        assert reg.value(family) == before
        assert layers and prefills() == [0, 0, 0]
        monkeypatch.setattr(afmoe, 'expert_kernel', functools.partial(
            pallas.expert_kernel, interpret=True))
        fresh_programs.clear_memory()
        log.clear()
        toks, _ = through_the_router(fam.model(cfg, w), served, 14)
        assert toks == base
        booked = [a['expert_kernel_substeps'] for a in rounds(log)]
        assert booked and booked == [a['expert_layer_substeps']
                                     for a in rounds(log)] and all(booked)
        assert reg.value(family) - before == sum(booked)
        assert prefills() == [layers * (block_rows < BUCKET)] * 3
    return test


def both_decode_programs(fam, check, **extra):
    """max_length 64: rounds attend over 32 rows while every active
    position allows it, then over 64. One request stays inside the half
    program, one crosses over, one starts past it. `check(eng, rounds)`:
    what the family's rounds read (the store's memory emptied first:
    whether these programs are traced here does not hang on what an
    earlier case served with the same model)."""
    @_per_preset(fam)
    def test(preset, fresh_programs):
        cfg, w, model = fam.build(preset)
        log = cleared_log()
        eng = engine(model, **extra)
        one_at_a_time(fam, cfg, w, eng, ((3, 12), (20, 24), (30, 12)))
        assert {a['rows'] for a in rounds(log)} == {32, 64}
        check(eng, rounds(log))
    return test


def decode_through_the_kernel(fam, check, presets=None):
    """The decode block through `kv_decode_attention`, interpreted, 2
    slots x 64 rows in tiles of 16, one request at a time so a round's
    `read_rows` is exact. `check(cfg, eng, rounds, calls)`: which layers
    the kernel bounds and what they walk."""
    @_per_preset(fam, presets)
    def test(preset, kv_interpreted):
        cfg, w, model = fam.build(preset)
        log = cleared_log()
        eng = engine(model)
        one_at_a_time(fam, cfg, w, eng, ((3, 14), (21, 34)))
        assert {a['rows'] for a in rounds(log)} == {32, 64}
        assert all(a['active'] == 1 for a in rounds(log))
        check(cfg, eng, rounds(log), kv_interpreted)
    return test
