"""Rule A.1: the seed never decides how much work a run does — every
seed runs the one schedule of the traffic file; it makes the weights and
the token ids."""
import collections
import inspect

import numpy as np
import pytest

from benchmarks import spec, traffic as T

SPEC = spec.Spec()
SEEDS = (0, 1, 7, 2147483999, 5000000001)
OPEN = ('chat', 'toy-chat')
BACKLOG = ('docs', 'toy-docs')


def _multiset(reqs, region='window'):
    return collections.Counter((r.prompt_len, r.output_len)
                               for r in reqs if r.region == region)


def _rows(reqs):
    return [(r.index, r.due, r.prompt_len, r.output_len, r.region)
            for r in reqs]


@pytest.mark.parametrize('fn', [T.open_loop_schedule, T.backlog_schedule,
                                T.length_pairs, T.arrival_gaps])
def test_no_schedule_function_takes_a_seed(fn):
    assert 'seed' not in inspect.signature(fn).parameters


@pytest.mark.parametrize('name', OPEN + BACKLOG)
def test_traffic_files_have_no_key_that_draws_the_order(name):
    assert not {'order', 'block'} & set(SPEC.data('traffic', name))


@pytest.mark.parametrize('name', OPEN)
def test_open_loop_is_one_schedule(name):
    tr = SPEC.data('traffic', name)
    a, b = T.open_loop_schedule(tr, 20.0), T.open_loop_schedule(tr, 20.0)
    assert _rows(a) == _rows(b)
    # a longer window offers the same mix: the stratified quantiles
    longer = T.open_loop_schedule(tr, 40.0)
    assert T.offered(longer)[0] == 2 * T.offered(a)[0]


@pytest.mark.parametrize('name', OPEN)
@pytest.mark.parametrize('seconds', (10.0, 20.0, 45.0))
def test_open_loop_window_holds_exactly_rate_times_seconds(name, seconds):
    tr = SPEC.data('traffic', name)
    reqs = T.open_loop_schedule(tr, seconds)
    win = [r for r in reqs if r.region == 'window']
    assert len(win) == round(tr['rate_per_s'] * seconds)
    assert all(0.0 < r.due < seconds for r in win)
    assert all(r.due < 0.0 for r in reqs if r.region == 'warm')
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    # the gaps of a region always sum to the region's length
    last_warm = max(r.due for r in reqs if r.region == 'warm')
    assert last_warm == pytest.approx(0.0, abs=1e-6)


def test_arrival_gaps_are_the_stratified_exponential_quantiles():
    gaps = T.arrival_gaps(4.0, 1000, 250.0)
    assert gaps.sum() == pytest.approx(250.0)
    assert np.all(np.diff(gaps) > 0)
    # mean 1/rate, and the exponential's coefficient of variation (~1)
    assert gaps.mean() == pytest.approx(0.25)
    assert 0.9 < gaps.std() / gaps.mean() < 1.05


@pytest.mark.parametrize('name', BACKLOG)
def test_backlog_is_one_schedule_and_twice_what_the_window_can_finish(name):
    tr = SPEC.data('traffic', name)
    first = T.backlog_schedule(tr, 30.0, tr['slots'])
    assert _rows(first) == _rows(T.backlog_schedule(tr, 30.0, tr['slots']))
    assert [r.index for r in first] == list(range(len(first)))
    assert sum(r.region == 'warm' for r in first) == tr['slots']
    n_window = sum(r.region == 'window' for r in first)
    assert n_window >= 2 * tr['finish_per_s_ceiling'] * 30.0
    assert all(r.prompt_len + r.output_len <= tr['max_length']
               for r in first)
    # the order is not the sorted one: long and short prompts mix
    prompts = [r.prompt_len for r in first if r.region == 'window']
    assert prompts != sorted(prompts)


@pytest.mark.parametrize('dist,lo,hi', [
    ({'kind': 'lognormal', 'median': 160, 'sigma': 0.8, 'min': 16,
      'max': 768}, 16, 768),
    ({'kind': 'uniform', 'min': 1024, 'max': 3072}, 1024, 3072),
    ({'kind': 'fixed', 'value': 7}, 7, 7)])
def test_quantiles_are_stratified_and_clipped(dist, lo, hi):
    q = T.quantiles(dist, 200)
    assert len(q) == 200 and q.min() >= lo and q.max() <= hi
    assert np.all(np.diff(q) >= 0)
    if dist['kind'] == 'lognormal':
        assert abs(int(np.median(q)) - dist['median']) <= 2


@pytest.mark.parametrize('seed', SEEDS)
def test_prompt_tokens_come_from_the_seed_and_take_a_large_one(seed):
    a = T.prompt_tokens(seed, 3, 64, 50304)
    b = T.prompt_tokens(seed, 3, 64, 50304)
    c = T.prompt_tokens(seed + 1, 3, 64, 50304)
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 3 and a.max() < 50304


@pytest.mark.parametrize('name', OPEN)
def test_every_block_of_an_open_loop_carries_its_share(name):
    tr = SPEC.data('traffic', name)
    seconds = 45.0
    win = [r for r in T.open_loop_schedule(tr, seconds)
           if r.region == 'window']
    n, size = len(win), T._BLOCK
    sets = []
    for b, start in enumerate(range(0, n, size)):
        block = win[start:start + size]
        m = len(block)
        # the block's lengths are the stratified pairs at its own offset
        want = collections.Counter(
            map(tuple, T.length_pairs(tr, m, T._offset(b)).tolist()))
        assert _multiset(block) == want
        # and its gaps sum to its share of the window
        assert block[-1].due == pytest.approx(
            seconds * (start + m) / n, abs=1e-6)
        sets.append(tuple(sorted(r.prompt_len for r in block)))
    # blocks differ from one another: together they stratify finely
    assert len(set(sets)) > 1 or len(sets) == 1
