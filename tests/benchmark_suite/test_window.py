"""Rules A.2-A.4 on synthetic timestamps."""
import pytest

from benchmarks import window as W


def test_train_rates_whole_units_over_the_time_they_took():
    # 8-step intervals of 2.0 s, one with a 1 s host stall
    stamps = [2.0, 4.0, 7.0, 9.0, 11.0]
    r = W.train_rates(0.0, stamps, tokens_per_interval=32768, chips=1)
    assert r['median_interval_s'] == pytest.approx(2.0)
    # the end-to-end figure sees the one stall, and divides by the time
    # the five intervals took (11 s), never by a nominal window ...
    assert r['tokens_per_s_chip'] == pytest.approx(5 * 32768 / 11.0)
    # ... the median-interval rate beside it is blind to it
    assert r['steady_tokens_per_s_chip'] == pytest.approx(16384.0)
    assert r['window_s'] == pytest.approx(11.0)


def test_train_rates_per_chip_and_bad_stamps():
    r = W.train_rates(10.0, [12.0, 14.0], 16384 * 4, chips=4)
    assert r['tokens_per_s_chip'] == pytest.approx(16384 * 4 / 2.0 / 4)
    assert r['steady_tokens_per_s_chip'] == r['tokens_per_s_chip']
    with pytest.raises(ValueError):
        W.train_rates(0.0, [], 1, 1)
    with pytest.raises(ValueError):
        W.train_rates(0.0, [1.0, 1.0], 1, 1)


def test_window_closes_at_the_first_sync_at_or_after_seconds():
    assert not W.closes(100.0, 144.9, 45.0)
    assert W.closes(100.0, 145.0, 45.0)
    assert W.closes(100.0, 146.3, 45.0)


def _emissions():
    em = W.Emissions()
    # request 0: started before the window, still running at its close
    for s in (9.0, 9.5, 10.5, 11.0, 12.5):
        em.note(0, s, 4)
    # request 1: due at 10.2, first tokens at 10.6, finished inside
    for s, n in ((10.6, 1), (10.8, 4), (11.2, 3)):
        em.note(1, s, n)
    em.note(2, 13.0, 4)         # after the window
    return em


def test_emitted_tokens_count_finished_and_unfinished_alike():
    em = _emissions()
    # window (10, 12]: request 0 emits at 10.5 and 11.0, request 1 all
    assert W.emitted_tokens(em, 10.0, 12.0) == 8 + 8
    assert W.out_tokens_per_s(em, 10.0, 12.0) == pytest.approx(8.0)
    assert W.per_second_tokens(em, 10.0, 12.0) == [9, 7]


def test_ttft_counts_from_the_due_instant_and_failures_are_the_worst():
    em = _emissions()

    class R:
        def __init__(self, index, due):
            self.index, self.due = index, due
    reqs = [R(1, 10.2), R(5, 11.0), R(6, 11.5)]
    ttft = W.ttft_samples(reqs, em, 10.0, 14.0, failed={6})
    assert ttft[0] == pytest.approx(0.4)        # 10.6 - 10.2, not - submit
    assert ttft[1] == pytest.approx(3.0)        # never answered: to the end
    assert ttft[2] == pytest.approx(2.5)        # failed: to the end


def test_tpot_is_the_mean_gap_of_in_window_emissions():
    em = _emissions()
    tp = sorted(W.tpot_samples(em, 10.0, 12.0))
    # request 0: stamps 10.5 and 11.0, 4 tokens after the first stamp
    # request 1: stamps 10.6..11.2, 7 tokens after the first stamp
    assert tp == pytest.approx(sorted([0.5 / 4, 0.6 / 7]))


@pytest.mark.parametrize('q,want', [(0, 1.0), (50, 2.5), (95, 3.85),
                                    (100, 4.0)])
def test_percentile(q, want):
    assert W.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert W.percentile([], q) is None
