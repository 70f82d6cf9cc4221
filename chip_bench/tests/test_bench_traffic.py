"""The traffic generator: deterministic in the seed, sources drawn from
it apart from the warm-up's, every seed the same gaps in another order."""
import numpy as np
import pytest

from chip_bench import traffic

DEGREES = np.array([0, 3, 1, 0, 2, 5, 1, 0, 4, 2])


def _deck(seed, stream=1, k=1):
    mix = {"kind": "closed", "sources_per_query": k}
    relabel = np.random.default_rng(seed).permutation(len(DEGREES))
    return traffic.SourceDeck(mix, DEGREES, relabel, seed, stream), relabel


def _dealt(deck, n=64):
    return np.stack([deck.deal() for _ in range(n)])


def test_deck_is_deterministic_in_the_seed():
    a, _ = _deck(7)
    b, _ = _deck(7)
    np.testing.assert_array_equal(_dealt(a), _dealt(b))


def test_sources_are_drawn_from_the_seed_among_nodes_with_out_edges():
    dealt = []
    for seed in (1, 2**31 + 11):
        deck, relabel = _deck(seed, k=3)
        rows = _dealt(deck)
        assert rows.shape == (64, 3)
        structural = np.argsort(relabel)[rows]
        assert (DEGREES[structural] > 0).all()
        dealt.append(structural)
    assert not np.array_equal(dealt[0], dealt[1])


def test_warm_up_and_window_draw_apart():
    warm, _ = _deck(2**33 + 5, stream=0)
    window, _ = _deck(2**33 + 5, stream=1)
    assert not np.array_equal(_dealt(warm), _dealt(window))


def test_poisson_arrivals_fixed_count_sorted_and_seeded():
    mix = {"rate_qps": 2.5}
    a = traffic.arrival_offsets(mix, 40.0, 9)
    assert len(a) == 100 and (np.diff(a) >= 0).all()
    assert 0.0 <= a[0] and a[-1] < 40.0
    np.testing.assert_array_equal(a, traffic.arrival_offsets(mix, 40.0, 9))
    assert not np.array_equal(a, traffic.arrival_offsets(mix, 40.0, 10))


def test_every_seed_offers_the_same_gaps_in_another_order():
    mix = {"rate_qps": 3.0}
    gaps = [np.sort(np.diff(traffic.arrival_offsets(mix, 50.0, s),
                            prepend=0.0))
            for s in (4, 2**32 + 9)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    # exponential quantiles: mean gap 1/rate, median ln 2/rate
    assert gaps[0].mean() == pytest.approx(50.0 / 151, rel=1e-9)
    assert np.median(gaps[0]) == pytest.approx(np.log(2) / 3.0, rel=0.02)


@pytest.mark.parametrize("name", ["poisson-1src-ldbc", "closed-64src"])
def test_committed_mixes_load(name):
    mix = traffic.load(name)
    assert mix["kind"] in traffic.KINDS
    assert mix["sources_per_query"] >= 1


def test_unknown_kind_is_refused(tmp_path):
    (tmp_path / "odd.json").write_text('{"kind": "sometimes"}')
    with pytest.raises(ValueError):
        traffic.load("odd", tmp_path)
