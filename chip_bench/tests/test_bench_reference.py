"""The bit-parallel reference (``Reference.levels``) against the plain one
(``Reference.bfs``), the comparison's counts against the comparison one
source at a time, and the compared rows as the harness stores them."""
import numpy as np
import pytest

from chip_bench import harness, oracle

N = 300
LIVE = 270  # nodes past it have no edge: isolated


def _reference(symmetric, row_cap=None, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, LIVE, 2 * N)
    dst = rng.integers(0, LIVE, 2 * N)
    if symmetric:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    return oracle.Reference(N, src, dst, row_cap=row_cap)


def _sources(k, seed=1):
    s = np.random.default_rng(seed).integers(0, LIVE, k)
    if k > 1:
        s[0] = N - 1  # isolated
    if k > 2:
        s[-1] = s[1]  # a duplicate source
    return s


@pytest.mark.parametrize("k", [1, 63, 64, 65, 130])
@pytest.mark.parametrize("row_cap", [None, 2])
@pytest.mark.parametrize("symmetric", [False, True])
def test_levels_equal_bfs_row_for_row(symmetric, row_cap, k):
    ref = _reference(symmetric, row_cap)
    sources = _sources(k)
    got = ref.levels(sources)
    assert got.dtype == np.int32 and got.shape == (k, N)
    for s, row in zip(sources, got):
        np.testing.assert_array_equal(row, ref.bfs(int(s)))
    # the cases hold levels past the first and nodes no source reaches
    assert got.max() >= 3 and (got < 0).any()


def test_levels_past_255_on_a_directed_path():
    n = 600
    ref = oracle.Reference(n, np.arange(n - 1), np.arange(1, n))
    sources = [0, 5, n - 1]
    got = ref.levels(sources)
    for s, row in zip(sources, got):
        np.testing.assert_array_equal(row, ref.bfs(s))
    assert got[0, -1] == n - 1


def _one_source_at_a_time(reference, sources, rows):
    """The comparison as the reference made it before ``levels``."""
    rows = np.asarray(rows)
    if rows.shape != (len(sources), reference.n_nodes):
        return len(sources) * reference.n_nodes
    return int(sum(np.count_nonzero(row != reference.bfs(int(s)))
                   for s, row in zip(sources, rows)))


@pytest.mark.parametrize("threads", [1, 3])
def test_mismatch_counts_are_unchanged(threads, monkeypatch):
    monkeypatch.setattr(oracle, "THREADS", threads)
    ref = _reference(True)
    capped = _reference(True, row_cap=2)
    rng = np.random.default_rng(2)
    sources = ([rng.integers(0, N, 1) for _ in range(70)]
               + [rng.integers(0, N, 64) for _ in range(3)]
               + [rng.integers(0, N, 5)])
    rows = []
    for q, s in enumerate(sources):
        if q % 3 == 0:  # the control's rows: wrong
            rows.append(np.stack([capped.bfs(int(v)) for v in s]))
        elif q % 3 == 1:  # exact, stored narrow
            rows.append(harness.narrow(np.stack([ref.bfs(int(v)) for v in s])))
        else:  # exact, one entry altered
            r = np.stack([ref.bfs(int(v)) for v in s])
            r[0, 7] += 1
            rows.append(r)
    rows[4] = rows[4][:, :-1]  # the wrong shape
    want = [_one_source_at_a_time(ref, s, r) for s, r in zip(sources, rows)]
    assert oracle.mismatches_by_query(ref, sources, rows) == want
    assert [oracle.mismatches(ref, s, r)
            for s, r in zip(sources, rows)] == want
    assert want[4] == N and sum(want) > 0
    control = sum(_one_source_at_a_time(ref, s, [capped.bfs(int(v))
                                                 for v in s])
                  for s in sources)
    assert oracle.control_mismatches(ref, capped, sources) == control > 0


@pytest.mark.parametrize("row,dtype", [
    (np.array([[0, 3, -1, 32767]], np.int32), np.int16),
    (np.array([[0, 3, -32768]], np.int64), np.int16),
    (np.array([[0, 3, np.iinfo(np.int32).max]], np.int32), np.int32),
    (np.array([[0, -32769]], np.int32), np.int32),
    (np.array([[0.0, 1.5]]), np.float64),
    (np.zeros((0, 4), np.int32), np.int32),
])
def test_rows_are_stored_narrow_only_where_every_value_fits(row, dtype):
    rec = harness.Recorder(lambda: 0.0, seed=0, sources_per_query=1)
    rec.loop = type("Loop", (), {"results": {}})()
    rec.window.add("q")
    rec("q", row)
    kept = rec.rows["q"]
    assert kept.dtype == dtype
    np.testing.assert_array_equal(kept, row)
    assert kept is not row
