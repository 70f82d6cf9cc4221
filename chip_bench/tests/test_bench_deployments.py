"""The copied generators give the committed deployments' counts, and the
reference BFS and its control behave as stated."""
import json

import numpy as np
import pytest

from chip_bench import graphs, harness, oracle
from chip_bench.peaks import peaks_for


def test_generated_counts_match_the_config():
    cfg = json.loads((harness.BENCH_DIR / "configs" / "ldbc-knows.json")
                     .read_text())
    src, dst = graphs.structural_edges(cfg)
    ref = oracle.Reference(cfg["n_nodes"], src, dst)
    assert ref.n_nodes == cfg["n_nodes"]
    assert int(ref.indptr[-1]) == cfg["n_edges"]
    assert int(ref.degrees.max()) == cfg["max_out_degree"]


def test_ldbc_counts_at_seed_0_keep_the_published_degree():
    s, d = graphs.powerlaw_edges(4486, 34.7, 1.8, seed=0)
    ref = oracle.Reference(4486, s, d)
    assert (int(ref.indptr[-1]), int(ref.degrees.max())) == (199212, 3573)
    published = 19941198 / 448626
    assert abs(int(ref.indptr[-1]) / 4486 - published) < 0.01 * published


def test_relabelling_keeps_the_degree_sequence():
    cfg = {"generator": "powerlaw", "n_nodes": 500,
           "avg_degree_per_direction": 5.0, "alpha": 1.8, "symmetric": True,
           "graph_seed": 0}
    s, d = graphs.structural_edges(cfg)
    base = oracle.Reference(500, s, d)
    for seed in (1, 2**31 + 3):
        r = graphs.relabelling(cfg, seed)
        moved = oracle.Reference(500, r[s], r[d])
        np.testing.assert_array_equal(moved.degrees[r], base.degrees)
        np.testing.assert_array_equal(moved.bfs(int(r[7]))[r], base.bfs(7))


def test_reference_bfs_on_a_small_graph():
    # 0->1->2->3, 0->4, 5 isolated
    src = np.array([0, 1, 2, 0, 1])
    dst = np.array([1, 2, 3, 4, 2])  # duplicate edge 1->2 merges
    ref = oracle.Reference(6, src, dst)
    np.testing.assert_array_equal(ref.bfs(0), [0, 1, 2, 3, 1, -1])
    assert ref.traversed_edges(ref.bfs(0)) == 4
    assert oracle.mismatches(ref, [0], [ref.bfs(0)]) == 0
    assert oracle.mismatches(ref, [0], [[0, 1, 2, 3, 2, -1]]) == 1
    assert oracle.mismatches(ref, [0, 1], [ref.bfs(0)]) == 12  # wrong shape


def test_control_cap_drops_slots_past_the_cap():
    n = 200
    src = np.zeros(n - 1, np.int64)
    dst = np.arange(1, n)
    capped = oracle.Reference(n, src, dst, row_cap=oracle.CONTROL_ROW_CAP)
    assert int(capped.degrees[0]) == oracle.CONTROL_ROW_CAP
    full = oracle.Reference(n, src, dst)
    assert oracle.mismatches(full, [0], [capped.bfs(0)]) == \
        n - 1 - oracle.CONTROL_ROW_CAP


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
