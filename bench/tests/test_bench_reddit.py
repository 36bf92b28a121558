"""GCN on Reddit: the configuration of the cell ``gcn-reddit.fwd-f32``
(``bench/configs/gcn-reddit.json``: 232,965 nodes, 114,615,892 edges,
F = 602, 41 classes; skew and cap assumed) and its calibrated limits
(``bench/checks/gcn-reddit.fwd-f32.json``), checked on the CPU through the
program's normal path (Pallas in interpret mode) against the float64
reference.

The Reddit-shaped graph keeps the configuration's skew, classes and
widths at 2,000 nodes and an average degree of 60.  At W = 16 it keeps
Reddit's ratio of average degree to W (3.8), so 70% of its rows are over
W (Reddit at W = 128: 72.7%) and fall in every band of Table 1, and many
rows at or under W take a DMA of their own because their block's staged
window covers only the first rows of the block.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import graphs, reference, run

CELL = "gcn-reddit.fwd-f32"
CONFIG = run.spec_of(CELL)["config"]
SEED = 2**33 + 4242
W = 16


def quiet(*_a, **_k):
    pass


def bands(row_nnz, sh_width: int) -> list:
    """Rows over ``sh_width`` in each band of Table 1 above R = 1
    (``reference.BANDS``)."""
    over = row_nnz > sh_width
    edges = [sh_width] + [t * sh_width for t, _, _ in reference.BANDS[:-1]] \
        + [np.inf]
    return [int((over & (row_nnz > lo) & (row_nnz <= hi)).sum())
            for lo, hi in zip(edges, edges[1:])]


def reddit_shaped() -> dict:
    spec = run.spec_of(CELL)
    spec["config"]["sh_width"] = W
    spec["config"]["graph"].update(nodes=2000, avg_degree=60.0)
    return spec


def test_reddits_graph_group_has_the_published_edges():
    """The configuration's graph, and what its ``assumed`` list states."""
    g = CONFIG["graph"]
    assert (CONFIG["model"], CONFIG["sh_width"], g["feat_dim"],
            g["classes"]) == ("gcn", 128, 602, 41)
    deg = graphs.degree_sequence(g)
    assert deg.shape == (232_965,) and deg.sum() == 114_615_892
    assert deg.min() >= 1 and deg.max() == g["max_degree"]
    assert int((deg == g["max_degree"]).sum()) == 226
    assert np.median(deg) == 215
    nnz = deg + 1                                    # GCN's self loops
    assert int((nnz > 128).sum()) == 169_288         # 72.7% of rows
    assert bands(nnz, 128) == [68_739, 98_201, 1_078, 1_270]
    assert graphs.sampled_edges(nnz, 128) == 27_778_417


def test_the_reddit_shaped_graph_meets_every_sampler_path():
    import jax.numpy as jnp

    from repro.kernels.aes_sample import row_paths

    g = reddit_shaped()["config"]["graph"]
    nnz = graphs.degree_sequence(g) + 1
    assert all(b > 0 for b in bands(nnz, W)), bands(nnz, W)
    row_ptr = jnp.asarray(np.concatenate([[0], np.cumsum(nnz)]), jnp.int32)
    whole, sampled, own = (int(c) for c in row_paths(row_ptr, W))
    assert sampled == int((nnz > W).sum()) and whole > 0 and own > 0


def test_gcn_on_a_reddit_shaped_graph_agrees_with_the_reference():
    """The harness's own run: GCN through ``make_sampled_agg(16, "aes",
    "pallas")`` on seeded random weights, compared with the float64
    reference under the Reddit cell's limits; the reference at ``high``
    precision in the program's place fails them."""
    r = run.run_cell(reddit_shaped(), SEED, 0.0, False, require_chip=False,
                     control=True, log=quiet)
    assert r["correct"], r["checks"]
    assert not run.passed(r["control"]), r["control"]


@pytest.mark.parametrize("stage", ["agg1", "agg2", "logits"])
def test_an_altered_answer_on_a_reddit_shaped_graph_is_caught(stage):
    """The cell's limits catch one row moved by 1 in a checked stage."""
    def fault(name, a):
        return a.at[0].add(1.0) if name == stage else a

    r = run.run_cell(reddit_shaped(), SEED, 0.0, False, require_chip=False,
                     log=quiet, fault=fault)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]
