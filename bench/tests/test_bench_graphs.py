"""The benchmark's generator, work counts, peaks and plain reference, on
the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from bench import graphs, reference

PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

# A hand-made sampled graph: row lengths 1, 3, 5 and 12 at W = 4 keep
# 1 + 3 + 4 + 4 = 12 live slots.
ROW_NNZ = np.array([1, 3, 5, 12])


def test_sampled_edges_by_hand():
    assert graphs.sampled_edges(ROW_NNZ, 4) == 12
    assert graphs.sampled_edges(ROW_NNZ, 128) == 21


def test_spmm_counts_by_hand():
    # 4 nodes, F = 3, 12 live slots: B 4*3*4 = 48 bytes, the operand
    # 12 * (4 + 4) = 96, C 48: 192 bytes; 2 * 12 * 3 = 72 FLOPs
    assert graphs.spmm_bytes(4, 3, 12, 4) == 192
    assert graphs.spmm_bytes(4, 3, 12, 1) == 12 + 96 + 48
    assert graphs.spmm_flops(3, 12) == 72


def test_forward_counts_by_hand():
    # X 4*3*4 = 48, weights 10 floats = 40, operand 96, logits 4*2*4 = 32
    assert graphs.forward_bytes(4, 3, 2, 12, 10, 4) == 48 + 40 + 96 + 32
    from bench.models import gcn, graphsage

    # GCN: 2*12*(3+5) = 192 for the aggregations, 2*4*(3*5 + 5*2) = 200
    assert gcn.flops(4, 3, 5, 2, 12) == 192 + 200
    assert graphsage.flops(4, 3, 5, 2, 12) == 192 + 400


@pytest.mark.parametrize("flops,nbytes", [(1000.0, 1.0), (1.0, 1000.0)])
def test_time_at_the_bound_is_exactly_a_full_share(flops, nbytes):
    least = graphs.least_time(flops, nbytes, PEAK)
    assert least == max(flops / 100.0, nbytes / 10.0)
    assert graphs.share(least, least) == 100.0
    assert graphs.share(least, 2 * least) == 50.0
    assert graphs.share(least, 0.0) is None


def test_a_kernel_that_reads_each_byte_once_stays_within_its_roofline():
    """Bytes moved at peak bandwidth by a kernel that reads B and the
    sampled operand once and writes C once take at least the least time."""
    n, f, e = 1000, 128, 14000
    moved = n * f * 4 + e * 8 + n * f * 4
    peak = graphs.PEAKS["TPU v5 lite"]
    t = moved / peak["hbm_bytes_per_s"]
    least = graphs.least_time(graphs.spmm_flops(f, e),
                              graphs.spmm_bytes(n, f, e, 4), peak)
    assert graphs.share(least, t) <= 100.0


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        graphs.peaks("cpu")
    assert graphs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


SMALL = {"nodes": 300, "avg_degree": 13.7, "skew": 1.6, "classes": 4,
         "feat_dim": 8, "homophily": 0.82, "feat_noise": 2.5,
         "degree_seed": 0}


@pytest.mark.parametrize("normalize", ["gcn", "mean"])
def test_graph_work_is_fixed_and_content_follows_the_seed(normalize):
    a = graphs.make_graph(SMALL, 2**33 + 1, normalize)
    b = graphs.make_graph(SMALL, 2**33 + 1, normalize)
    c = graphs.make_graph(SMALL, 5, normalize)
    np.testing.assert_array_equal(a.col_ind, b.col_ind)
    np.testing.assert_array_equal(a.row_ptr, c.row_ptr)
    assert not np.array_equal(a.col_ind, c.col_ind)
    deg = graphs.degree_sequence(SMALL)
    loops = 1 if normalize == "gcn" else 0
    np.testing.assert_array_equal(a.row_nnz, deg + loops)
    if normalize == "mean":
        np.testing.assert_allclose(
            np.add.reduceat(a.val, a.row_ptr[:-1]), 1.0, rtol=1e-6)


def test_aes_sample_by_hand():
    """nnz 12 at W 8 falls in Table 1's first band: N = 2, cnt = 4, and
    sample i starts at (1429 i) mod 11: 0, 10, 9, 8."""
    row_ptr = np.array([0, 12])
    col = np.arange(100, 112)
    val = np.arange(12, dtype=np.float32) + 1
    v, c = reference.aes_sample(row_ptr, col, val, 8)
    np.testing.assert_array_equal(c[0] - 100, [0, 10, 9, 8, 1, 11, 10, 9])
    np.testing.assert_array_equal(v[0], c[0] - 99)


def test_aes_sample_agrees_with_the_programs_sampler():
    """A second witness: the program's jnp sampler makes the same operand
    as the reference on a skewed graph with rows past W."""
    import jax.numpy as jnp

    from repro.core.sampling import sample_csr_to_ell

    g = graphs.make_graph(SMALL, 3, "gcn")
    assert g.row_nnz.max() > 16
    v, c = reference.aes_sample(g.row_ptr, g.col_ind, g.val, 16)
    pv, pc = sample_csr_to_ell(jnp.asarray(g.row_ptr),
                               jnp.asarray(g.col_ind),
                               jnp.asarray(g.val), 16)
    np.testing.assert_array_equal(c, np.asarray(pc))
    np.testing.assert_array_equal(v, np.asarray(pv))


def test_quantize_agrees_with_the_programs_eq1():
    import jax.numpy as jnp

    from repro.core.quantization import quantize

    x = np.random.default_rng(0).normal(size=(64, 33)).astype(np.float32)
    mine = reference.quantize(x, 8)
    theirs = quantize(jnp.asarray(x), 8)
    np.testing.assert_array_equal(mine.levels, np.asarray(theirs.q))
    assert np.max(np.abs(mine.dequantize() - x)) <= mine.scale / 2 * 1.001


def test_requant_guard_follows_the_documented_rules():
    x = np.linspace(-1, 1, 101, dtype=np.float32)
    stored = reference.quantize(x, 8)
    # the matrix the range came from re-encodes to itself
    np.testing.assert_array_equal(reference.requant_guard(
        stored, stored.dequantize()), stored.dequantize())
    # out of range by more than half a step: served as float
    far = x * 3
    np.testing.assert_array_equal(reference.requant_guard(stored, far), far)
    # in range but shrunk past a quarter of the span: a fresh range
    narrow = x * 0.25
    got = reference.requant_guard(stored, narrow)
    np.testing.assert_allclose(got, reference.quantize(
        narrow, 8).dequantize())


def test_high_precision_keeps_sixteen_bits():
    x = np.random.default_rng(1).normal(size=1000).astype(np.float32)
    hi, lo = reference.bf16_split(x)
    assert np.all((hi.view(np.uint32) & 0xFFFF) == 0)
    assert np.all((lo.view(np.uint32) & 0xFFFF) == 0)
    rel = np.abs((hi.astype(np.float64) + lo) - x) / np.abs(x)
    assert rel.max() < 2.0 ** -16
    a = np.random.default_rng(2).normal(size=(50, 40)).astype(np.float32)
    b = np.random.default_rng(3).normal(size=(40, 30)).astype(np.float32)
    exact = reference.Arith("highest").matmul(a, b)
    high = reference.Arith("high").matmul(a, b)
    gap = np.max(np.abs(high - exact)) / np.max(np.abs(exact))
    assert 1e-8 < gap < 1e-4


@pytest.mark.parametrize("config", ["gcn-ogbn-arxiv", "graphsage-pubmed"])
def test_degrees_sum_to_the_configured_edges_under_the_cap(config):
    import json

    from bench import run

    with open(run.BENCH / "configs" / f"{config}.json") as f:
        g = json.load(f)["graph"]
    deg = graphs.degree_sequence(g)
    assert deg.shape == (g["nodes"],)
    assert deg.sum() == round(g["nodes"] * g["avg_degree"])
    assert deg.min() >= 1
    assert deg.max() <= g.get("max_degree", g["nodes"] - 1)
    # the tail reaches past W, so the sampler has rows to sample
    assert (deg > 128).any()


def test_a_level_flip_reads_one_step_at_the_boundary():
    from bench import run

    x = np.array([[0.0, 0.5 / 255 + 1e-9, 1.0]], np.float32)
    stored = reference.quantize(x, 8)
    want = {"input": stored.dequantize().astype(np.float64),
            "stored": stored}
    got = stored.dequantize().copy()
    got[0, 1] -= stored.scale            # the program rounded it down
    d = run.input_diagnostics(got, want, x)
    assert d["input_level_flips"] == 1
    assert d["input_flip_steps_max"] == pytest.approx(1.0)
    assert d["input_flip_boundary_max"] < 1e-5
    assert d["input_other_gap_max"] == 0.0
