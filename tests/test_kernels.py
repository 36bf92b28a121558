"""Per-kernel shape/dtype sweeps, each asserted allclose vs the ref.py
pure-jnp oracle (interpret mode executes kernel bodies on CPU)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import CSR, ELL
from repro.core.quantization import dequantize, quantize
from repro.core.sampling import sample_csr_to_ell
from repro.kernels import ops, ref

from conftest import random_csr


def _ell(g: CSR, W: int) -> ELL:
    val, col = sample_csr_to_ell(g.row_ptr, g.col_ind, g.val, W)
    return ELL(val, col, g.num_cols)


@pytest.mark.parametrize("n,feat,W,block_r,block_f", [
    (8, 128, 8, 8, 128),       # exact tiles
    (37, 33, 16, 8, 128),      # ragged everything
    (64, 256, 4, 16, 128),     # wide features
    (130, 64, 32, 8, 32),      # small feature blocks
    (16, 128, 1, 4, 128),      # W=1 degenerate
])
def test_ell_spmm_shape_sweep(rng, n, feat, W, block_r, block_f):
    g = random_csr(rng, n, 5.0, skew=1.0)
    b = jnp.asarray(rng.normal(size=(n, feat)).astype(np.float32))
    ell = _ell(g, W)
    want = ref.ell_spmm_rowloop(ell.val, ell.col, b)
    got = ops.ell_spmm(ell, b, block_r=block_r, block_f=block_f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_spmm_dtype_sweep(rng, dtype):
    g = random_csr(rng, 24, 4.0)
    b = jnp.asarray(rng.normal(size=(24, 64))).astype(dtype)
    ell = _ell(g, 8)
    want = ref.ell_spmm_rowloop(ell.val, ell.col, b.astype(jnp.float32))
    got = ops.ell_spmm(ell, b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def _ell_of_widths(rng, live, n: int, W: int):
    """An ELL operand whose row ``r`` has ``live[r]`` random edges into
    ``n`` nodes (dead slots zeroed), rows padded to tiles of 8."""
    rows = -(-len(live) // 8) * 8
    live = np.pad(np.asarray(live, np.int32), (0, rows - len(live)))
    col = np.zeros((rows, W), np.int32)
    val = np.zeros((rows, W), np.float32)
    for r, k in enumerate(live):
        col[r, :k] = rng.integers(0, n, k)
        val[r, :k] = rng.normal(size=k)
    return jnp.asarray(val), jnp.asarray(col), jnp.asarray(live)


@pytest.mark.parametrize("feat,quant,tiles", [
    pytest.param(200, False, 2, id="f32-2tiles"),
    pytest.param(300, False, 3, id="f32-3tiles"),
    pytest.param(500, False, 4, id="f32-4tiles"),
    pytest.param(602, False, 5, id="f32-5tiles"),
    pytest.param(600, True, 2, id="uint8-2packed"),
])
def test_ring_gather_equals_the_tile_loop_bit_for_bit(rng, feat, quant,
                                                      tiles):
    """Where a row spans several lane tiles ``ell_spmm`` gathers it with
    the DMA ring; its sums equal the per-tile two-slot loop's bit for bit,
    at live widths 0, 1, D - 1, D, D + 1 and W (D = ``RING_DEPTH``)."""
    from repro.kernels import ell_spmm
    from repro.kernels.gather import tile_features

    d, n = ell_spmm.RING_DEPTH, 40
    W = d + 5
    val, col, live = _ell_of_widths(rng, [0, 1, d - 1, d, d + 1, W, 3, 0,
                                          W, d, 2], n, W)
    if quant:
        x = rng.integers(0, 256, (n, feat)).astype(np.uint8)
        kw = dict(scale=0.25, x_min=-3.0)
    else:
        x = rng.normal(size=(n, feat)).astype(np.float32)
        kw = dict(scale=1.0, x_min=0.0)
    b = tile_features(jnp.asarray(x))
    assert b.tiles.shape[0] == tiles and ell_spmm.ring_gather(b)
    got = ell_spmm.ell_spmm(val, col, live, b, **kw)
    want = ell_spmm._launch(val, col, live, b, ring=False, block_r=8,
                            interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))
    xd = (x * kw["scale"] + kw["x_min"]) if quant else x
    np.testing.assert_allclose(
        np.asarray(got)[:, :feat],
        np.asarray(ref.ell_spmm_rowloop(val, col, jnp.asarray(
            xd, jnp.float32))), rtol=1e-4, atol=1e-4)


def _banded_csr(rng, W: int, rows: int, block_r: int) -> CSR:
    """Rows of every Table-1 band (nnz 0, 1, W-1, W, W+1, 2W+1, 36W+1,
    54W+1) among random rows at or under W; row 1 is a hub longer than
    its block's staged window, so the short rows after it in that block
    take their own DMA."""
    from repro.kernels.aes_sample import geometry

    _, window = geometry(rows, W, block_r)
    deg = rng.integers(0, W + 1, rows)
    deg[2:6] = [2, W - 1, W, 0]
    deg[block_r:block_r + 5] = [W + 1, 2 * W + 1, 36 * W + 1, 54 * W + 1, W]
    # the hub's length puts row 2's two entries across a 1024-entry
    # boundary; the last band row's puts row block_r + 4 across a lane
    # tile only
    deg[1] = window * 128 + 300 + (1023 - deg[0] - window * 128 - 300) % 1024
    start = deg[:block_r + 3].sum() + 54 * W + 1
    pad = (126 - start) % 128
    deg[block_r + 3] += pad + (128 if (start + pad) % 1024 == 1022 else 0)
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    nnz = int(row_ptr[-1])
    return CSR(jnp.asarray(row_ptr),
               jnp.asarray(rng.integers(0, rows, nnz).astype(np.int32)),
               jnp.asarray(rng.normal(size=nnz).astype(np.float32)), rows)


@pytest.mark.parametrize("W,block_r,banded", [
    pytest.param(4, None, False, id="4"),
    pytest.param(16, None, False, id="16"),
    pytest.param(64, None, False, id="64"),
    pytest.param(4, 8, True, id="banded-W4"),
    pytest.param(16, 16, True, id="banded-W16"),
    pytest.param(64, 8, True, id="banded-W64"),
    pytest.param(128, 16, True, id="banded-W128"),
    pytest.param(256, 8, True, id="banded-W256"),
])
def test_aes_sample_kernel_matches_jax_sampler(rng, W, block_r, banded):
    """The kernel's ELL is ``sample_csr_to_ell``'s, bit for bit.  The
    banded graphs (44 rows: not a multiple of ``block_r``) hold every
    Table-1 band, rows after a hub that take their own DMA, and short
    runs across a 128-lane and a 1024-entry boundary."""
    from repro.kernels.aes_sample import row_paths

    g = _banded_csr(rng, W, 44, block_r) if banded \
        else random_csr(rng, 40, 12.0, skew=0.8)
    want_val, want_col = sample_csr_to_ell(g.row_ptr, g.col_ind, g.val, W)
    got = ops.aes_sample(g, W, block_r=block_r)
    np.testing.assert_array_equal(np.asarray(got.col), np.asarray(want_col))
    np.testing.assert_array_equal(np.asarray(got.val).view(np.int32),
                                  np.asarray(want_val).view(np.int32))
    if banded:
        rp = np.asarray(g.row_ptr)
        short = (rp[1:] - rp[:-1] <= W) & (rp[1:] - rp[:-1] > 1)
        lane, flat = (rp[:-1] // t != (rp[1:] - 1) // t
                      for t in (128, 1024))
        assert (short & flat).any() and (short & lane & ~flat).any()
        whole, sampled, own = row_paths(g.row_ptr, W, block_r)
        assert int(sampled) == 5 and int(whole) == 39 and int(own) >= 4


@pytest.mark.parametrize("n,feat,W", [(8, 128, 8), (37, 60, 16), (72, 32, 32)])
def test_fused_kernel_matches_end_to_end_oracle(rng, n, feat, W):
    g = random_csr(rng, n, 9.0, skew=0.8)
    b = jnp.asarray(rng.normal(size=(n, feat)).astype(np.float32))
    want = ref.aes_spmm(g.row_ptr, g.col_ind, g.val, b, sh_width=W)
    got = ops.fused_aes_spmm(g, b, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 128), (256, 128), (100, 33), (1, 1)])
@pytest.mark.parametrize("bits", [8, 16])
def test_dequant_kernel_sweep(shape, bits):
    x = np.random.default_rng(3).normal(size=shape).astype(np.float32) * 5
    qf = quantize(x, bits)
    want = ref.dequantize(qf.q, qf.x_min, qf.x_max, bits)
    got = ops.dequantize(qf.q, qf.scale, qf.x_min, bits=bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_quantized_fused_gather(rng):
    """Beyond-paper kernel: INT8 B + in-gather dequant == dequant-then-spmm."""
    g = random_csr(rng, 48, 6.0)
    x = rng.normal(size=(48, 96)).astype(np.float32)
    qf = quantize(x, 8)
    ell = _ell(g, 16)
    want = ref.ell_spmm_rowloop(ell.val, ell.col, dequantize(qf))
    got = ops.ell_spmm(ell, qf.q, quantized_meta=(qf.scale, qf.x_min))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 48),
       feat=st.integers(1, 80), w_log=st.integers(0, 6))
def test_property_pallas_equals_oracle(seed, n, feat, w_log):
    rng = np.random.default_rng(seed)
    g = random_csr(rng, n, 6.0, skew=0.9)
    b = jnp.asarray(rng.normal(size=(n, feat)).astype(np.float32))
    W = 2**w_log
    ell = _ell(g, W)
    want = ref.ell_spmm_rowloop(ell.val, ell.col, b)
    got = ops.ell_spmm(ell, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_empty_graph(rng):
    g = random_csr(rng, 8, 0.0, skew=0.0)
    b = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    got = ops.ell_spmm(_ell(g, 4), b)
    np.testing.assert_array_equal(np.asarray(got), 0)


@pytest.mark.parametrize("feat", [200, 600])
def test_quantized_gather_packs_words(rng, feat):
    """uint8 features wider than one lane tile ride 2 (feat 200) or 4
    (feat 600) to an int32 word; every quantized kernel unpacks them to
    the dequantize-then-aggregate result."""
    from repro.core.sampling import sample_csr_to_block_ell

    g = random_csr(rng, 40, 5.0)
    x = rng.normal(size=(40, feat)).astype(np.float32)
    qf = quantize(x, 8)
    meta = (qf.scale, qf.x_min)
    xd = dequantize(qf)
    tol = dict(rtol=1e-4, atol=1e-4)
    ell = _ell(g, 8)
    np.testing.assert_allclose(
        np.asarray(ops.ell_spmm(ell, qf.q, quantized_meta=meta)),
        np.asarray(ref.ell_spmm_rowloop(ell.val, ell.col, xd)), **tol)
    bell = sample_csr_to_block_ell(g, [("aes", 8), ("full", 0), ("afs", 4)],
                                   16)
    np.testing.assert_allclose(
        np.asarray(ops.block_ell_spmm(bell, qf.q, quantized_meta=meta)),
        np.asarray(ref.block_ell_spmm(bell, xd)), **tol)
    w = jnp.asarray(rng.normal(size=(feat, 24)).astype(np.float32))
    bias = jnp.zeros(24, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.fused_layer_spmm(ell, qf.q, w, bias,
                                        quantized_meta=meta)),
        np.asarray(ref.fused_layer(ell.val, ell.col, xd, w, bias)), **tol)


def test_smem_bound_refuses_wide_ell(rng):
    """An ELL wider than the kernels' SMEM holds is refused up front with
    a ValueError (Mosaic would fail to compile it), and the tuner offers
    no pallas candidate at that width."""
    from repro.tuning.cost_model import CandidateConfig

    g = random_csr(rng, 8, 2.0)
    wide = next(w for w in range(8000, 9000) if not ops.ell_fits_smem(w))
    ell = ELL(jnp.zeros((8, wide), jnp.float32),
              jnp.zeros((8, wide), jnp.int32), g.num_cols)
    b = jnp.ones((8, 16), jnp.float32)
    with pytest.raises(ValueError, match="SMEM"):
        ops.ell_spmm(ell, b)
    with pytest.raises(ValueError, match="SMEM"):
        ops.fused_layer_spmm(ell, b, jnp.ones((16, 4)), jnp.zeros(4))
    assert not ops.ell_fits_smem(wide, sampled=True)
    assert ops.ell_fits_smem(wide - 1) \
        and ops.ell_fits_smem(wide - 1, sampled=True)
    # aes_sample's own bounds lie far wider: its SMEM holds one sampled
    # row, its VMEM the window and output tiles of eight rows
    with pytest.raises(ValueError, match="VMEM"):
        ops.aes_sample(g, 40_000)
    with pytest.raises(ValueError, match="SMEM"):
        ops.aes_sample(g, 1 << 16)

    from repro.tuning import PlanCache
    from repro.tuning.autotune import tune

    grid = [CandidateConfig("sfs", wide, "pallas"),
            CandidateConfig("sfs", 4, "jax")]
    plan = tune(g, np.asarray(b), grid=grid, budget=2, cache=PlanCache(),
                warmup=0, iters=1)
    assert plan.config.backend == "jax"


def test_tiled_operand_is_built_once(rng):
    """A live jax.Array is re-laid out into the kernels' tiles once and
    reused by later calls; the layout is dropped with the array."""
    from repro.kernels import gather

    g = random_csr(rng, 16, 3.0)
    x = jnp.asarray(rng.normal(size=(16, 40)).astype(np.float32))
    qf = quantize(x, 8)
    t1 = gather.tiled(qf.q)
    assert gather.tiled(qf.q) is t1
    assert (t1.num_features, t1.pack, t1.bits) == (40, 1, 8)
    ell = _ell(g, 8)
    got = ops.ell_spmm(ell, qf.q, quantized_meta=(qf.scale, qf.x_min))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.ell_spmm_rowloop(ell.val, ell.col, dequantize(qf))),
        rtol=1e-4, atol=1e-4)
    key = (id(qf.q), gather.LANES)
    assert key in gather._TILED
    del qf, t1
    assert key not in gather._TILED
