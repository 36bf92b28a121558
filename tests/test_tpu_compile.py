"""Ahead-of-time compiles of every Pallas kernel for a TPU v5e.

Interpret mode (every other kernel test) accepts programs that Mosaic
refuses: scalars read out of VMEM, slices off the HBM tiling, more scoped
VMEM than a kernel may use.  These tests compile each kernel for a v5e
chip that is described, not attached, at the published widths of the
paper's graphs (F = 128, 640 for reddit's 602, 1536 for cora's 1433;
W = 128; ogbn-arxiv's 169,344 padded rows), and check that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import aes_sample, ell_spmm, fused_layer, fused_spmm, ops
from repro.kernels.gather import (LANES, SMEM_BUDGET, block_tail,
                                  edge_tile_smem_bytes, flat_window,
                                  tile_features)

ROWS = 169_344               # ogbn-arxiv's 169,343 nodes, padded to 8 rows
NNZ = 2_489_342              # its GCN adjacency at average degree 13.7
W = 128
BLOCK_ROWS = 4096            # tune_blocked's default row block
FEATS = (128, 640, 1536)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip.
    The persistent compile cache stays off while these compile: an entry
    written here could not be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _operand(spec, feat: int, quant: bool):
    """The dense operand at ``feat`` features in the kernels' layout
    (``gather.TiledFeatures``), f32 or packed uint8."""
    t = jax.eval_shape(tile_features, jax.ShapeDtypeStruct(
        (ROWS, feat), jnp.uint8 if quant else jnp.float32))
    return dataclasses.replace(t, tiles=spec(t.tiles.shape, t.tiles.dtype))


def _ell(spec, width: int = W):
    return (spec((ROWS, width), jnp.float32), spec((ROWS, width), jnp.int32),
            spec((ROWS,), jnp.int32))


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "uint8"])
@pytest.mark.parametrize("feat", FEATS)
def test_ell_spmm_compiles(spec, feat, quant):
    kw = dict(scale=0.1, x_min=-1.0) if quant else {}
    _compile(lambda v, c, lw, b: ell_spmm.ell_spmm(
        v, c, lw, b, interpret=False, **kw),
        *_ell(spec), _operand(spec, feat, quant))


def _mosaic(text: str) -> str:
    """The Mosaic kernel a compiled program holds, as MLIR text without
    debug locations."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    body, = re.findall(r'"body":"([^"]+)"', text)
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


@pytest.mark.parametrize("feat,quant,ring", [
    (128, False, None), (128, True, None), (500, True, None),
    (640, False, (5, 640)), (500, False, (4, 512)), (1536, True, (3, 1536)),
], ids=["f32-128", "uint8-128", "uint8-500", "f32-640", "f32-500",
        "uint8-1536"])
def test_ell_spmm_takes_the_ring_only_past_one_lane_tile(spec, feat, quant,
                                                         ring):
    """The Mosaic kernel ``ell_spmm`` lowers to.  On a one-tile operand
    (arxiv's F = 128, f32 and uint8, and pubmed's uint8 F = 500) it is the
    per-tile grid, (row tiles, 1), with a ``[2, 1, 1, 128]`` landing zone
    and two DMA semaphores.  Past one lane tile the grid is the row tiles
    alone, the output block the whole padded row, and the landing zone
    ``[RING_DEPTH, tiles, 1, 128]`` on ``RING_DEPTH`` semaphores."""
    kw = dict(scale=0.1, x_min=-1.0) if quant else {}
    body = _mosaic(_compile(lambda v, c, lw, b: ell_spmm.ell_spmm(
        v, c, lw, b, interpret=False, **kw),
        *_ell(spec), _operand(spec, feat, quant)))
    dtype = "i32" if quant else "f32"
    if ring is None:
        grid, out, zone, sems = f"{ROWS // 8}, 1", 128 * (4 if feat == 500
                                                          else 1), \
            f"2x1x1x128x{dtype}", 2
    else:
        d = ell_spmm.RING_DEPTH
        grid, out, zone, sems = f"{ROWS // 8}", ring[1], \
            f"{d}x{ring[0]}x1x128x{dtype}", d
    assert f"iteration_bounds = array<i64: {grid}>" in body
    assert f"memref<8x{out}xf32, #tpu.memory_space<vmem>>, " \
        f"memref<{zone}, #tpu.memory_space<vmem>>, " \
        f"memref<{sems}x!tpu.dma_semaphore" in body
    assert "scratch_operands = 2" in body


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "uint8"])
@pytest.mark.parametrize("feat", FEATS)
def test_block_ell_spmm_compiles(spec, feat, quant):
    blocks = -(-ROWS // BLOCK_ROWS)
    slots = BLOCK_ROWS * blocks * W + block_tail(W)
    kw = dict(scale=0.1, x_min=-1.0) if quant else {}
    _compile(lambda t, lw, v, c, b: ell_spmm.block_ell_spmm(
        t, lw, v, c, b, block_rows=BLOCK_ROWS, max_w=W, interpret=False,
        **kw),
        spec((blocks, 2), jnp.int32), spec((blocks * BLOCK_ROWS,), jnp.int32),
        spec((slots,), jnp.float32), spec((slots,), jnp.int32),
        _operand(spec, feat, quant))


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "uint8"])
@pytest.mark.parametrize("feat", FEATS)
def test_fused_layer_compiles(spec, feat, quant):
    b = _operand(spec, feat, quant)
    kw = dict(scale=0.1, x_min=-1.0) if quant else {}
    _compile(lambda v, c, lw, x, w, bias: fused_layer.fused_layer(
        v, c, lw, x, w, bias, interpret=False, **kw),
        *_ell(spec), b, spec((b.padded_features, LANES), jnp.float32),
        spec((LANES,), jnp.float32))


def test_fused_layer_compiles_at_its_vmem_bound(spec):
    """The widest square layer the VMEM budget admits compiles; one lane
    tile wider is refused by the budget."""
    dim = max(d for d in range(LANES, 4096, LANES)
              if fused_layer.vmem_bytes(8, d, d) <= fused_layer.VMEM_BUDGET)
    assert fused_layer.vmem_bytes(8, dim + LANES, dim + LANES) \
        > fused_layer.VMEM_BUDGET
    _compile(lambda v, c, lw, x, w, bias: fused_layer.fused_layer(
        v, c, lw, x, w, bias, interpret=False),
        *_ell(spec), _operand(spec, dim, False),
        spec((dim, dim), jnp.float32), spec((dim,), jnp.float32))


def test_aes_sample_compiles(spec):
    _compile(lambda rp, ci, av: aes_sample.aes_sample(
        rp, ci, av, sh_width=W, interpret=False),
        spec((ROWS + 1,), jnp.int32), spec((NNZ,), jnp.int32),
        spec((NNZ,), jnp.float32))


@pytest.mark.parametrize("feat", FEATS)
def test_fused_aes_spmm_compiles(spec, feat):
    pad = NNZ + flat_window(W)
    _compile(lambda rs, nz, ci, av, b: fused_spmm.fused_aes_spmm(
        rs, nz, ci, av, b, sh_width=W, interpret=False),
        spec((ROWS,), jnp.int32), spec((ROWS,), jnp.int32),
        spec((pad,), jnp.int32), spec((pad,), jnp.float32),
        _operand(spec, feat, False))


@pytest.mark.parametrize("kernel,quant,feat", [("aes_sample", False, 0),
                                               ("ell_spmm", False, LANES),
                                               ("ell_spmm", True, LANES),
                                               ("sampler", False, 0),
                                               ("ell_spmm", False, 640)],
                         ids=["aes_sample", "ell_spmm-f32", "ell_spmm-uint8",
                              "sampler", "ell_spmm-ring-f32"])
def test_kernel_names_are_pinned(spec, kernel, quant, feat):
    """The compiled custom-call carries the kernel's own name, which the
    benchmark's ``sample_ms`` and ``spmm_ms`` select, even when its jitted
    wrapper is bypassed under another name.  Every Pallas call the
    program's sampler (``ops.aes_sample``, which ``core.aes_spmm.sample``
    runs) lowers to is named ``aes_sample``; ``ell_spmm`` keeps its name
    on the multi-tile ring too."""
    rows, pad = 1024, 1024 + flat_window(W)
    if kernel == "sampler":
        from repro.core.graph import CSR

        def renamed(rp, ci, av):
            return ops.aes_sample(CSR(rp, ci, av, rows), W, interpret=False)

        kernel = "aes_sample"
        args = (spec((rows + 1,), jnp.int32), spec((pad,), jnp.int32),
                spec((pad,), jnp.float32))
    elif kernel == "aes_sample":
        body = aes_sample.aes_sample.__wrapped__

        def renamed(rp, ci, av):
            return body(rp, ci, av, sh_width=W, interpret=False)

        args = (spec((rows + 1,), jnp.int32), spec((pad,), jnp.int32),
                spec((pad,), jnp.float32))
    else:
        body = ell_spmm.ell_spmm.__wrapped__
        kw = dict(scale=0.1, x_min=-1.0) if quant else {}

        def renamed(v, c, lw, b):
            return body(v, c, lw, b, interpret=False, **kw)

        t = jax.eval_shape(tile_features, jax.ShapeDtypeStruct(
            (rows, feat), jnp.uint8 if quant else jnp.float32))
        args = (spec((rows, W), jnp.float32), spec((rows, W), jnp.int32),
                spec((rows,), jnp.int32),
                dataclasses.replace(t, tiles=spec(t.tiles.shape,
                                                  t.tiles.dtype)))
    text = _compile(renamed, *args)
    names = re.findall(r"%(\S+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert [n.rpartition(".")[0] for n in names] == [kernel]


def test_sampler_width_is_read_from_its_hlo(spec):
    """The benchmark's ``sample_roofline`` reads the sampler's width W
    from its output shape in the op's HLO text, as the trace names it."""
    from bench import run
    from repro.core.graph import CSR

    metric = run.load_module(run.BENCH / "metrics" / "sample_roofline.py")
    rows, pad = 1024, 1024 + flat_window(W)
    text = _compile(
        lambda rp, ci, av: ops.aes_sample(CSR(rp, ci, av, rows), W,
                                          interpret=False),
        spec((rows + 1,), jnp.int32), spec((pad,), jnp.int32),
        spec((pad,), jnp.float32))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert metric.OUTPUT.findall(calls[0]) == [str(W)]


def _widest(fits) -> int:
    """The largest width ``fits`` admits (``fits`` is monotone)."""
    lo, hi = 1, 1 << 20
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo


@pytest.mark.parametrize("kernel", ["ell_spmm", "fused_layer", "aes_sample",
                                    "fused_aes_spmm", "ell_spmm-ring"])
def test_compiles_at_its_smem_bound(spec, kernel):
    """The widest ELL row the SMEM budget admits compiles for each kernel
    whose SMEM grows with the width; one slot more is refused by the
    budget, before Mosaic would refuse it.  ``ell_spmm`` compiles there
    on one lane tile and on the multi-tile ring (F = 640), whose SMEM is
    the same.  ``aes_sample`` keeps one sampled row in SMEM and eight
    rows' windows in VMEM, so its VMEM budget binds first."""
    fits = {"ell_spmm": lambda w: edge_tile_smem_bytes(8, w) <= SMEM_BUDGET,
            "ell_spmm-ring":
                lambda w: edge_tile_smem_bytes(8, w) <= SMEM_BUDGET,
            "fused_layer":
                lambda w: edge_tile_smem_bytes(8, w) <= SMEM_BUDGET,
            "aes_sample": aes_sample.fits,
            "fused_aes_spmm":
                lambda w: fused_spmm.smem_bytes(8, w) <= SMEM_BUDGET}[kernel]
    w = _widest(fits)
    assert not fits(w + 1)
    if kernel in ("ell_spmm", "fused_layer", "ell_spmm-ring"):
        assert ops.ell_fits_smem(w) and not ops.ell_fits_smem(w + 1)
    if kernel == "aes_sample":
        assert aes_sample.smem_bytes(8, w + 1) <= SMEM_BUDGET
        with pytest.raises(ValueError, match="VMEM"):
            aes_sample.check_fits(w + 1)
    rows, pad = 4096, 4096 + flat_window(w)
    ell = (spec((rows, w), jnp.float32), spec((rows, w), jnp.int32),
           spec((rows,), jnp.int32))
    b = _operand(spec, LANES, False)
    csr = (spec((rows,), jnp.int32), spec((rows,), jnp.int32),
           spec((pad,), jnp.int32), spec((pad,), jnp.float32))
    if kernel.startswith("ell_spmm"):
        if kernel == "ell_spmm-ring":
            b = _operand(spec, 640, False)
        _compile(lambda v, c, lw, x: ell_spmm.ell_spmm(
            v, c, lw, x, interpret=False), *ell, b)
    elif kernel == "fused_layer":
        _compile(lambda v, c, lw, x, wt, bias: fused_layer.fused_layer(
            v, c, lw, x, wt, bias, interpret=False), *ell, b,
            spec((LANES, LANES), jnp.float32), spec((LANES,), jnp.float32))
    elif kernel == "aes_sample":
        _compile(lambda rp, ci, av: aes_sample.aes_sample(
            rp, ci, av, sh_width=w, interpret=False),
            spec((rows + 1,), jnp.int32), *csr[2:])
    else:
        _compile(lambda rs, nz, ci, av, x: fused_spmm.fused_aes_spmm(
            rs, nz, ci, av, x, sh_width=w, interpret=False), *csr, b)
