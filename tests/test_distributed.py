"""Distribution tests: sharding rules are valid for every architecture
(divisibility on the production mesh), and a real dry-run cell passes in a
subprocess with 512 forced host devices."""
from __future__ import annotations

import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, NamedSharding

from repro.configs import ALL_ARCHS, SHAPES, get_config, smoke_config
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        opt_shardings, param_shardings)
from repro.models import init_cache, init_params, input_specs, loss_fn
from repro.optim import adamw_init

ABSTRACT_MESH = AbstractMesh((16, 16), ("data", "model"))
ABSTRACT_MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(tree, shardings, mesh):
    """Every non-None spec axis must divide its dimension."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    shards = jax.tree.leaves(shardings,
                             is_leaf=lambda x: isinstance(x, NamedSharding))
    assert len(leaves) == len(shards)
    for (path, leaf), sh in zip(leaves, shards):
        spec = sh.spec
        assert len(spec) <= leaf.ndim, (path, leaf.shape, spec)
        for dim, axes in zip(leaf.shape, spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (jax.tree_util.keystr(path),
                                     leaf.shape, spec)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("mesh", [ABSTRACT_MESH, ABSTRACT_MESH_MP],
                         ids=["16x16", "2x16x16"])
def test_param_and_opt_shardings_valid(arch, mesh):
    cfg = get_config(arch)
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    _check_divisible(params, param_shardings(mesh, params), mesh)
    opt = jax.eval_shape(adamw_init, params)
    _check_divisible(opt, opt_shardings(mesh, opt), mesh)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_cache_shardings_valid(arch, shape):
    cfg = get_config(arch)
    seq, batch, kind = SHAPES[shape]
    if shape == "long_500k" and not cfg.sub_quadratic:
        pytest.skip("full-attention arch skips long_500k (DESIGN.md §4)")
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, seq))
    sh = cache_shardings(ABSTRACT_MESH, cache,
                         stacked=cfg.block_pattern is None)
    _check_divisible(cache, sh, ABSTRACT_MESH)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_batch_shardings_valid(arch):
    cfg = get_config(arch)
    specs = input_specs(cfg, "train", 4096, 256)
    sh = batch_shardings(ABSTRACT_MESH_MP, specs)
    _check_divisible(specs, sh, ABSTRACT_MESH_MP)


def test_sharded_train_step_runs_on_local_mesh():
    """End-to-end jit with in_shardings on a real (1-device) mesh —
    verifies the sharding trees structurally match the computation."""
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    with mesh:
        p_sh = param_shardings(mesh, params)
        b_sh = batch_shardings(mesh, batch)
        params = jax.device_put(params, p_sh)
        loss = jax.jit(lambda p, b: loss_fn(p, cfg, b),
                       in_shardings=(p_sh, b_sh))(params, batch)
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """Deliverable (e) gate: one real dry-run cell must lower + compile on
    the 16x16 production mesh (512 forced host devices, fresh process)."""
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "tinyllama-1.1b", "--shape", "decode_32k"],
        env=env, capture_output=True, text=True, timeout=540)
    assert "decode_32k/16x16: OK" in r.stdout, r.stdout + r.stderr
