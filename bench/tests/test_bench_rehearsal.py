"""Rehearsal of whole runs on the CPU, Pallas in interpret mode: each cell
of ``BENCHMARK.json`` at a tiny node count, with its committed limits.

A sound run is correct and its control (the mix's lower precision) is
not; a run whose timed path is broken underneath is not correct; the
harness refuses to run without a chip.
"""
from __future__ import annotations

import copy
import json

import pytest

from bench import run

CELLS = [w["name"] for w in
         run.load_json(run.CHECKOUT / "BENCHMARK.json")["workloads"]]
SEED = 2**33 + 12345
TINY_NODES = 300


def tiny(cell: str) -> dict:
    """The cell with its graph cut to ``TINY_NODES`` nodes; every width,
    the mix and the limits as committed."""
    spec = run.spec_of(cell)
    spec["config"] = copy.deepcopy(spec["config"])
    spec["config"]["graph"]["nodes"] = TINY_NODES
    return spec


def quiet(*_a, **_k):
    pass


def run_tiny(cell, **kw):
    return run.run_cell(tiny(cell), SEED, 0.0, False, require_chip=False,
                        log=quiet, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(cell):
    r = run_tiny(cell, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"forward_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    assert not run.passed(r["control"]), r["control"]


def bump_row(stage):
    def fault(name, a):
        return a.at[0].add(1.0) if name == stage else a
    return fault


FAULTS = {
    "agg1": bump_row("agg1"),
    "agg2": bump_row("agg2"),
    "logits": bump_row("logits"),
}


@pytest.mark.parametrize("stage", sorted(FAULTS))
@pytest.mark.parametrize("cell", [CELLS[0], CELLS[-1]])
def test_an_altered_answer_is_caught(cell, stage):
    r = run_tiny(cell, fault=FAULTS[stage])
    assert not r["correct"]
    assert r["failed"] == r["attempted"]


def test_a_sampler_that_drops_an_edge_is_caught(monkeypatch):
    """Each row's first sampled edge is zeroed where the operand is made."""
    import importlib

    from repro.core.graph import ELL

    aes = importlib.import_module("repro.core.aes_spmm")

    real = aes.sample

    def broken(*a, **k):
        ell = real(*a, **k)
        return ELL(ell.val.at[:, 0].set(0.0), ell.col, ell.num_cols)

    monkeypatch.setattr(aes, "sample", broken)
    assert not run_tiny(CELLS[0])["correct"]


def test_traced_rehearsal_reports_per_layer_metrics():
    def cpu_ops(plane, line):
        return plane == "/host:CPU" and line.startswith("tf_XLA")

    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    r = run.run_cell(tiny(CELLS[0]), SEED, 0.0, True, require_chip=False,
                     log=quiet, is_op_line=cpu_ops, peak=peak)
    assert r["correct"]
    d = r["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    assert {"fwd_mfu", "xla_ms", "idle_share"} <= set(r["metrics"])
    assert 0 < r["metrics"]["fwd_mfu"]["value"] <= 100
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(r["breakdown"][key]) <= 10
    json.dumps(r)


def test_without_a_chip_the_run_exits_2_and_prints_nothing(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""
