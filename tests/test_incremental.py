"""Incremental plan maintenance for evolving graphs (ISSUE 7).

The differential delta-testing harness behind ``repro.tuning.incremental``:

  * **CSR delta layer** — ``apply_csr_deltas`` unit + seeded-fuzz tests:
    invariants (sorted indptr, index ranges, duplicate-free edges, degree
    bookkeeping, empty-row transitions) on random insert/delete streams,
    with failing cases persisted to ``tests/corpus/`` and replayed first
    on every run.
  * **Rolling digests** — patching only the touched
    ``DIGEST_BLOCK_ROWS``-granularity digests must land on the same
    fingerprint as a full re-hash.
  * **Differential parity** — a patched ``BlockedPlan`` must be
    *bit-identical* to a cold ``tune_blocked`` of the patched graph
    (fingerprint, per-block configs, operand bytes), including the
    quantized-operand variant; hypothesis drives random streams over the
    conformance harness's four adversarial graphs.
  * **Concurrency** — one process re-publishing a cached plan while
    another loads it: the loader sees the old or the new version, never a
    torn mix (the ``tmp + os.replace`` atomic swap ``PlanCache._save_disk``
    performs).  Mirrors the calibration-log O_APPEND regression test:
    top-level worker fns, ``multiprocessing.Pool``, no jax in the forked
    workers.
  * **Sharded routing** — ``route_edge_deltas`` /
    ``apply_edge_updates_sharded`` / ``GNNServer.apply_edge_updates``:
    deltas only touch the owning shards, halo growth falls back to a
    re-tune, outputs match the patched graph's ground truth.
"""
from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import shutil
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.graph import (CSR, DIGEST_BLOCK_ROWS, apply_csr_deltas,
                              combine_block_digests, csr_block_digests,
                              csr_from_edges, csr_to_dense)
from repro.tuning import PlanCache
from repro.tuning.autotune import tune, tune_blocked
from repro.tuning.incremental import apply_edge_updates

from conftest import random_csr

CORPUS_DIR = Path(__file__).parent / "corpus"


def _edge_dict(csr) -> dict:
    rp = np.asarray(csr.row_ptr)
    ci = np.asarray(csr.col_ind)
    v = np.asarray(csr.val)
    out: dict = {}
    for r in range(csr.num_rows):
        for k in range(int(rp[r]), int(rp[r + 1])):
            key = (r, int(ci[k]))
            out[key] = out.get(key, 0.0) + float(v[k])
    return out


def _dedup(csr) -> CSR:
    """Duplicate-free, column-sorted copy (values of dupes summed)."""
    edges = _edge_dict(csr)
    keys = sorted(edges)
    n = csr.num_rows
    cnt = np.bincount([r for r, _ in keys], minlength=n)
    rp = np.zeros(n + 1, np.int64)
    np.cumsum(cnt, out=rp[1:])
    return CSR(jnp.asarray(rp.astype(np.int32)),
               jnp.asarray(np.array([c for _, c in keys] or [0],
                                    np.int32)[:len(keys)]),
               jnp.asarray(np.array([edges[k] for k in keys] or [0.0],
                                    np.float32)[:len(keys)]),
               num_cols=csr.num_cols)


def _interpret_stream(csr, pairs):
    """Raw (row, col) pairs -> a valid (additions, deletions) split:
    each pair is judged against the current edge set (present -> delete,
    absent -> add); repeats of a scheduled pair are dropped."""
    edges = set(_edge_dict(csr))
    adds, dels, seen = [], [], set()
    n, m = csr.num_rows, csr.num_cols
    for r, c in pairs:
        p = (int(r) % n, int(c) % m)
        if p in seen:
            continue
        seen.add(p)
        (dels if p in edges else adds).append(p)
    return adds, dels


def _fingerprint(csr) -> str:
    return combine_block_digests(csr_block_digests(csr),
                                 csr.num_rows, csr.num_cols)


# ---------------------------------------------------------------------------
# CSR delta layer: unit tests
# ---------------------------------------------------------------------------

def test_empty_delta_is_noop(rng):
    g = random_csr(rng, 30, 3.0)
    out, touched = apply_csr_deltas(g)
    assert out is g and touched.size == 0


def test_delta_edge_semantics(rng):
    g = _dedup(random_csr(rng, 40, 4.0))
    edges = _edge_dict(g)
    dels = sorted(edges)[::4][:5]
    eset = set(edges)
    adds, c = [], 0
    for r in range(0, 40, 7):
        while (r, c) in eset:
            c += 1
        adds.append((r, c, 2.5))
    out, touched = apply_csr_deltas(g, adds, dels)
    want = {k: v for k, v in edges.items() if k not in set(dels)}
    want.update({(r, c): v for r, c, v in adds})
    assert _edge_dict(out) == want
    assert set(touched) == {r for r, _ in dels} | {r for r, _, _ in adds}
    # value defaults to 1.0 for bare pairs
    out2, _ = apply_csr_deltas(out, [(0, g.num_cols - 1)]
                               if (0, g.num_cols - 1) not in want else [])
    if (0, g.num_cols - 1) not in want:
        assert _edge_dict(out2)[(0, g.num_cols - 1)] == 1.0


def test_delta_error_paths(rng):
    g = _dedup(random_csr(rng, 12, 3.0))
    edges = sorted(_edge_dict(g))
    r0, c0 = edges[0]
    absent = next((r, c) for r in range(12) for c in range(12)
                  if (r, c) not in set(edges))
    cases = [
        (([(0, 99)], ()), "addition col out of range"),
        (((), [(99, 0)]), "deletion row out of range"),
        (((), [absent]), "deleting an absent edge"),
        (([(r0, c0)], ()), "adding a present edge"),
        (([absent, absent], ()), "duplicate addition"),
        (((), [(r0, c0), (r0, c0)]), "duplicate deletion"),
    ]
    for (adds, dels), what in cases:
        with pytest.raises(ValueError):
            apply_csr_deltas(g, adds, dels)
    # malformed entries
    with pytest.raises(ValueError):
        apply_csr_deltas(g, [(1,)], ())
    with pytest.raises(ValueError):
        apply_csr_deltas(g, [(1.5, 2)], ())


def test_unsorted_rows_take_lexsort_fallback():
    """A CSR whose rows are not column-sorted still patches correctly
    (the merge fast path is only for sorted rows)."""
    rp = np.array([0, 3, 3, 5], np.int32)
    ci = np.array([2, 0, 1, 2, 1], np.int32)       # row 0 unsorted
    v = np.arange(5, dtype=np.float32) + 1
    g = CSR(jnp.asarray(rp), jnp.asarray(ci), jnp.asarray(v), num_cols=3)
    out, touched = apply_csr_deltas(g, [(1, 0)], [(0, 2)])
    assert _edge_dict(out) == {(0, 0): 2.0, (0, 1): 3.0, (1, 0): 1.0,
                               (2, 2): 4.0, (2, 1): 5.0}
    assert touched.tolist() == [0, 1]


def test_deletion_removes_every_duplicate_instance():
    src = np.array([3, 3, 5], np.int64)
    dst = np.array([1, 1, 1], np.int64)             # (1, 3) stored twice
    g = csr_from_edges(src, dst, 8)
    out, _ = apply_csr_deltas(g, (), [(1, 3)])
    assert _edge_dict(out) == {(1, 5): 1.0}


def test_untouched_rows_are_byte_identical(rng):
    g = _dedup(random_csr(rng, 64, 5.0))
    edges = sorted(_edge_dict(g))
    dels = [e for e in edges if e[0] == edges[-1][0]][:2]
    out, touched = apply_csr_deltas(g, (), dels)
    rp0, rp1 = np.asarray(g.row_ptr), np.asarray(out.row_ptr)
    ci0, ci1 = np.asarray(g.col_ind), np.asarray(out.col_ind)
    v0, v1 = np.asarray(g.val), np.asarray(out.val)
    tset = set(touched.tolist())
    for r in range(64):
        if r in tset:
            continue
        a, b = int(rp0[r]), int(rp0[r + 1])
        c, d = int(rp1[r]), int(rp1[r + 1])
        assert b - a == d - c
        assert ci0[a:b].tobytes() == ci1[c:d].tobytes()
        assert v0[a:b].tobytes() == v1[c:d].tobytes()


# ---------------------------------------------------------------------------
# rolling digests
# ---------------------------------------------------------------------------

def test_digest_patch_matches_full_rehash(rng):
    g = _dedup(random_csr(rng, 200, 4.0))
    digests = csr_block_digests(g, digest_rows=64)
    cur = g
    for step in range(4):
        edges = sorted(_edge_dict(cur))
        dels = edges[step::37][:3]
        eset, adds, c = set(edges), [], step
        for r in range(step, 200, 41):
            while (r, c) in eset or (r, c) in set(adds):
                c = (c + 1) % cur.num_cols
            adds.append((r, c))
        cur, touched = apply_csr_deltas(cur, adds, dels)
        for b in np.unique(np.asarray(touched) // 64):
            digests[int(b)] = csr_block_digests(
                cur, digest_rows=64, blocks=[int(b)])[0]
        assert (combine_block_digests(digests, cur.num_rows, cur.num_cols,
                                      digest_rows=64)
                == combine_block_digests(
                    csr_block_digests(cur, digest_rows=64),
                    cur.num_rows, cur.num_cols, digest_rows=64)), step


def test_digest_is_shape_and_content_sensitive(rng):
    g = _dedup(random_csr(rng, 50, 3.0))
    fp = _fingerprint(g)
    edges = sorted(_edge_dict(g))
    out, _ = apply_csr_deltas(g, (), edges[:1])
    assert _fingerprint(out) != fp
    # value-only change alters the digest too
    v = np.asarray(g.val).copy()
    v[0] += 1.0
    g2 = CSR(g.row_ptr, g.col_ind, jnp.asarray(v), num_cols=g.num_cols)
    assert _fingerprint(g2) != fp


# ---------------------------------------------------------------------------
# differential parity: patched plan vs cold re-tune
# ---------------------------------------------------------------------------

_TK = dict(block_rows=32, widths=(4, 8), measure_plan=False,
           measure_buckets=False)


def _assert_plan_parity(patched, cold):
    assert patched.fingerprint == cold.fingerprint
    assert patched.bell.widths == cold.bell.widths
    assert patched.bell.strategies == cold.bell.strategies
    assert patched.buckets == cold.buckets
    assert np.array_equal(np.asarray(patched.bell.val),
                          np.asarray(cold.bell.val))
    assert np.array_equal(np.asarray(patched.bell.col),
                          np.asarray(cold.bell.col))
    assert np.array_equal(np.asarray(patched.bell.live_w),
                          np.asarray(cold.bell.live_w))


def test_patched_plan_bit_equals_cold_tune(rng):
    g = _dedup(random_csr(rng, 300, 5.0))
    x = jnp.asarray(rng.normal(size=(300, 8)).astype(np.float32))
    cache = PlanCache()
    plan = tune_blocked(g, x, cache=cache, **_TK)
    edges = sorted(_edge_dict(g))
    dels = edges[::31][:10]
    eset, adds, c = set(edges), [], 0
    for r in range(3, 300, 47):
        while (r, c) in eset or (r, c) in set(adds):
            c = (c + 1) % 300
        adds.append((r, c))
    patched, new_csr, report = apply_edge_updates(
        plan, g, adds, dels, widths=_TK["widths"], features=x, cache=cache)
    cold = tune_blocked(new_csr, x, cache=None, refresh=True, **_TK)
    _assert_plan_parity(patched, cold)
    assert patched.version == 1 and cold.version == 0
    assert patched.block_digests == cold.block_digests
    assert report.blocks_skipped == report.num_blocks - len(
        report.touched_blocks) > 0
    # measurement is skipped by design — a patch never re-times
    assert patched.measured_spmm_us == 0.0
    # the patched plan serves from the cache under the new fingerprint
    hit = cache.get(patched.fingerprint, "block")
    assert hit is not None and hit.version == 1
    np.testing.assert_array_equal(np.asarray(hit.run(x)),
                                  np.asarray(cold.run(x)))


def test_quantized_patch_requants_only_touched_rows(rng):
    g = _dedup(random_csr(rng, 128, 4.0))
    x = rng.normal(size=(128, 8)).astype(np.float32)
    plan = tune_blocked(g, jnp.asarray(x), quant=8, cache=None, **_TK)
    edges = sorted(_edge_dict(g))
    eset, c = set(edges), 0
    r = 5
    while (r, c) in eset:
        c += 1
    # feature update that stays inside the stored global range — avoid
    # the rows holding the extrema, or a cold tune would widen its range
    extreme = {int(np.argmax(x.max(axis=1))), int(np.argmin(x.min(axis=1)))}
    requant = [r_ for r_ in (3, 7, 11, 13, 17) if r_ not in extreme][:3]
    x2 = x.copy()
    x2[requant] *= 0.5
    patched, new_csr, report = apply_edge_updates(
        plan, g, [(r, c)], (), widths=_TK["widths"], features=x2,
        requant_rows=requant)
    assert report.requantized_rows == 3
    cold = tune_blocked(new_csr, jnp.asarray(x2), quant=8, cache=None,
                        refresh=True, **_TK)
    _assert_plan_parity(patched, cold)
    assert patched.quantized is not None
    np.testing.assert_array_equal(np.asarray(patched.quantized.q),
                                  np.asarray(cold.quantized.q))
    assert patched.features_fp == cold.features_fp
    np.testing.assert_array_equal(np.asarray(patched.run(jnp.asarray(x2))),
                                  np.asarray(cold.run(jnp.asarray(x2))))


def test_patch_guards(rng):
    g = _dedup(random_csr(rng, 60, 3.0))
    x = jnp.asarray(rng.normal(size=(60, 4)).astype(np.float32))
    plan = tune_blocked(g, x, cache=None, refresh=True, **_TK)
    other = _dedup(random_csr(np.random.default_rng(99), 60, 3.0))
    edges = sorted(_edge_dict(other))
    with pytest.raises(ValueError, match="pre-delta"):
        apply_edge_updates(plan, other, (), edges[:1],
                           widths=_TK["widths"], features=x)
    # global (non-block) plans cannot be patched
    gplan = tune(g, x, budget=1, warmup=0, iters=1, cache=None)
    with pytest.raises(ValueError):
        apply_edge_updates(gplan, g, (), edges[:1], features=x)
    # a quantized plan requires the feature matrix.  refresh=True: a cache
    # hit ignores tuning knobs, so the float plan tuned above would come
    # back from the process-wide default cache under the same fingerprint.
    qplan = tune_blocked(g, x, quant=8, cache=None, refresh=True, **_TK)
    eset = set(_edge_dict(g))
    add = next((r, c) for r in range(60) for c in range(60)
               if (r, c) not in eset)
    with pytest.raises(ValueError):
        apply_edge_updates(qplan, g, [add], ())


def test_noop_update_returns_plan_unchanged(rng):
    g = _dedup(random_csr(rng, 40, 3.0))
    x = jnp.asarray(rng.normal(size=(40, 4)).astype(np.float32))
    plan = tune_blocked(g, x, cache=None, **_TK)
    out, csr_out, report = apply_edge_updates(plan, g, (), (),
                                              widths=_TK["widths"],
                                              features=x)
    assert out is plan and csr_out is g
    assert report.version == plan.version
    assert report.touched_blocks == ()


# ---------------------------------------------------------------------------
# hypothesis: random insert/delete streams over the conformance graphs
# ---------------------------------------------------------------------------

def _conformance_graphs():
    from test_conformance import _GRAPHS
    return _GRAPHS


@given(name=st.sampled_from(["empty", "empty_rows", "dense_row",
                             "ragged70"]),
       pairs=st.lists(st.tuples(st.integers(0, 4095),
                                st.integers(0, 4095)),
                      max_size=16),
       cut=st.integers(0, 16))
@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large,
                                 HealthCheck.filter_too_much])
def test_property_patch_stream_matches_cold_tune(name, pairs, cut):
    """Any insert/delete stream, applied as two sequential patches, lands
    bit-identically on a cold tune of the final graph — (row, col) lists
    shrink to minimal counterexamples."""
    g = _dedup(_conformance_graphs()[name]())
    x = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(g.num_rows, 5)).astype(np.float32))

    # simulate the full stream once to fix a covering width grid
    sim = g
    for chunk in (pairs[:cut], pairs[cut:]):
        adds, dels = _interpret_stream(sim, chunk)
        sim, _ = apply_csr_deltas(sim, adds, dels)
    wmax = max(int(np.asarray(s.row_nnz()).max(initial=0))
               for s in (g, sim)) or 1
    tk = dict(_TK, widths=(wmax, 2 * wmax), block_rows=16)

    # refresh: the process-wide cache keys plans by graph, not by grid, so
    # an earlier example's plan of ``g`` may hold another example's widths
    plan = tune_blocked(g, x, cache=None, refresh=True, **tk)
    cur = g
    for chunk in (pairs[:cut], pairs[cut:]):
        adds, dels = _interpret_stream(cur, chunk)
        plan, cur, _ = apply_edge_updates(plan, cur, adds, dels,
                                          widths=tk["widths"], features=x)
    cold = tune_blocked(cur, x, cache=None, refresh=True, **tk)
    _assert_plan_parity(plan, cold)
    assert _fingerprint(cur) == plan.fingerprint
    np.testing.assert_array_equal(np.asarray(plan.run(x)),
                                  np.asarray(cold.run(x)))
    want = np.asarray(csr_to_dense(cur)) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(plan.run(x)), want,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# seeded fuzz for CSR delta invariants, with a persisted corpus
# ---------------------------------------------------------------------------

def _run_fuzz_case(case: dict) -> None:
    """Replay one corpus/fuzz case and assert every CSR invariant."""
    rng = np.random.default_rng(case["seed"])
    g = _dedup(random_csr(rng, case["num_nodes"], case["avg_deg"]))
    digests = csr_block_digests(g)
    cur = g
    pairs = [tuple(p) for p in case["pairs"]]
    for start in range(0, len(pairs), 6):
        adds, dels = _interpret_stream(cur, pairs[start:start + 6])
        before = _edge_dict(cur)
        nxt, touched = apply_csr_deltas(cur, adds, dels)

        rp = np.asarray(nxt.row_ptr)
        ci = np.asarray(nxt.col_ind)
        n = nxt.num_rows
        # indptr: starts at 0, non-decreasing, ends at nnz
        assert rp[0] == 0 and rp[-1] == len(ci)
        assert (np.diff(rp) >= 0).all()
        # indices in range, rows sorted, no duplicate edges
        if len(ci):
            assert ci.min() >= 0 and ci.max() < nxt.num_cols
        for r in range(n):
            row = ci[rp[r]:rp[r + 1]]
            assert (np.diff(row) > 0).all(), f"row {r} unsorted/dup"
        # degree bookkeeping
        want_deg = np.bincount([r for r, _ in before], minlength=n)
        want_deg -= np.bincount([r for r, _ in dels], minlength=n)
        want_deg += np.bincount([r for r, _ in adds] or [0],
                                minlength=n) if adds else 0
        assert np.array_equal(np.diff(rp), want_deg)
        # empty-row transitions are representable both ways
        assert set(np.flatnonzero(want_deg == 0)) == \
            set(r for r in range(n) if rp[r] == rp[r + 1])
        # edge semantics
        want = {k: v for k, v in before.items() if k not in set(dels)}
        want.update({p: 1.0 for p in adds})
        assert _edge_dict(nxt) == want
        # rolling digests == full re-hash
        for b in np.unique(np.asarray(touched) // DIGEST_BLOCK_ROWS):
            digests[int(b)] = csr_block_digests(nxt, blocks=[int(b)])[0]
        assert combine_block_digests(digests, n, nxt.num_cols) \
            == _fingerprint(nxt)
        cur = nxt


def _corpus_files():
    return sorted(CORPUS_DIR.glob("delta-*.json"))


def test_fuzz_corpus_replay():
    """Previously-failing cases replay first; a regression trips here
    before the randomized search even starts."""
    assert CORPUS_DIR.is_dir()
    for path in _corpus_files():
        _run_fuzz_case(json.loads(path.read_text()))


def test_fuzz_random_streams():
    """Seeded random insert/delete streams; a failure is persisted to
    ``tests/corpus/`` so every later run replays it first."""
    master = np.random.default_rng(20260809)
    for _ in range(25):
        case = {
            "seed": int(master.integers(0, 2**31)),
            "num_nodes": int(master.integers(3, 80)),
            "avg_deg": float(master.uniform(0.5, 6.0)),
            "pairs": [[int(master.integers(0, 4096)),
                       int(master.integers(0, 4096))]
                      for _ in range(int(master.integers(0, 24)))],
        }
        try:
            _run_fuzz_case(case)
        except Exception:
            blob = json.dumps(case, sort_keys=True)
            tag = hashlib.sha1(blob.encode()).hexdigest()[:12]
            CORPUS_DIR.mkdir(exist_ok=True)
            (CORPUS_DIR / f"delta-{tag}.json").write_text(blob + "\n")
            raise


# ---------------------------------------------------------------------------
# concurrency: patch-publish vs load, never torn
# ---------------------------------------------------------------------------

def _mp_swap(args):
    # Top-level for pickling; must not touch jax (forked worker).  Replays
    # byte-for-byte the publish sequence PlanCache._save_disk performs:
    # write tmp beside the target, then one atomic os.replace.
    target, variants, iters = args
    for i in range(iters):
        src = variants[i % len(variants)]
        tmp = target + ".tmp.npz"
        shutil.copyfile(src, tmp)
        os.replace(tmp, target)
    return iters


def _mp_load(args):
    # Top-level for pickling; no jax.  Every load must parse and be
    # internally consistent — version stamp matching the payload marker.
    target, iters = args
    seen = set()
    for _ in range(iters):
        try:
            with np.load(target) as z:
                meta = json.loads(bytes(z["meta"].tobytes()).decode())
                val = np.asarray(z["bell_val"])
        except FileNotFoundError:
            continue
        version = int(meta.get("version", -1))
        marker = float(val[0]) if val.size else -1.0
        assert marker == float(version), \
            f"torn read: version={version} marker={marker}"
        seen.add(version)
    return sorted(seen)


def test_concurrent_patch_publish_never_torn(rng, tmp_path):
    """Regression (ISSUE 7 satellite): while one process re-publishes a
    cached plan (the patch-in-place versioned swap), concurrent loaders
    see the old or the new entry — never a torn mix of the two."""
    import dataclasses

    g = _dedup(random_csr(rng, 48, 3.0))
    x = jnp.asarray(rng.normal(size=(48, 4)).astype(np.float32))
    variants = []
    for version in (0, 1):
        cdir = tmp_path / f"stage{version}"
        cache = PlanCache(cache_dir=cdir)
        plan = tune_blocked(g, x, cache=cache, **_TK)
        # stamp the payload so a torn read is detectable: val[0] == version
        val = np.asarray(plan.bell.val).copy()
        val[0] = float(version)
        cache.put(dataclasses.replace(
            plan, version=version, bell=plan.bell._replace(
                val=jnp.asarray(val))))
        [path] = cdir.glob("*.block.npz")
        variants.append(str(path))

    live = tmp_path / "live"
    live.mkdir()
    target = str(live / Path(variants[0]).name)
    shutil.copyfile(variants[0], target)

    with multiprocessing.Pool(3) as pool:
        writer = pool.apply_async(_mp_swap, [(target, variants, 200)])
        readers = [pool.apply_async(_mp_load, [(target, 200)])
                   for _ in range(2)]
        assert writer.get(timeout=120) == 200
        seen = [r.get(timeout=120) for r in readers]
    for versions in seen:
        assert set(versions) <= {0, 1}
    # the final published entry loads through the real cache path
    fresh = PlanCache(cache_dir=live)
    g_fp = _fingerprint(g)
    loaded = fresh.get(g_fp, "block")
    assert loaded is not None and loaded.version in (0, 1)


def test_fresh_cache_instance_sees_patched_entry(rng, tmp_path):
    """Disk round trip of a patch: a *new* PlanCache (another process in
    spirit) must load the patched plan under the new fingerprint, with
    digests and version intact; the pre-patch entry stays addressable."""
    g = _dedup(random_csr(rng, 80, 4.0))
    x = jnp.asarray(rng.normal(size=(80, 6)).astype(np.float32))
    cache = PlanCache(cache_dir=tmp_path)
    plan = tune_blocked(g, x, cache=cache, **_TK)
    edges = sorted(_edge_dict(g))
    patched, new_csr, _ = apply_edge_updates(
        plan, g, (), edges[:3], widths=_TK["widths"], features=x,
        cache=cache)
    fresh = PlanCache(cache_dir=tmp_path)
    loaded = fresh.get(patched.fingerprint, "block")
    assert loaded is not None
    assert loaded.version == 1
    assert loaded.block_digests == patched.block_digests
    np.testing.assert_array_equal(np.asarray(loaded.bell.val),
                                  np.asarray(patched.bell.val))
    assert fresh.get(plan.fingerprint, "block") is not None


# ---------------------------------------------------------------------------
# sharded routing + the serving engine
# ---------------------------------------------------------------------------

def _spread_delta(csr, n_dels=6, n_adds=5):
    edges = sorted(_edge_dict(csr))
    dels = edges[::max(len(edges) // max(n_dels, 1), 1)][:n_dels]
    eset, adds, c = set(edges), [], 0
    for r in range(1, csr.num_rows, max(csr.num_rows // n_adds, 1)):
        while (r, c) in eset or (r, c) in set(adds):
            c = (c + 1) % csr.num_cols
        adds.append((r, c))
    return adds[:n_adds], dels


def test_route_edge_deltas_by_owning_row(rng):
    from repro.serving.partition import partition_csr
    from repro.serving.plans import route_edge_deltas

    g = _dedup(random_csr(rng, 90, 4.0))
    shards = partition_csr(g, 3)
    adds, dels = _spread_delta(g)
    routed = route_edge_deltas(shards, adds, dels)
    assert len(routed) == 3
    got_a = sorted(e[:2] for a, _ in routed for e in a)
    got_d = sorted(e[:2] for _, d in routed for e in d)
    assert got_a == sorted(adds) and got_d == sorted(dels)
    for sh, (a, d) in zip(shards, routed):
        for r, *_ in list(a) + list(d):
            assert sh.row_start <= r < sh.row_stop
    with pytest.raises(ValueError):
        route_edge_deltas(shards, [(900, 0)], ())


def test_sharded_patch_matches_cold_per_shard(rng):
    from repro.serving.partition import partition_csr
    from repro.serving.plans import apply_edge_updates_sharded, plan_shards

    g = _dedup(random_csr(rng, 120, 4.0))
    x = jnp.asarray(rng.normal(size=(120, 6)).astype(np.float32))
    shards = partition_csr(g, 3)
    tk = dict(block_rows=16, widths=(4, 8), measure_plan=False,
              measure_buckets=False)
    plans = plan_shards(shards, x, mesh_shape=(3,), tune_kwargs=tk)
    adds, dels = _spread_delta(g)
    new_shards, new_plans, report = apply_edge_updates_sharded(
        shards, plans, adds, dels, features=x, mesh_shape=(3,),
        tune_kwargs=tk)
    # edge-level: union of patched shard-local edges == patched graph
    patched_g, _ = apply_csr_deltas(g, adds, dels)
    want = _edge_dict(patched_g)
    got: dict = {}
    for sh in new_shards:
        local = _edge_dict(sh.csr)
        hids = np.asarray(sh.halo_ids)
        for (lr, lc), v in local.items():
            gc = sh.row_start + lc if lc < sh.num_local \
                else int(hids[lc - sh.num_local])
            got[(sh.row_start + lr, gc)] = v
    assert got == want
    # per-shard plan parity vs a cold tune of the patched shard
    for i in report["patched"]:
        cold = tune_blocked(new_shards[i].csr, new_shards[i].gather(x),
                            shard_meta=new_plans[i].shard_meta,
                            refresh=True, cache=None, **tk)
        _assert_plan_parity(new_plans[i], cold)
        assert report["reports"][i].version == 1
    # untouched shards keep their object identity
    for i in report["untouched"]:
        assert new_plans[i] is plans[i] and new_shards[i] is shards[i]


def test_server_patch_and_halo_growth(rng):
    from repro.serving.engine import GNNServer

    g = _dedup(random_csr(rng, 100, 4.0))
    x = jnp.asarray(rng.normal(size=(100, 5)).astype(np.float32))
    adds, dels = _spread_delta(g)
    patched_g, _ = apply_csr_deltas(g, adds, dels)
    wmax = max(int(np.asarray(s.row_nnz()).max(initial=0))
               for s in (g, patched_g)) + 2
    tk = dict(block_rows=16, widths=(wmax, 2 * wmax), measure_plan=False,
              measure_buckets=False)
    srv = GNNServer(g, x, num_shards=2, mode="loop", cache=PlanCache(),
                    tune_kwargs=tk)
    report = srv.apply_edge_updates(adds, dels)
    assert sorted(report["patched"] + report["retuned"]
                  + report["untouched"]) == [0, 1]
    assert srv.stats["edge_updates"] == 1
    want = np.asarray(csr_to_dense(patched_g)) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(srv.aggregate()), want,
                               rtol=1e-4, atol=1e-4)

    # an addition whose column is outside the shard's halo forces a
    # rebuild + re-tune of that shard only
    sh0 = srv.shards[0]
    halo = set(np.asarray(sh0.halo_ids).tolist())
    local = set(range(sh0.row_start, sh0.row_stop))
    out_col = next(c for c in range(99, -1, -1)
                   if c not in halo and c not in local)
    rep2 = srv.apply_edge_updates([(sh0.row_start, out_col)], ())
    assert rep2["retuned"] == [0]
    final_g, _ = apply_csr_deltas(patched_g, [(sh0.row_start, out_col)], ())
    want2 = np.asarray(csr_to_dense(final_g)) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(srv.aggregate()), want2,
                               rtol=1e-4, atol=1e-4)

    # deletions re-tune only when they strand a halo column (the shard
    # then compacts its gather set); plain deletions still patch in place
    del_edges = sorted(_edge_dict(final_g))[:3]
    rep3 = srv.apply_edge_updates((), del_edges)
    assert set(rep3["halo_shrunk"]) <= set(rep3["retuned"])
    final2_g, _ = apply_csr_deltas(final_g, (), del_edges)
    want3 = np.asarray(csr_to_dense(final2_g)) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(srv.aggregate()), want3,
                               rtol=1e-4, atol=1e-4)


def test_halo_shrinks_back_after_growth_then_delete(rng):
    """Regression: deleting the only edge that referenced a grown halo
    column must return the shard's halo (and its gather bytes) to the
    pre-growth size — before the fix the halo only ever grew, so a
    long-lived server leaked gather bandwidth on every transient edge."""
    from repro.serving.engine import GNNServer

    g = _dedup(random_csr(rng, 80, 3.0))
    x = jnp.asarray(rng.normal(size=(80, 5)).astype(np.float32))
    wmax = int(np.asarray(g.row_nnz()).max(initial=0)) + 2
    tk = dict(block_rows=16, widths=(wmax, 2 * wmax), measure_plan=False,
              measure_buckets=False)
    srv = GNNServer(g, x, num_shards=2, mode="loop", cache=PlanCache(),
                    tune_kwargs=tk)
    sh0 = srv.shards[0]
    pre_ids = np.asarray(sh0.halo_ids).copy()
    pre_bytes = pre_ids.nbytes
    halo = set(pre_ids.tolist())
    local = set(range(sh0.row_start, sh0.row_stop))
    out_col = next(c for c in range(79, -1, -1)
                   if c not in halo and c not in local)
    row = sh0.row_start

    rep = srv.apply_edge_updates([(row, out_col)], ())
    assert rep["retuned"] == [0]
    grown = np.asarray(srv.shards[0].halo_ids)
    assert grown.size == pre_ids.size + 1 and out_col in grown.tolist()

    rep2 = srv.apply_edge_updates((), [(row, out_col)])
    assert 0 in rep2["halo_shrunk"] and 0 in rep2["retuned"]
    post_ids = np.asarray(srv.shards[0].halo_ids)
    assert post_ids.nbytes == pre_bytes
    assert np.array_equal(post_ids, pre_ids)
    # and the round trip left the deployment serving the original graph
    want = np.asarray(csr_to_dense(g)) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(srv.aggregate()), want,
                               rtol=1e-4, atol=1e-4)


def test_requant_triggers_on_accumulated_drift(rng):
    """Regression: features that drift *inside* the stored quantization
    range used to be re-encoded against the stale grid forever, silently
    losing resolution as the live distribution shrank.  Past the drift
    threshold the patch must now derive a fresh range, and the fresh
    encoding must beat the stale one on reconstruction error."""
    from repro.core.quantization import (DRIFT_THRESHOLD, dequantize,
                                         range_drift, requantize_rows)

    g = _dedup(random_csr(rng, 96, 4.0))
    x = rng.normal(size=(96, 6)).astype(np.float32)
    plan = tune_blocked(g, jnp.asarray(x), quant=8, cache=None, **_TK)
    qf0 = plan.quantized
    assert qf0 is not None and plan.quant_drift == 0.0

    # shrink every feature towards the mean: stays strictly inside the
    # stored [x_min, x_max] but the live span collapses to 30%
    x2 = (x - x.mean()) * 0.3 + x.mean()
    assert range_drift(qf0, x2) > DRIFT_THRESHOLD
    eset, c = set(_edge_dict(g)), 0
    while (1, c) in eset:
        c += 1
    patched, _, report = apply_edge_updates(
        plan, g, [(1, c)], (), widths=_TK["widths"], features=x2,
        requant_rows=np.arange(96))
    assert report.requant_refreshed
    assert patched.quant_drift == 0.0
    qf1 = patched.quantized
    # the refreshed grid actually covers the live distribution tightly...
    assert float(qf1.x_max) - float(qf1.x_min) \
        < 0.5 * (float(qf0.x_max) - float(qf0.x_min))
    # ...and reconstructs the drifted features strictly better than
    # re-encoding on the stale grid would have
    stale = requantize_rows(qf0, np.arange(96), x2)
    err_fresh = np.abs(np.asarray(dequantize(qf1)) - x2).max()
    err_stale = np.abs(np.asarray(dequantize(stale)) - x2).max()
    assert err_fresh < err_stale

    # below the threshold nothing refreshes: the stored range is kept
    x3 = x * 0.95
    plan2 = tune_blocked(g, jnp.asarray(x), quant=8, cache=None,
                         refresh=True, **_TK)
    assert range_drift(plan2.quantized, x3) <= DRIFT_THRESHOLD
    patched2, _, rep2 = apply_edge_updates(
        plan2, g, [(1, c)], (), widths=_TK["widths"], features=x3,
        requant_rows=np.arange(96))
    assert not rep2.requant_refreshed
    assert float(patched2.quantized.x_min) == float(plan2.quantized.x_min)
    assert patched2.quant_drift > 0.0
