"""Fused streamed-pass engine (§3.4.3): parity + byte-exact I/O bounds."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GraphOperator, MultiVector, SubspacePass, TieredStore,
                        bcgs2, cholqr, eigsh, ortho_error)
from repro.core import stream
from repro.core.krylov_schur import _expand
from repro.graphs import pack_tiles
from repro.obs import trace

# the all-blocks-demoted measurement fixture is shared with the bench so
# both assert against the identical I/O state (tier-1 runs pytest from the
# repo root via `python -m`, so `benchmarks` is importable)
from benchmarks.bench_subspace_io import _demoted_mv


# --------------------------------------------------------------- parity
def test_fused_bcgs2_matches_unfused():
    rng = np.random.default_rng(3)
    n = 384
    store = TieredStore()
    basis = MultiVector(store, n, impl="ref")
    qs = np.linalg.qr(rng.standard_normal((n, 12)))[0].astype(np.float32)
    for j in range(0, 12, 4):
        basis.append_block(jnp.asarray(qs[:, j:j + 4]))
    w = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    qf, hf, rf = bcgs2(basis, w, impl="ref", fused=True)
    qu, hu, ru = bcgs2(basis, w, impl="ref", fused=False)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hu),
                               rtol=1e-5, atol=1e-5)
    # both Qs orthogonal to the basis and to themselves
    for q in (qf, qu):
        assert float(jnp.max(jnp.abs(basis.mv_trans_mv(q)))) < 1e-4
    # same subspace: |QfᵀQu| ≈ I up to signs
    g = np.abs(np.asarray(qf).T @ np.asarray(qu))
    np.testing.assert_allclose(g, np.eye(4), atol=1e-3)


def test_compress_fused_matches_unfused_exactly():
    rng = np.random.default_rng(4)
    store = TieredStore()
    mv = _demoted_mv(store, n=256, b=4, nb=6, seed=4)
    q = jnp.asarray(rng.standard_normal((24, 12)), jnp.float32)
    outf = mv.compress(q, [4, 4, 4], fused=True)
    outu = mv.compress(q, [4, 4, 4], fused=False)
    # identical accumulation order per output block → bit-for-bit on ref
    np.testing.assert_array_equal(np.asarray(outf.to_dense()),
                                  np.asarray(outu.to_dense()))


def test_krylov_invariant_with_bcgs2_h_convention(small_graph):
    """Regression for the unified H convention: _expand now takes its H
    column from bcgs2 (h1 + h2, the second-pass correction included —
    previously hand-inlined CGS2 discarded h2). The Krylov invariant
    A·q = V·h + q_next·r must hold with the RETURNED h, on both paths."""
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    for fused in (True, False):
        store = TieredStore()
        op = GraphOperator(tm, store=store, impl="ref")
        mv = MultiVector(store, op.n, impl="ref")
        rng = np.random.default_rng(7)
        q = jnp.asarray(np.linalg.qr(rng.standard_normal((op.n, 4)))[0],
                        jnp.float32)
        h = np.zeros((0, 0))
        for step in range(3):
            aq = np.asarray(op.matmat(q))
            q_next, h, r_next = _expand(op, mv, q, h, "ref",
                                        fused_passes=fused)
            m = h.shape[0]
            h_col = h[:, m - 4:]
            recon = (np.asarray(mv.to_dense()) @ h_col
                     + np.asarray(q_next) @ r_next)
            np.testing.assert_allclose(recon, aq, rtol=2e-3, atol=2e-3,
                                       err_msg=f"fused={fused} step={step}")
            q = q_next


def test_eigsh_fused_vs_unfused_spectrum(small_graph):
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    evs = {}
    for fused in (True, False):
        store = TieredStore()
        op = GraphOperator(tm, store=store, impl="ref")
        res = eigsh(op, 4, block_size=4, tol=1e-6, max_restarts=100,
                    store=store, impl="ref", fused_passes=fused)
        assert res.converged
        evs[fused] = np.sort(res.eigenvalues)
    np.testing.assert_allclose(evs[True], evs[False], rtol=1e-5)


@pytest.mark.disk
def test_eigsh_fused_vs_unfused_spectrum_safs(disk_tmp, small_graph):
    """Parity with the subspace genuinely in SAFS page files."""
    import os
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    evs = {}
    for fused in (True, False):
        store = TieredStore(
            device_budget_bytes=2 * n * 4 * 4, backend="safs",
            backend_opts={"root": os.path.join(disk_tmp, f"f{fused}"),
                          "cache_bytes": 3 * n * 4 * 4})
        op = GraphOperator(tm, store=store, impl="ref")
        res = eigsh(op, 4, block_size=4, tol=1e-6, max_restarts=100,
                    store=store, impl="ref", fused_passes=fused)
        assert res.converged
        evs[fused] = np.sort(res.eigenvalues)
        store.close()
    np.testing.assert_allclose(evs[True], evs[False], rtol=1e-5)


# ------------------------------------------------------------ byte counts
def test_fused_expansion_reads_at_most_2x_subspace():
    """An expansion at NB blocks must read the host tier at most ~2× the
    subspace size (two project_out passes); the unfused path reads 4×."""
    n, b, nb = 512, 4, 8
    sub_bytes = n * b * 4 * nb
    w = jnp.asarray(np.random.default_rng(1).standard_normal((n, b)),
                    jnp.float32)
    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb)
    store.reset_stats()
    bcgs2(mv, w, impl="ref", fused=True)
    assert store.stats.host_bytes_read == 2 * sub_bytes
    assert store.stats.passes == 2

    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb)
    store.reset_stats()
    bcgs2(mv, w, impl="ref", fused=False)
    assert store.stats.host_bytes_read == 4 * sub_bytes
    assert store.stats.passes == 4


def test_fused_compress_reads_subspace_exactly_once():
    """Restart compression must read the subspace EXACTLY once regardless
    of k_keep (the pre-fusion path paid one full pass per output block)."""
    n, b, nb = 512, 4, 8
    sub_bytes = n * b * 4 * nb
    for k_blocks in (2, 4, 6):
        q = jnp.asarray(np.random.default_rng(2)
                        .standard_normal((nb * b, k_blocks * b)), jnp.float32)
        store = TieredStore()
        mv = _demoted_mv(store, n, b, nb)
        store.reset_stats()
        mv.compress(q, [b] * k_blocks, fused=True)
        assert store.stats.host_bytes_read == sub_bytes, k_blocks
        assert store.stats.passes == 1

        store = TieredStore()
        mv = _demoted_mv(store, n, b, nb)
        store.reset_stats()
        mv.compress(q, [b] * k_blocks, fused=False)
        assert store.stats.host_bytes_read == k_blocks * sub_bytes


def test_multi_consumer_pass_shares_one_read():
    """N consumers on one pass cost one streamed read, not N."""
    n, b, nb = 512, 4, 6
    sub_bytes = n * b * 4 * nb
    rng = np.random.default_rng(5)
    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb, seed=5)
    dense = np.asarray(mv.to_dense())
    other = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
    small = jnp.asarray(rng.standard_normal((nb * b, 2)), jnp.float32)
    store.reset_stats()
    p = SubspacePass(mv)
    hg = p.add_gram(other)
    hm = p.add_matmul(small)
    hn = p.add_norm()
    p.run()
    assert store.stats.host_bytes_read == sub_bytes
    assert store.stats.passes == 1
    np.testing.assert_allclose(np.asarray(hg.value), dense.T @ other,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hm.value[0]),
                               dense @ np.asarray(small),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hn.value),
                               np.linalg.norm(dense, axis=0), rtol=1e-5)


def test_handle_before_run_raises():
    store = TieredStore()
    mv = _demoted_mv(store, n=128, b=2, nb=2)
    p = SubspacePass(mv)
    h = p.add_norm()
    with pytest.raises(RuntimeError, match="before run"):
        h.value


def test_pass_is_single_use():
    """Consumers accumulate across visits; a silent re-run would double
    every result. The second run must be loud."""
    store = TieredStore()
    mv = _demoted_mv(store, n=128, b=2, nb=2)
    p = SubspacePass(mv)
    p.add_norm()
    p.run()
    with pytest.raises(RuntimeError, match="already ran"):
        p.run()


def test_compress_acc_budget_chunks_passes():
    """A pass_acc_bytes smaller than k_keep·n·4 must chunk the fused
    compress into multiple passes (bounded device accumulators at
    billion-row scale) without changing the result — and each output
    column still rides exactly one of the passes."""
    n, b, nb = 256, 4, 6
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((nb * b, 12)), jnp.float32)
    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb, seed=13)
    one_pass = np.asarray(mv.compress(q, [4, 4, 4]).to_dense())
    store.reset_stats()
    # budget fits one 4-wide accumulator (n*4*4 bytes) → 3 passes
    chunked = mv.compress(q, [4, 4, 4], pass_acc_bytes=n * 4 * 4)
    assert store.stats.passes == 3
    np.testing.assert_array_equal(np.asarray(chunked.to_dense()), one_pass)


# ------------------------------------------------------- compiled visits
def _basis(store, n, b, nb, impl, seed):
    """nb orthonormal blocks of width b, every one on the slow tier."""
    rng = np.random.default_rng(seed)
    qs = np.linalg.qr(rng.standard_normal((n, b * nb)))[0].astype(np.float32)
    mv = MultiVector(store, n, impl=impl)
    for j in range(nb):
        mv.append_block(jnp.asarray(qs[:, j * b:(j + 1) * b]))
    for name in mv.block_names():
        store.unpin(name)
        store.demote(name)
    return mv, qs


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_compiled_visits_match_reference_math(impl):
    """The compiled Gram, TSGEMM and project-out visits at an n that is
    not a multiple of the 512-row interval (the Pallas path pads every
    block inside the visit's program) against plain float64 math."""
    n, b, nb = 1000, 4, 4
    rng = np.random.default_rng(21)
    mv, v = _basis(TieredStore(), n, b, nb, impl, seed=21)
    w = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    w64, v64 = np.asarray(w, np.float64), v.astype(np.float64)

    # project_out: block-MGS order, so W = V·h + w' holds by telescoping
    h, w1 = mv.project_out(w)
    np.testing.assert_allclose(np.asarray(h), v64.T @ w64,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(w1), w64 - v64 @ v64.T @ w64,
                               rtol=1e-4, atol=1e-4)

    np.testing.assert_allclose(np.asarray(mv.mv_trans_mv(w, alpha=2.0)),
                               2.0 * v64.T @ w64, rtol=1e-4, atol=1e-4)

    # compress into 4 accumulators from one streamed read
    q = jnp.asarray(rng.standard_normal((nb * b, 4 * b)), jnp.float32)
    out = mv.compress(q, [b] * 4)
    np.testing.assert_allclose(np.asarray(out.to_dense()),
                               v64 @ np.asarray(q, np.float64),
                               rtol=1e-4, atol=1e-4)

    # CGS2 through the compiled visits and the compiled CholQR2
    q2, h2, r2 = bcgs2(mv, w, impl=impl)
    assert ortho_error(q2) < 1e-4
    assert float(jnp.max(jnp.abs(mv.mv_trans_mv(q2)))) < 1e-4
    recon = v @ np.asarray(h2) + np.asarray(q2) @ np.asarray(r2)
    np.testing.assert_allclose(recon, np.asarray(w), rtol=2e-3, atol=2e-3)


def test_visit_programs_compile_once():
    """One program serves every row offset of a warm compress; after one
    warm CGS2, Gram pass and compress, passes over bases of other block
    counts and offsets reuse the same programs, and the pass span counts
    every visit as compiled. (The TSGEMM visit's program is keyed by the
    shape of its small operand, the basis width: a restart compresses at
    one width.)"""
    n, b = 203, 4
    rng = np.random.default_rng(22)
    programs = (stream._project_visit, stream._project_first_visit,
                stream._matmul_visit, stream._gram_visit, cholqr)

    store = TieredStore()
    w = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((6 * b, 4 * b)), jnp.float32)
    cold = [f._cache_size() for f in programs]
    warm = _basis(store, n, b, 3, "ref", seed=1)[0]
    bcgs2(warm, w, impl="ref")
    warm.mv_trans_mv(w)
    _basis(store, n, b, 6, "ref", seed=2)[0].compress(q, [b] * 4)
    sizes = [f._cache_size() for f in programs]
    assert all(s - c <= 1 for s, c in zip(sizes, cold)), (cold, sizes)

    tracer = trace.Tracer()
    visited = 0
    with trace.tracing(tracer):
        for nb in (1, 3, 5):
            basis = _basis(store, n, b, nb, "ref", seed=nb)[0]
            bcgs2(basis, w, impl="ref")
            basis.mv_trans_mv(w, alpha=0.5)
            visited += (2 + 1) * nb
        _basis(store, n, b, 6, "ref", seed=7)[0].compress(q, [b] * 4)
        visited += 6
        # a walk over blocks 3..8 of a 9-block basis: other offsets
        p = SubspacePass(_basis(store, n, b, 9, "ref", seed=9)[0],
                         block_ids=range(3, 9))
        p.add_matmul(q, [b] * 4)
        p.run()
        visited += 6
    assert [f._cache_size() for f in programs] == sizes

    passes = [r["args"] for r in tracer.records()
              if r["type"] == "span" and r["name"] == "pass.subspace"]
    assert len(passes) == 3 * (2 + 1) + 2
    assert all(a["compiled_visits"] == a["blocks"] for a in passes)
    assert all(a["eager_visits"] == 0 for a in passes)
    assert sum(a["compiled_visits"] for a in passes) == visited


def test_eager_visits_counted():
    """Generic visitors stay eager and are counted as such."""
    store = TieredStore()
    mv = _demoted_mv(store, n=128, b=2, nb=3)
    tracer = trace.Tracer()
    with trace.tracing(tracer):
        p = SubspacePass(mv)
        p.add_norm()
        p.add_gram(jnp.ones((128, 2), jnp.float32))
        p.run()
    (args,) = [r["args"] for r in tracer.records()
               if r["type"] == "span" and r["name"] == "pass.subspace"]
    assert args["compiled_visits"] == 3 and args["eager_visits"] == 3


# ------------------------------------------------------- readahead routing
def test_small_reductions_announce_full_pass(monkeypatch):
    """mv_dot / mv_norm / clone_view / mv_add_mv used to stream with no
    prefetch at all; through the pass engine every walk announces its full
    block list up front."""
    n, b, nb = 256, 2, 4
    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb, seed=6)
    mv2 = _demoted_mv(store, n, b, nb, seed=7)
    calls = []
    orig = store.prefetch
    monkeypatch.setattr(store, "prefetch",
                        lambda names: (calls.append(list(names)),
                                       orig(names))[1])
    for op in (mv.mv_norm, lambda: mv.mv_dot(mv2),
               lambda: mv.clone_view([0, 3]),
               lambda: mv.mv_add_mv(1.0, mv2, 2.0),
               lambda: mv.mv_scale_diag(jnp.ones(nb * b, jnp.float32))):
        calls.clear()
        op()
        # first announcement covers the whole pass
        assert calls and set(calls[0]) >= set(mv.block_names())


def test_mv_scale_diag_single_pass():
    """MvScale2 through the pass engine: one announced streamed pass, the
    whole subspace read exactly once, blocks scaled in place (previously a
    bare get/put loop with no prefetch announcement)."""
    n, b, nb = 256, 2, 4
    store = TieredStore()
    mv = _demoted_mv(store, n, b, nb, seed=10)
    dense = np.asarray(mv.to_dense())
    vec = jnp.asarray(np.random.default_rng(10).standard_normal(nb * b),
                      jnp.float32)
    store.reset_stats()
    mv.mv_scale_diag(vec)
    assert store.stats.passes == 1
    assert store.stats.pass_bytes_read == n * b * 4 * nb
    np.testing.assert_allclose(np.asarray(mv.to_dense()),
                               dense * np.asarray(vec)[None, :],
                               rtol=1e-6, atol=1e-6)


def test_mv_dot_add_mv_still_correct():
    store = TieredStore()
    mv = _demoted_mv(store, n=256, b=2, nb=4, seed=8)
    mv2 = _demoted_mv(store, n=256, b=2, nb=4, seed=9)
    d1, d2 = np.asarray(mv.to_dense()), np.asarray(mv2.to_dense())
    np.testing.assert_allclose(np.asarray(mv.mv_dot(mv2)),
                               np.sum(d1 * d2, axis=0), rtol=1e-4, atol=1e-5)
    out = mv.mv_add_mv(0.5, mv2, -2.0)
    np.testing.assert_allclose(np.asarray(out.to_dense()),
                               0.5 * d1 - 2.0 * d2, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- micro-perf
def test_device_byte_counter_tracks_scan():
    """The running device-byte counter (replacing per-eviction full scans)
    must agree with a fresh scan through put/promote/demote/delete/
    overwrite churn."""
    store = TieredStore(device_budget_bytes=256 * 4 * 6)
    rng = np.random.default_rng(11)

    def scan():
        from repro.core.tiered import DEVICE
        return sum(e.nbytes for e in store._entries.values()
                   if e.tier == DEVICE)

    for i in range(8):
        store.put(f"x{i}", jnp.asarray(rng.standard_normal((256, 2)),
                                       jnp.float32))
        assert store.device_bytes() == scan()
    store.put("x3", jnp.asarray(rng.standard_normal((256, 4)), jnp.float32))
    assert store.device_bytes() == scan()
    store.demote("x3")
    store.promote("x5")
    store.delete("x6")
    store.put("y", jnp.ones((256, 1)), tier="host")
    assert store.device_bytes() == scan()
    # budget respected (nothing pinned here)
    assert store.device_bytes() <= 256 * 4 * 6
    # overwrite while near budget: eviction must not demote the stale
    # entry being replaced nor double-release it from the counter
    store.put("x7", jnp.asarray(rng.standard_normal((256, 4)), jnp.float32))
    assert store.device_bytes() == scan()
    assert store.device_bytes() <= 256 * 4 * 6


def test_passes_counter_in_stats_dict():
    store = TieredStore()
    mv = _demoted_mv(store, n=128, b=2, nb=3)
    store.reset_stats()
    mv.mv_norm()
    d = store.stats.as_dict()
    assert d["passes"] == 1
    assert d["bytes_per_pass"] == 128 * 2 * 4 * 3
