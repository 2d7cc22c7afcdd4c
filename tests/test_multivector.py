"""MultiVector (Table 1) ops vs dense numpy + tiering/laziness invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MultiVector, TieredStore, HOST, DEVICE


def make_mv(store, n=256, widths=(4, 4, 2), seed=0, group_size=8):
    rng = np.random.default_rng(seed)
    mv = MultiVector(store, n, group_size=group_size, impl="ref")
    blocks = [rng.standard_normal((n, w)).astype(np.float32) for w in widths]
    for b in blocks:
        mv.append_block(jnp.asarray(b))
    return mv, np.concatenate(blocks, axis=1)


def test_mv_times_mat_grouping_invariance(rng):
    store = TieredStore()
    mv, dense = make_mv(store, widths=(4, 4, 4, 2, 2))
    small = rng.standard_normal((16, 3)).astype(np.float32)
    outs = []
    for gs in (1, 2, 8):
        mv.group_size = gs
        outs.append(np.asarray(mv.mv_times_mat(jnp.asarray(small))))
    np.testing.assert_allclose(outs[0], dense @ small, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6, atol=1e-6)


def test_mv_trans_mv(rng):
    store = TieredStore()
    mv, dense = make_mv(store)
    other = rng.standard_normal((256, 5)).astype(np.float32)
    g = np.asarray(mv.mv_trans_mv(jnp.asarray(other), alpha=1.5))
    np.testing.assert_allclose(g, 1.5 * dense.T @ other, rtol=1e-4, atol=1e-4)


def test_lazy_scale_zero_io(rng):
    store = TieredStore()
    mv, dense = make_mv(store)
    # demote everything to "SSD", reset counters
    for i in range(mv.nblocks):
        store.unpin(mv._block_name(i))
        store.demote(mv._block_name(i))
    store.reset_stats()
    mv.mv_scale(2.0)                      # lazy: no bytes moved
    assert store.stats.host_bytes_read == 0
    assert store.stats.host_bytes_written == 0
    small = rng.standard_normal((10, 2)).astype(np.float32)
    out = np.asarray(mv.mv_times_mat(jnp.asarray(small)))
    np.testing.assert_allclose(out, 2.0 * dense @ small, rtol=1e-5, atol=1e-5)


def test_most_recent_block_pinned():
    store = TieredStore(device_budget_bytes=256 * 4 * 4)   # one block
    mv, _ = make_mv(store)
    names = [mv._block_name(i) for i in range(mv.nblocks)]
    assert store.tier_of(names[-1]) == DEVICE       # newest pinned
    assert store.tier_of(names[0]) == HOST          # older demoted


def test_mv_dot_norm_scale_diag(rng):
    store = TieredStore()
    mv, dense = make_mv(store)
    mv2, dense2 = make_mv(store, seed=1)
    np.testing.assert_allclose(np.asarray(mv.mv_dot(mv2)),
                               np.sum(dense * dense2, axis=0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(mv.mv_norm()),
                               np.linalg.norm(dense, axis=0), rtol=1e-5)
    d = rng.standard_normal(10).astype(np.float32)
    mv.mv_scale_diag(jnp.asarray(d))
    np.testing.assert_allclose(np.asarray(mv.to_dense()), dense * d[None, :],
                               rtol=1e-5, atol=1e-5)


def test_clone_view_and_compress(rng):
    store = TieredStore()
    mv, dense = make_mv(store)
    view = np.asarray(mv.clone_view([1, 4, 9]))
    np.testing.assert_allclose(view, dense[:, [1, 4, 9]], rtol=1e-6)
    q = rng.standard_normal((10, 4)).astype(np.float32)
    out = mv.compress(jnp.asarray(q), [2, 2])
    np.testing.assert_allclose(np.asarray(out.to_dense()), dense @ q,
                               rtol=1e-4, atol=1e-4)


def test_device_budget_eviction():
    store = TieredStore(device_budget_bytes=256 * 4 * 6)  # ~1.5 blocks
    mv, _ = make_mv(store, widths=(4, 4, 4))
    dev_bytes = store.device_bytes()
    assert dev_bytes <= 256 * 4 * 8  # pinned newest + at most slack
    # reading an evicted block counts as SSD read
    store.reset_stats()
    mv.block(0)
    assert store.stats.host_bytes_read > 0


def test_write_avoidance_on_clean_demote():
    store = TieredStore()
    store.put("x", jnp.ones((64, 4)))
    store.demote("x")
    w1 = store.stats.host_bytes_written
    store.promote("x")
    store.demote("x")     # not dirty — must not write again
    assert store.stats.host_bytes_written == w1


def test_readonly_entry_rejects_overwrite():
    from repro.core import ReadOnlyError
    store = TieredStore()
    store.put("img/c0", jnp.ones((32, 4)), tier=HOST, readonly=True)
    with pytest.raises(ReadOnlyError, match="read-only"):
        store.put("img/c0", jnp.zeros((32, 4)))
    # unchanged — the guard fired before any bytes moved
    np.testing.assert_array_equal(np.asarray(store.get("img/c0")),
                                  np.ones((32, 4), np.float32))
    store.delete("img/c0")              # delete stays allowed (delete_image)
    store.put("img/c0", jnp.zeros((32, 4)))   # fresh entry is writable again
