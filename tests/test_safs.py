"""repro.safs — page store, cache, crash consistency, backend equivalence.

Everything filesystem-touching is `@pytest.mark.disk` and runs inside the
size-guarded `disk_tmp` fixture (conftest): scripts/run_tier1.sh re-runs
this subset in a bounded TMPDIR.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MultiVector, TieredStore, DEVICE, HOST
from repro.safs import (CrashPoint, PageCache, PageFile, PrefetchError,
                        Prefetcher, SafsBackend, WriteBehind,
                        WriteBehindError, coalesce_runs)
from repro.ckpt import checkpoint as ck

pytestmark = pytest.mark.disk


# ------------------------------------------------------------------ pagefile
def test_pagefile_roundtrip_and_cold_reopen(disk_tmp):
    path = os.path.join(disk_tmp, "a.pages")
    arr = np.arange(5000, dtype=np.float32).reshape(100, 50)
    pf = PageFile(path, page_size=4096, shape=arr.shape, dtype="float32")
    pf.write_pages(pf.split(arr))
    np.testing.assert_array_equal(pf.assemble(
        {i: pf.read_page(i) for i in pf.page_indices()}), arr)
    pf.close()
    # cold reopen recovers shape/dtype from the sidecar
    pf2 = PageFile(path)
    assert pf2.shape == (100, 50) and pf2.dtype == np.float32
    np.testing.assert_array_equal(pf2.assemble(
        {i: pf2.read_page(i) for i in pf2.page_indices()}), arr)
    pf2.delete()
    assert not os.path.exists(path)


def test_crash_after_journal_commit_redoes_on_reopen(disk_tmp):
    """Kill mid-flush AFTER the journal committed: reopening must replay
    the journal, so every page shows the NEW contents."""
    path = os.path.join(disk_tmp, "c.pages")
    old = np.zeros((64, 64), np.float32)
    new = np.full((64, 64), 7.0, np.float32)
    pf = PageFile(path, page_size=4096, shape=old.shape, dtype="float32")
    pf.write_pages(pf.split(old))
    with pytest.raises(CrashPoint):
        pf.write_pages(pf.split(new), crash_after_pages=1)  # died mid-patch
    pf.close()
    pf2 = PageFile(path)   # recovery replays the committed journal
    got = pf2.assemble({i: pf2.read_page(i) for i in pf2.page_indices()})
    np.testing.assert_array_equal(got, new)
    assert not os.path.exists(path + ".journal")
    pf2.close()


def test_crash_before_journal_commit_keeps_old_pages(disk_tmp):
    """Kill mid-flush BEFORE the commit trailer: the uncommitted journal is
    discarded and every page shows the OLD contents (no torn pages)."""
    path = os.path.join(disk_tmp, "d.pages")
    old = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    new = old + 100.0
    pf = PageFile(path, page_size=4096, shape=old.shape, dtype="float32")
    pf.write_pages(pf.split(old))
    with pytest.raises(CrashPoint):
        pf.write_pages(pf.split(new), crash_in_journal=True)
    pf.close()
    pf2 = PageFile(path)
    got = pf2.assemble({i: pf2.read_page(i) for i in pf2.page_indices()})
    np.testing.assert_array_equal(got, old)
    assert not os.path.exists(path + ".journal")
    pf2.close()


# --------------------------------------------------------- batched/vectored
def test_coalesce_runs_merges_adjacent_and_dedups():
    assert coalesce_runs([]) == []
    assert coalesce_runs([3]) == [(3, 1)]
    assert coalesce_runs([5, 0, 1, 2, 7, 6, 2]) == [(0, 3), (5, 3)]


def test_read_pages_batch_matches_per_page_reads(disk_tmp):
    """The vectored engine returns byte-identical pages to the PR-2
    single-pread path, across runs longer than one iovec batch."""
    path = os.path.join(disk_tmp, "b.pages")
    arr = np.random.default_rng(0).standard_normal(70000).astype(np.float32)
    pf = PageFile(path, page_size=4096, shape=arr.shape, dtype="float32")
    pf.write_pages(pf.split(arr))
    idxs = [0, 1, 2, 40, 41, 5, pf.n_pages - 1]
    got = pf.read_pages_batch(idxs)
    assert sorted(got) == sorted(set(idxs))
    for i in got:
        assert got[i] == pf.read_page(i)
    # whole-file batch assembles back to the array
    np.testing.assert_array_equal(
        pf.assemble(pf.read_pages_batch(pf.page_indices())), arr)
    pf.delete()


# --------------------------------------------------------------- page cache
def _cache(capacity_pages=4, page_size=64):
    written = []

    def writer(data_id, pages):
        written.append((data_id, dict(pages)))
        return len(pages) * page_size

    return PageCache(capacity_pages * page_size, page_size, writer), written


def test_cache_lru_eviction_and_dirty_writeback():
    c, written = _cache(capacity_pages=2)
    c.put("a", 0, b"x" * 64, dirty=True)
    c.put("a", 1, b"y" * 64, dirty=False)
    c.put("b", 0, b"z" * 64, dirty=True)      # evicts ("a",0) → write-back
    assert written == [("a", {0: b"x" * 64})]
    assert c.get("a", 0) is None              # miss: evicted
    assert c.get("b", 0) == b"z" * 64         # hit
    assert c.stats.host_bytes_written == 64
    # clean eviction writes nothing (write-avoidance / endurance)
    c.put("b", 1, b"w" * 64, dirty=False)     # evicts clean ("a",1)
    assert len(written) == 1


def test_cache_pinning_protects_recent_block():
    c, written = _cache(capacity_pages=2)
    c.put("recent", 0, b"r" * 64, dirty=True)
    c.pin("recent")
    c.put("other", 0, b"o" * 64, dirty=False)
    c.put("other", 1, b"p" * 64, dirty=False)  # pressure: must skip pinned
    assert c.peek("recent", 0)                 # survived (no LRU touch)
    c.unpin("recent")
    c.put("other", 2, b"q" * 64, dirty=False)  # now evictable → write-back
    assert written and written[0][0] == "recent"


def test_cache_flush_batches_per_file():
    c, written = _cache(capacity_pages=8)
    for i in range(3):
        c.put("f", i, bytes([i]) * 64, dirty=True)
    n = c.flush()
    assert n == 3 * 64
    assert written == [("f", {0: b"\0" * 64, 1: b"\1" * 64, 2: b"\2" * 64})]
    assert c.flush() == 0                      # idempotent: now clean


# ----------------------------------------------------- backend equivalence
def _twin_mvs(disk_tmp, n=384, widths=(4, 4, 2), seed=0, cache_pages=2,
              sub="pages"):
    """Identical MultiVectors on ram and safs stores (+ the dense oracle).
    Each call gets its own page-store root (`sub`): a SafsBackend owns its
    root exclusively — two live backends over one directory would race
    recovery against each other's async write-behind."""
    rng = np.random.default_rng(seed)
    blocks = [rng.standard_normal((n, w)).astype(np.float32)
              for w in widths]
    one_block = n * 4 * max(widths)   # every block but the newest spills
    ram = MultiVector(TieredStore(device_budget_bytes=one_block), n,
                      group_size=2, impl="ref")
    safs = MultiVector(
        TieredStore(device_budget_bytes=one_block, backend="safs",
                    backend_opts={"root": os.path.join(disk_tmp, sub),
                                  "cache_bytes": cache_pages * 4096}),
        n, group_size=2, impl="ref")
    for b in blocks:
        ram.append_block(jnp.asarray(b))
        safs.append_block(jnp.asarray(b))
    return ram, safs, np.concatenate(blocks, axis=1)


def test_backend_equivalence_all_eleven_ops(disk_tmp):
    """The eleven Table-1 MultiVector ops agree byte-for-byte between the
    ram emulation and the file-backed safs store (tiny page cache, so the
    safs side genuinely round-trips the filesystem)."""
    rng = np.random.default_rng(3)
    ram, safs, dense = _twin_mvs(disk_tmp)
    n, m = dense.shape
    small = jnp.asarray(rng.standard_normal((m, 3)), jnp.float32)
    other = jnp.asarray(rng.standard_normal((n, 5)), jnp.float32)
    diag = jnp.asarray(rng.standard_normal(m), jnp.float32)

    def both(f):
        a, b = np.asarray(f(ram)), np.asarray(f(safs))
        np.testing.assert_array_equal(a, b)
        return a

    # 1 MvTimesMatAddMv  2 MvTransMv  3 MvDot  4 MvNorm  5 CloneView
    both(lambda mv: mv.mv_times_mat(small))
    both(lambda mv: mv.mv_trans_mv(other, alpha=1.5))
    other_mv_r, other_mv_s, _ = _twin_mvs(disk_tmp, n=n, widths=(4, 4, 2),
                                          seed=7, sub="pages2")
    np.testing.assert_array_equal(np.asarray(ram.mv_dot(other_mv_r)),
                                  np.asarray(safs.mv_dot(other_mv_s)))
    both(lambda mv: mv.mv_norm())
    both(lambda mv: mv.clone_view([0, 3, 9]))
    # 6 ConvLayout
    both(lambda mv: mv.conv_layout())
    # 7 MvScale (lazy) + 8 MvScale-diag (materializing)
    ram.mv_scale(0.5), safs.mv_scale(0.5)
    ram.mv_scale_diag(diag), safs.mv_scale_diag(diag)
    both(lambda mv: mv.to_dense())
    # 9 MvAddMv
    np.testing.assert_array_equal(
        np.asarray(ram.mv_add_mv(2.0, other_mv_r, -1.0).to_dense()),
        np.asarray(safs.mv_add_mv(2.0, other_mv_s, -1.0).to_dense()))
    # 10 SetBlock
    blk = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    ram.set_block(1, blk), safs.set_block(1, blk)
    both(lambda mv: mv.to_dense())
    # 11 MvRandom (same key → same blocks on both backends)
    key = jax.random.PRNGKey(11)
    ram.mv_random(key, [4, 4]), safs.mv_random(key, [4, 4])
    both(lambda mv: mv.to_dense())
    # restart compression (the big out-of-core GEMM) rides on ops 1
    q = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ram.compress(q, [4]).to_dense()),
        np.asarray(safs.compress(q, [4]).to_dense()))
    # the safs side actually touched the medium
    assert safs.store.backend.stats.host_bytes_read > 0
    safs.store.close()


def test_safs_streams_from_disk_under_tiny_cache(disk_tmp):
    """Cache smaller than one block: every grouped pass re-reads pages from
    the file, and the result still matches the dense oracle."""
    rng = np.random.default_rng(5)
    n, widths = 512, (4, 4, 4, 4)
    store = TieredStore(
        device_budget_bytes=2 * n * 4 * 4, backend="safs",
        backend_opts={"root": os.path.join(disk_tmp, "p"),
                      "cache_bytes": 2 * 4096})
    mv = MultiVector(store, n, group_size=2, impl="ref")
    blocks = [rng.standard_normal((n, w)).astype(np.float32) for w in widths]
    for b in blocks:
        mv.append_block(jnp.asarray(b))
    # drain the write-behind queue: otherwise its victim buffer (legally)
    # serves the evicted pages and no read ever needs the medium
    store.flush()
    dense = np.concatenate(blocks, axis=1)
    small = rng.standard_normal((16, 3)).astype(np.float32)
    out = np.asarray(mv.mv_times_mat(jnp.asarray(small)))
    np.testing.assert_allclose(out, dense @ small, rtol=1e-5, atol=1e-5)
    d = store.backend.stats
    assert d.host_bytes_read > 0 and d.host_bytes_written > 0
    store.close()


def test_recent_block_pin_survives_flood_and_hits_on_reread(disk_tmp):
    """§3.4.4 regression: the newest spilled subspace block's pages
    must stay pinned through a sequential scan larger than
    the cache (LRU's pathological flood) and hit on the reorth re-read.
    Pre-fix, every demotion re-pinned — unrelated LRU spills stole the pin
    and the solver-path hit rate collapsed to ~0.02 (BENCH_safs.json)."""
    rng = np.random.default_rng(7)
    n, b, nblocks = 2048, 4, 8
    store = TieredStore(
        device_budget_bytes=n * 4 * b, backend="safs",   # one block
        backend_opts={"root": os.path.join(disk_tmp, "pin"),
                      "cache_bytes": 3 * n * 4 * b, "page_size": 4096,
                      "enable_prefetch": False})
    mv = MultiVector(store, n, group_size=2, impl="ref")
    for _ in range(nblocks):
        mv.append_block(jnp.asarray(rng.standard_normal((n, b)), np.float32))
    cache = store.backend.cache
    recent = mv.block_names()[-2]          # newest on-"SSD" block
    assert cache.pinned() == {recent}
    # flood: a full sequential scan (8 blocks through a 3-block cache)
    small = jnp.asarray(rng.standard_normal((nblocks * b, 2)), jnp.float32)
    mv.mv_times_mat(small)
    d = store.backend.stats
    hits0, misses0 = d.cache_hits, d.cache_misses
    # the pinned block's pages must all still be resident: pure hits
    np.asarray(store.get(recent))
    pf = store.backend.pagefile(recent)
    assert d.cache_hits == hits0 + pf.n_pages
    assert d.cache_misses == misses0
    # unrelated demotion churn must NOT steal the pin (the pre-fix bug):
    # spill a pile of non-subspace entries through the device budget
    for k in range(6):
        store.put(f"scratch/{k}", jnp.asarray(
            rng.standard_normal((n, b)), np.float32))
    assert cache.pinned() == {recent}
    # ...until the next append supersedes it
    mv.append_block(jnp.asarray(rng.standard_normal((n, b)), np.float32))
    assert cache.pinned() == {mv.block_names()[-2]}
    store.close()


def test_tier_semantics_identical_across_backends(disk_tmp):
    """Pin/demote/write-avoidance logic is backend-independent."""
    store = TieredStore(backend="safs",
                        backend_opts={"root": os.path.join(disk_tmp, "t")})
    store.put("x", jnp.ones((64, 4)))
    store.demote("x")
    assert store.tier_of("x") == HOST
    w1 = store.stats.host_bytes_written
    store.promote("x")
    assert store.tier_of("x") == DEVICE
    store.demote("x")     # not dirty — must not write again
    assert store.stats.host_bytes_written == w1
    np.testing.assert_array_equal(np.asarray(store.get("x")),
                                  np.ones((64, 4), np.float32))
    store.close()


# ----------------------------------------------------------------- prefetch
def test_prefetch_staging_is_correct_and_counted(disk_tmp):
    store = TieredStore(backend="safs",
                        backend_opts={"root": os.path.join(disk_tmp, "pf"),
                                      "cache_bytes": 1 << 20})
    arrs = {f"v{i}": np.random.default_rng(i).standard_normal(
        (256, 4)).astype(np.float32) for i in range(4)}
    for k, a in arrs.items():
        store.put(k, jnp.asarray(a), tier=HOST)
    store.flush()
    # fully cache-resident files are SKIPPED in O(1) (a fused full-pass
    # announcement must not burn the readahead window on no-op fills) ...
    store.prefetch(list(arrs))
    store.backend.prefetcher.drain()
    assert store.backend.prefetcher.stats()["files_prefetched"] == 0
    # ... and once the pages are gone, the same announcement stages them
    for k in arrs:
        store.backend.cache.invalidate(k)
    store.prefetch(list(arrs))
    store.backend.prefetcher.drain()
    assert store.backend.prefetcher.stats()["files_prefetched"] >= 1
    for k, a in arrs.items():
        np.testing.assert_array_equal(np.asarray(store.get(k)), a)
    store.close()


def test_prefetch_wait_propagates_reader_exception(disk_tmp):
    """A reader that dies mid-read must surface at wait(), not hang the
    consumer (PR-2's worker swallowed the exception silently)."""
    calls = []

    def reader(data_id):
        calls.append(data_id)
        if data_id == "bad":
            raise IOError("device gone")
        return 7

    pf = Prefetcher(reader, io_workers=1, depth=4)
    pf.schedule(["ok", "bad"])
    assert pf.wait("ok") >= 0.0
    with pytest.raises(PrefetchError):
        pf.wait("bad")
    assert pf.stats()["read_errors"] == 1
    # a re-offer after the failure is accepted again (error state cleared)
    pf.schedule(["bad"])
    with pytest.raises(PrefetchError):
        pf.wait("bad")
    pf.close()


def test_prefetch_wait_detects_dead_worker_pool():
    """wait() on a pool whose workers have exited raises instead of
    blocking forever (the satellite's hang case)."""
    pf = Prefetcher(lambda d: 0, io_workers=1, depth=2)
    with pf._cv:                      # simulate a crashed worker thread
        pf._done["never"] = __import__("threading").Event()
    pf.close()                        # workers exit; "never" still unset
    with pytest.raises(PrefetchError):
        pf.wait("never", poll=0.01)


def test_prefetch_depth_bounds_queue():
    """Ids offered past the readahead window are dropped, not queued."""
    import threading
    gate = threading.Event()
    pf = Prefetcher(lambda d: gate.wait(5) and 0, io_workers=1, depth=2)
    pf.schedule([f"f{i}" for i in range(8)])   # 1 in flight + 2 queued max
    st = pf.stats()
    assert st["files_dropped"] >= 5
    gate.set()
    pf.drain()
    pf.close()


# ------------------------------------------------------------ write-behind
def test_write_behind_ack_survives_kill_mid_demotion(disk_tmp):
    """Kill mid-demotion with a populated write-behind queue: every *acked*
    page (journal committed for its batch) must be recovered by journal
    replay on reopen; un-acked queued pages are simply lost (the sync
    barrier is flush/drain, which the kill precedes)."""
    path = os.path.join(disk_tmp, "wb.pages")
    old = np.zeros((128, 32), np.float32)
    new = np.full((128, 32), 9.0, np.float32)
    pf = PageFile(path, page_size=4096, shape=old.shape, dtype="float32")
    pf.write_pages(pf.split(old))

    # the drain thread's journaled writer dies after the journal committed
    # but mid in-place patch — the acked-but-torn state of a real kill
    def writer(data_id, pages):
        return pf.write_pages(pages, crash_after_pages=1)

    wb = WriteBehind(writer, max_pages=1024)
    wb.submit("wb", pf.split(new))            # demotion enters the queue
    with pytest.raises(WriteBehindError) as ei:
        wb.drain()
    assert isinstance(ei.value.__cause__, CrashPoint)
    wb.close()
    pf.close()

    pf2 = PageFile(path)    # process restart: replay the committed journal
    got = pf2.assemble({i: pf2.read_page(i) for i in pf2.page_indices()})
    np.testing.assert_array_equal(got, new)   # every acked page recovered
    assert not os.path.exists(path + ".journal")
    pf2.delete()


def test_write_behind_serves_queued_pages_and_orders_rewrites(disk_tmp):
    """The queue is a victim buffer: evicted-but-unwritten pages are served
    by lookup (never stale disk bytes), and a page resubmitted with newer
    bytes retires with the newer bytes."""
    import threading
    path = os.path.join(disk_tmp, "vb.pages")
    arr = np.arange(2048, dtype=np.float32)
    pf = PageFile(path, page_size=4096, shape=arr.shape, dtype="float32")
    gate = threading.Event()

    def slow_writer(data_id, pages):
        gate.wait(5)
        return pf.write_pages(pages)

    wb = WriteBehind(slow_writer, max_pages=64)
    pages_v1 = pf.split(arr)
    pages_v2 = pf.split(arr + 100.0)
    wb.submit("vb", pages_v1)
    wb.submit("vb", pages_v2)      # newer bytes for the same pages
    assert wb.lookup("vb", 0) == pages_v2[0]   # newest wins pre-retire
    assert wb.lookup("vb", 99) is None
    gate.set()
    wb.drain()
    assert wb.lookup("vb", 0) is None          # retired: disk is current
    np.testing.assert_array_equal(
        pf.assemble({i: pf.read_page(i) for i in pf.page_indices()}),
        arr + 100.0)
    wb.close()
    pf.delete()


def test_backend_read_your_evictions_via_write_behind(disk_tmp):
    """End-to-end: a dirty block evicted from a tiny cache into the
    write-behind queue reads back its newest bytes immediately."""
    store = TieredStore(backend="safs", backend_opts={
        "root": os.path.join(disk_tmp, "rye"), "cache_bytes": 2 * 4096})
    a = np.random.default_rng(1).standard_normal((600, 4)).astype(np.float32)
    b = np.random.default_rng(2).standard_normal((600, 4)).astype(np.float32)
    store.put("x", jnp.asarray(a), tier=HOST)
    store.put("y", jnp.asarray(b), tier=HOST)   # evicts x's dirty pages
    np.testing.assert_array_equal(np.asarray(store.get("x")), a)
    np.testing.assert_array_equal(np.asarray(store.get("y")), b)
    store.close()


def test_stale_clean_fill_cannot_outlive_write_behind_entry(disk_tmp):
    """Race reconciliation: a clean fill that reads old disk bytes while a
    concurrent eviction pushes newer bytes into the write-behind queue
    must not publish a stale clean line — while the batch is queued the
    queue shadows it, but once it retires the line would be served
    forever. The interleaving (evict wins the lock just before the
    reader's guarded insert) is forced by intercepting put_clean_if."""
    backend = SafsBackend(os.path.join(disk_tmp, "race"),
                          write_behind=True)
    old = np.arange(1024, dtype=np.float32)          # exactly one page
    new = np.full(1024, 7.0, dtype=np.float32)
    backend.store("x", old)
    backend.flush()                                   # disk holds `old`
    backend.cache.invalidate("x")                     # force a disk fill
    new_payload = backend.pagefile("x").split(new)[0]

    real_pci = backend.cache.put_clean_if
    fired = []

    def racing_pci(data_id, page, data, fresh):
        if data_id == "x" and page == 0 and not fired:
            fired.append(True)   # the eviction wins the lock first
            backend.writebehind.submit("x", {0: new_payload})
        return real_pci(data_id, page, data, fresh)

    backend.cache.put_clean_if = racing_pci
    np.testing.assert_array_equal(backend.load("x"), new)   # not stale
    backend.cache.put_clean_if = real_pci
    backend.writebehind.drain()       # batch retires: queue stops shadowing
    np.testing.assert_array_equal(backend.load("x"), new)
    backend.close()


def test_stale_clean_fill_guard_covers_retired_batch(disk_tmp):
    """The harder interleaving: the racing batch both lands AND retires
    inside the reader's read+insert window — a queue lookup alone comes
    back empty (the entry is gone) while the disk already holds the newer
    bytes, so only the submit-generation check can flag the stale fill."""
    backend = SafsBackend(os.path.join(disk_tmp, "race2"),
                          write_behind=True)
    old = np.arange(1024, dtype=np.float32)          # exactly one page
    new = np.full(1024, 9.0, dtype=np.float32)
    backend.store("x", old)
    backend.flush()
    backend.cache.invalidate("x")
    new_payload = backend.pagefile("x").split(new)[0]

    real_pci = backend.cache.put_clean_if
    fired = []

    def racing_pci(data_id, page, data, fresh):
        if data_id == "x" and page == 0 and not fired:
            fired.append(True)
            backend.writebehind.submit("x", {0: new_payload})
            backend.writebehind.drain()   # batch fully retires to disk
        return real_pci(data_id, page, data, fresh)

    backend.cache.put_clean_if = racing_pci
    np.testing.assert_array_equal(backend.load("x"), new)   # re-read disk
    backend.cache.put_clean_if = real_pci
    assert not backend.cache.peek("x", 0)   # stale fill was never inserted
    np.testing.assert_array_equal(backend.load("x"), new)
    backend.close()


def test_stale_fill_guard_generation_captured_before_probe(disk_tmp):
    """Ordering of the guard itself: the generation must be captured
    BEFORE the staleness probes. If an evict lands between a page's probe
    and a capture taken afterwards, and its batch retires while the disk
    read is in flight, both the queue lookup (entry gone) and a late-
    captured generation compare (bump already included) would pass on
    stale bytes. Forced here: the evict fires during another page's
    probe, the retire during the disk read."""
    backend = SafsBackend(os.path.join(disk_tmp, "race3"),
                          write_behind=True)
    old = np.arange(2048, dtype=np.float32)          # exactly two pages
    new_page0 = np.full(1024, 3.0, dtype=np.float32)
    backend.store("x", old)
    backend.flush()
    backend.cache.invalidate("x")
    pf = backend.pagefile("x")
    want = old.copy()
    want[:1024] = new_page0
    new_payload = pf.split(want)[0]

    real_get = backend.cache.get
    fired = []

    def probing_get(data_id, page, **kw):
        if data_id == "x" and page == 1 and not fired:
            fired.append(True)   # evict lands between probe(0) and capture
            backend.writebehind.submit("x", {0: new_payload})
        return real_get(data_id, page, **kw)

    real_read = pf.read_pages_batch

    def draining_read(idxs):
        out = real_read(idxs)        # reads the pre-retire (stale) bytes
        backend.writebehind.drain()  # batch retires mid-read
        return out

    backend.cache.get = probing_get
    pf.read_pages_batch = draining_read
    np.testing.assert_array_equal(backend.load("x"), want)
    backend.cache.get = real_get
    pf.read_pages_batch = real_read
    np.testing.assert_array_equal(backend.load("x"), want)
    backend.close()


# --------------------------------------------------- SSD-streamed SpMM image
def test_graph_operator_streams_image_from_safs(disk_tmp, small_graph):
    """stream_image=True spills the edge tiles into the page store and
    matmat reproduces the RAM-resident operator exactly while the tier
    accounts the streamed image reads."""
    from repro.graphs import pack_tiles
    from repro.core import GraphOperator
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    store = TieredStore(backend="safs", backend_opts={
        "root": os.path.join(disk_tmp, "img"), "cache_bytes": 8 * 4096})
    op_stream = GraphOperator(tm, store=store, impl="ref",
                              stream_image=True, image_chunk_bytes=1 << 16)
    # drain the write-behind queue: until the spilled chunks retire, its
    # victim buffer (legally) serves every miss and no read needs the disk
    store.flush()
    op_ram = GraphOperator(tm, impl="ref")
    x = jnp.asarray(np.random.default_rng(3)
                    .standard_normal((tm.shape[0], 4)), jnp.float32)
    y_stream = np.asarray(op_stream.matmat(x))
    np.testing.assert_allclose(y_stream, np.asarray(op_ram.matmat(x)),
                               rtol=1e-6, atol=1e-6)
    r0 = store.stats.host_bytes_read
    assert r0 > 0                         # image chunks counted as reads
    assert store.backend.stats.host_bytes_read > 0   # really hit the medium
    np.testing.assert_allclose(np.asarray(op_stream.matmat(x)), y_stream,
                               rtol=0, atol=0)
    assert store.stats.host_bytes_read > r0   # re-streamed per matmat
    op_stream.delete_image()
    assert not [d for d in store.backend.data_ids() if "tiles" in d]
    store.close()


def test_streamed_image_chunks_are_readonly(disk_tmp, small_graph):
    """The streamed image has no per-chunk dirty tracking: writing through
    a chunk name must raise, not silently diverge from the on-disk image."""
    from repro.graphs import pack_tiles
    from repro.core import GraphOperator, ReadOnlyError
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    store = TieredStore(backend="safs", backend_opts={
        "root": os.path.join(disk_tmp, "ro")})
    op = GraphOperator(tm, store=store, impl="ref", stream_image=True,
                       image_chunk_bytes=1 << 16)
    chunk = next(nm for nm in store.names() if "/tiles/" in nm)
    with pytest.raises(ReadOnlyError, match="read-only"):
        store.put(chunk, jnp.zeros((8, 8)))
    if op._has_coo:
        with pytest.raises(ReadOnlyError, match="read-only"):
            store.put(f"{op._name}/coo_vals", jnp.zeros(4))
    x = jnp.asarray(np.random.default_rng(4)
                    .standard_normal((tm.shape[0], 2)), jnp.float32)
    y0 = np.asarray(op.matmat(x))        # image unharmed by the attempts
    np.testing.assert_allclose(
        y0, np.asarray(GraphOperator(tm, impl="ref").matmat(x)),
        rtol=1e-6, atol=1e-6)
    op.delete_image()                    # delete path still allowed
    store.close()


def test_normal_operator_streams_both_images(disk_tmp):
    """NormalOperator.from_tiles forwards the streamed-image machinery to
    BOTH constituent operators (an SVD solve otherwise keeps two full
    images in RAM) and delete_image drops both spills."""
    from repro.graphs import pack_tiles, clustered_web_graph
    from repro.core import NormalOperator, svds
    n = 600
    r, c, v = clustered_web_graph(n, 4000, seed=2)
    tm_a = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    tm_at = pack_tiles(n, n, c, r, v, block_shape=(64, 64), min_block_nnz=4)
    store = TieredStore(backend="safs", backend_opts={
        "root": os.path.join(disk_tmp, "svd")})
    gram = NormalOperator.from_tiles(tm_a, tm_at, store=store, impl="ref",
                                     stream_image=True,
                                     image_chunk_bytes=1 << 16, name="pg")
    assert gram.stream_image
    store.flush()
    spilled = [d for d in store.backend.data_ids() if "tiles" in d]
    assert any(d.startswith("pg/A/") for d in spilled)
    assert any(d.startswith("pg/At/") for d in spilled)   # transpose too
    res = svds(gram.a, gram.at, 3, block_size=2, tol=1e-6,
               max_restarts=120, store=store, impl="ref")
    import scipy.sparse as sp
    import scipy.sparse.linalg as spla
    a = sp.coo_matrix((v, (r, c)), shape=(n, n)).tocsr()
    s_sc = np.sort(spla.svds(a, k=3, return_singular_vectors=False))
    np.testing.assert_allclose(np.sort(res.s), s_sc, rtol=1e-3, atol=1e-3)
    gram.delete_image()
    assert not [d for d in store.backend.data_ids() if "tiles" in d]
    store.close()


# --------------------------------------------------------------- checkpoint
def test_checkpoint_direct_from_pages_roundtrip(disk_tmp):
    """save_safs snapshots the page files themselves; restore_safs reopens
    them with contents intact — no array ever assembled for the copy."""
    root = os.path.join(disk_tmp, "live")
    store = TieredStore(backend="safs", backend_opts={"root": root})
    a = np.random.default_rng(9).standard_normal((300, 4)).astype(np.float32)
    b = np.random.default_rng(10).standard_normal((300, 2)).astype(np.float32)
    d = np.random.default_rng(11).standard_normal((300, 4)).astype(np.float32)
    store.put("mv/b0", jnp.asarray(a), tier=HOST)
    store.put("mv/b1", jnp.asarray(b), tier=HOST)
    # device-tier, never demoted (the pinned newest block of §3.4.4): the
    # snapshot must write it through rather than silently drop it
    store.put("mv/b2", jnp.asarray(d))
    store.pin("mv/b2")
    path = ck.save_safs(os.path.join(disk_tmp, "ck"), 7, store,
                        extra={"nev": 8})
    assert os.path.basename(path) == "step_0000000007"
    assert store.tier_of("mv/b2") == DEVICE      # residency unchanged
    backend, extra = ck.restore_safs(os.path.join(disk_tmp, "ck"), 7,
                                     os.path.join(disk_tmp, "restored"))
    assert extra == {"nev": 8}
    assert sorted(backend.data_ids()) == ["mv/b0", "mv/b1", "mv/b2"]
    np.testing.assert_array_equal(backend.load("mv/b0"), a)
    np.testing.assert_array_equal(backend.load("mv/b1"), b)
    np.testing.assert_array_equal(backend.load("mv/b2"), d)
    backend.close()
    store.close()


def test_checkpoint_safs_rejects_ram_store(disk_tmp):
    with pytest.raises(TypeError):
        ck.save_safs(os.path.join(disk_tmp, "ck"), 0, TieredStore())


# -------------------------------------------------------------- end to end
def test_eigsh_safs_matches_ram_backend(disk_tmp, small_graph):
    """The acceptance bar at test scale: Krylov–Schur with the subspace in
    page files converges to the same spectrum as the ram emulation, and the
    tier stays read-dominated (Table 3)."""
    from repro.graphs import pack_tiles
    from repro.core import GraphOperator, eigsh
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)

    def run(backend, opts):
        store = TieredStore(device_budget_bytes=2 * n * 4 * 4,
                            backend=backend, backend_opts=opts)
        op = GraphOperator(tm, store=store, impl="ref")
        res = eigsh(op, 4, block_size=4, tol=1e-7, max_restarts=60,
                    store=store, impl="ref", group_size=2)
        return res, store

    res_ram, _ = run("ram", None)
    res_safs, store = run("safs", {"root": os.path.join(disk_tmp, "sub"),
                                   "cache_bytes": 6 * 4096})
    np.testing.assert_allclose(np.sort(res_safs.eigenvalues),
                               np.sort(res_ram.eigenvalues), rtol=1e-5)
    s = store.stats
    assert s.host_bytes_read > 10 * s.host_bytes_written
    assert store.backend.stats.host_bytes_read > 0   # really hit the medium
    store.close()
