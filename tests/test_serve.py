"""Multi-tenant serving layer: namespaces, arbiter, scheduler, service.

Covers the three layers of the eigensolver-as-a-service stack plus its
cross-cutting invariants:

  * `TieredStore.namespace()` isolation + per-namespace accounting, with
    parent == Σ namespaces reconciliation (logical AND physical);
  * concurrent-hammer reconciliation of the shared IOStats / LRU
    bookkeeping (the thread-safety fix: counters must balance EXACTLY);
  * `BudgetArbiter` priority splits and the fused-compress cap riding a
    session's allotment (a small-budget session chunks its compress);
  * `SolveScheduler` priority dispatch, admission control and
    checkpoint-based preemption (deterministic stub sessions);
  * the disk-marked E2E: 4 mixed jobs (eigsh + lobpcg + cluster) over ONE
    shared SafsBackend with ≥1 preempt/resume, spectra matching serial
    runs at rtol 1e-5 and exact per-namespace byte reconciliation.
"""
import json
import threading
import time
import types

import numpy as np
import pytest

from repro.core.tiered import IOStats, TieredStore
from repro.serve import (AdmissionError, BudgetArbiter, JobSpec,
                         PagedConfig, PagedKVCache, PreemptFlag,
                         SolveScheduler, SolveSession, build_service,
                         validate_report)
from repro.serve.session import DONE, PENDING, RUNNING, SUSPENDED


# ===================================================== namespaces (resource)
def test_namespace_isolation_and_accounting():
    store = TieredStore(device_budget_bytes=1 << 20)
    a = store.namespace("a")
    b = store.namespace("b")
    a.put("x", np.full((64,), 1.0, np.float32))
    b.put("x", np.full((64,), 2.0, np.float32))
    assert float(np.asarray(a.get("x"))[0]) == 1.0
    assert float(np.asarray(b.get("x"))[0]) == 2.0
    assert a.names() == ["x"] and b.names() == ["x"]
    # host-tier traffic lands in the owning session's bucket and the
    # parent's counters alike: parent == Σ namespaces, field by field
    a.demote("x"), b.demote("x")
    a.get("x"), b.get("x")
    ns = store.namespace_stats()
    for field in ("host_bytes_written", "host_bytes_read",
                  "host_writes", "host_reads"):
        total = sum(d[field] for d in ns.values())
        assert total == getattr(store.stats, field) > 0, field


def test_namespace_drop_reclaims_but_stats_survive():
    store = TieredStore(device_budget_bytes=1 << 20)
    a = store.namespace("a")
    a.put("x", np.zeros(64, np.float32))
    a.demote("x")
    written = store.namespace_stats()["a"]["host_bytes_written"]
    assert written > 0
    a.close()
    assert store.names() == []
    # post-mortem accounting survives the drop (the serve report needs it)
    assert store.namespace_stats()["a"]["host_bytes_written"] == written
    # a fresh facade under the same id starts empty
    assert store.namespace("a").names() == []


def test_namespace_budget_evicts_own_entries_only():
    store = TieredStore(device_budget_bytes=1 << 30)
    a, b = store.namespace("a"), store.namespace("b")
    blk = np.zeros((1024,), np.float32)          # 4 KiB each
    for i in range(4):
        a.put(f"v{i}", blk + i)
        b.put(f"v{i}", blk + i)
    store.set_namespace_budget("a", 8 << 10)     # room for 2 of a's blocks
    assert sum(a.tier_of(f"v{i}") == "device" for i in range(4)) <= 2
    assert all(b.tier_of(f"v{i}") == "device" for i in range(4))
    # values survive eviction (demoted, not dropped)
    assert float(np.asarray(a.get("v0"))[0]) == 0.0


# ================================================ satellite b: thread safety
def test_iostats_concurrent_hammer_reconciles_exactly():
    stats = IOStats()
    n_threads, n_iter = 8, 2000

    def hammer():
        for _ in range(n_iter):
            stats.add(host_reads=1, host_bytes_read=128)

    ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert stats.host_reads == n_threads * n_iter
    assert stats.host_bytes_read == n_threads * n_iter * 128


def test_store_concurrent_sessions_reconcile_exactly():
    """N threads, one store, one namespace each: per-ns logical sums must
    equal the parent's counters to the byte (the unsynchronized-increment
    bug this PR fixes would lose updates here)."""
    store = TieredStore(device_budget_bytes=32 << 10)   # force LRU churn
    n_threads, n_iter = 6, 120
    blk = np.zeros(512, np.float32)                     # 2 KiB

    def worker(sid):
        ns = store.namespace(sid)
        for i in range(n_iter):
            ns.put(f"v{i % 8}", blk + i)
            ns.get(f"v{i % 8}")

    ts = [threading.Thread(target=worker, args=(f"s{k}",))
          for k in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    ns = store.namespace_stats()
    for field in ("host_bytes_written", "host_bytes_read",
                  "host_reads", "host_writes",
                  "cache_hits", "cache_misses"):
        assert sum(d[field] for d in ns.values()) == \
            getattr(store.stats, field), field
    # device residency bookkeeping also balances
    assert store.device_bytes() <= 32 << 10


# ======================================================= arbiter + budgets
def test_arbiter_priority_split_and_recompute():
    store = TieredStore(device_budget_bytes=12 << 20)
    arb = BudgetArbiter(store, device_budget=12 << 20)
    s_lo = arb.admit("lo", priority=0)
    assert s_lo == 12 << 20                      # alone: the whole budget
    s_hi = arb.admit("hi", priority=3)
    # weights 1:4 over 12 MiB (floor division per share)
    assert arb.allotment("lo") == (12 << 20) * 1 // 5
    assert arb.allotment("hi") == (12 << 20) * 4 // 5
    assert s_hi == arb.allotment("hi")
    assert store.namespace_budget("lo") == arb.allotment("lo")
    arb.release("hi")
    assert arb.allotment("lo") == 12 << 20       # share redistributed
    assert store.namespace_budget("hi") is None
    st = arb.stats_dict()
    assert st["admits"] == 2 and st["releases"] == 1


def test_arbiter_min_share_floor():
    store = TieredStore(device_budget_bytes=4 << 20)
    arb = BudgetArbiter(store, device_budget=4 << 20, min_share=1 << 20)
    arb.admit("lo", priority=0)
    arb.admit("hi", priority=100)
    assert arb.allotment("lo") == 1 << 20        # floored, not starved
    assert arb.stats_dict()["oversubscribed"] in (True, False)


# ================================== satellite a: compress cap ← allotment
def test_compress_chunks_under_small_session_allotment():
    """A session whose arbiter allotment is small must chunk its fused
    compress pass (multiple SubspacePass runs) instead of materializing
    k_keep·n·4 transient accumulator bytes; an uncapped session does the
    whole compress in ONE pass."""
    from repro.core.multivector import MultiVector
    n, widths = 40_000, (4, 4, 4)                # 640 KiB per output block
    q = np.eye(12, dtype=np.float32)

    def run(budget):
        store = TieredStore(device_budget_bytes=1 << 30)
        ns = store.namespace("s")
        if budget is not None:
            store.set_namespace_budget("s", budget)
        mv = MultiVector(ns, n, name="V")
        rng = np.random.default_rng(0)
        for w in widths:
            mv.append_block(rng.standard_normal((n, w)).astype(np.float32))
        before = store.stats.passes
        out = mv.compress(q, widths)
        return store.stats.passes - before, out.to_dense()

    p_big, d_big = run(None)
    # 2 MiB allotment → 1 MiB compress cap → 3 single-width pass groups
    p_small, d_small = run(2 << 20)
    assert p_big == 1
    assert p_small == 3
    np.testing.assert_allclose(d_small, d_big, rtol=1e-6)


# ============================================================== job specs
def test_jobspec_validation():
    with pytest.raises(ValueError, match="unknown kind"):
        JobSpec("j", kind="svd")
    with pytest.raises(ValueError, match="unknown job-spec fields"):
        JobSpec.from_dict({"job_id": "j", "frobnicate": 1})
    with pytest.raises(ValueError, match="job_id"):
        JobSpec.from_dict({"kind": "eigsh"})
    assert JobSpec("c", kind="cluster").graph == "planted"
    assert JobSpec("l", kind="lobpcg").method == "lobpcg"


# ============================================== scheduler (stub sessions)
class _StubSession:
    """Duck-typed SolveSession: runs until released (or preempted), so
    scheduler decisions can be single-stepped deterministically."""

    def __init__(self, jid, priority, *, instant=False):
        self.spec = types.SimpleNamespace(job_id=jid, priority=priority,
                                          preemptible=True)
        self.state = PENDING
        self.guard = PreemptFlag()
        self.ckpt_root = "stub"
        self.preemptions = 0
        self.release = threading.Event()
        if instant:
            self.release.set()

    def mark_queued(self):
        pass

    def mark_dequeued(self):
        pass

    @property
    def can_preempt(self):
        return self.state == RUNNING and not self.guard.requested()

    def progress(self):
        return {"state": self.state}

    def run(self):
        self.state = RUNNING
        while not self.release.is_set():
            if self.guard.requested():
                self.preemptions += 1
                self.state = SUSPENDED
                return
            time.sleep(0.002)
        self.state = DONE


def _mini_sched(max_concurrent=1, max_queued=64):
    store = TieredStore(device_budget_bytes=8 << 20)
    arb = BudgetArbiter(store, device_budget=8 << 20)
    return SolveScheduler(store, arb, max_concurrent=max_concurrent,
                          max_queued=max_queued, poll_interval=0.002)


def test_scheduler_runs_in_priority_order():
    sched = _mini_sched(max_concurrent=1)
    jobs = {p: _StubSession(f"p{p}", p, instant=True) for p in (0, 2, 1)}
    for s in jobs.values():
        sched.submit(s)
    done = sched.drain()
    assert [s.spec.job_id for s in done] == ["p2", "p1", "p0"]


def test_scheduler_admission_control():
    sched = _mini_sched(max_queued=2)
    sched.submit(_StubSession("a", 0, instant=True))
    sched.submit(_StubSession("b", 0, instant=True))
    with pytest.raises(AdmissionError):
        sched.submit(_StubSession("c", 0, instant=True))


def test_scheduler_preempts_for_higher_priority():
    sched = _mini_sched(max_concurrent=1)
    low = _StubSession("low", 0)
    sched.submit(low)
    for _ in range(200):                 # let the low job occupy the slot
        sched.tick()
        if low.state == RUNNING:
            break
        time.sleep(0.002)
    assert low.state == RUNNING
    high = _StubSession("high", 5, instant=True)
    sched.submit(high)
    deadline = time.monotonic() + 5
    while high.state != DONE and time.monotonic() < deadline:
        sched.tick()
        time.sleep(0.002)
    assert high.state == DONE            # jumped the queue via preemption
    assert sched.preempt_requests == 1 and sched.requeues == 1
    assert low.preemptions == 1
    low.release.set()                    # let the requeued victim finish
    done = sched.drain()
    assert {s.spec.job_id for s in done} == {"low", "high"}
    assert low.state == DONE
    # every admit was released (namespace + share teardown balanced)
    a = sched.arbiter.stats_dict()
    assert a["admits"] == a["releases"] == 3 and not a["live_sessions"]


def test_equal_priority_never_preempts():
    sched = _mini_sched(max_concurrent=1)
    a = _StubSession("a", 1)
    sched.submit(a)
    for _ in range(200):
        sched.tick()
        if a.state == RUNNING:
            break
        time.sleep(0.002)
    sched.submit(_StubSession("b", 1, instant=True))
    for _ in range(20):
        sched.tick()
        time.sleep(0.002)
    assert sched.preempt_requests == 0 and a.state == RUNNING
    a.release.set()
    sched.drain()


# ===================================================== service (ram, fast)
@pytest.fixture(scope="module")
def ram_service_report():
    svc = build_service(backend="ram", device_budget=8 << 20,
                        max_concurrent=2)
    svc.submit(JobSpec("embed", kind="eigsh", n=300, nnz=3000, nev=3,
                       tol=1e-6, max_iters=60))
    svc.submit(JobSpec("pcg", kind="lobpcg", n=200, nnz=2000, nev=2,
                       tol=1e-4, max_iters=50, priority=1))
    svc.drain()
    rep = svc.report()
    svc.close()
    return rep


def test_service_report_valid_and_json(ram_service_report):
    rep = ram_service_report
    assert validate_report(rep) == []
    assert {j["job_id"] for j in rep["jobs"]} == {"embed", "pcg"}
    for j in rep["jobs"]:
        assert j["state"] == DONE and j["spectrum"]["sha"]
        assert j["wall_s"] > 0 and j["queue_wait_s"] >= 0
    assert rep["arbiter"]["admits"] == 2
    # the report is the machine-readable surface: must be JSON-clean
    json.dumps(rep, default=str)


def test_validate_report_catches_violations(ram_service_report):
    rep = json.loads(json.dumps(ram_service_report, default=str))
    rep["jobs"][0]["state"] = "failed"
    # the embed job's subspace fits its allotment and may never reach
    # the backend, which then has no entry for it
    embed = rep["backend"]["namespaces"].setdefault("embed", {})
    embed["host_bytes_written"] = embed.get("host_bytes_written", 0) + 7
    errs = validate_report(rep)
    assert any("lost" in e for e in errs)
    assert any("accounting leak" in e for e in errs)
    assert validate_report({"jobs": [], "scheduler": {}}) != []


def test_service_rejects_duplicate_job_id():
    svc = build_service(backend="ram", device_budget=4 << 20)
    svc.submit(JobSpec("a", n=100, nnz=600, nev=2, tol=1e-3, max_iters=10))
    with pytest.raises(ValueError, match="duplicate"):
        svc.submit(JobSpec("a"))
    svc.drain()
    svc.close()


# ============================================ satellite f: paged KV rides
def test_paged_kv_namespaced_coexistence():
    store = TieredStore(device_budget_bytes=4 << 20)
    solver_ns = store.namespace("solve")
    solver_ns.put("V/b0", np.zeros(256, np.float32))
    cfg = PagedConfig(page_size=8, n_kv_heads=2, head_dim=4, hot_pages=2)
    kv = PagedKVCache(cfg, store, session_id="kv")
    kv.start(0)
    for t in range(20):
        k = np.full((2, 4), t, np.float32)
        kv.append(0, k, k)
    assert kv.length(0) == 20
    # pages are namespaced on the SHARED store, solver blocks untouched
    assert all(n.startswith("kv/") for n in kv._tables[0])
    assert any(n.startswith("kv/") for n in store.namespace("kv").names())
    assert solver_ns.names() == ["V/b0"]
    out = kv.attend(0, np.ones((4, 4), np.float32))
    assert out.shape == (4, 4)
    kv.close()
    assert store.namespace("kv").names() == []
    assert solver_ns.names() == ["V/b0"]         # survivors intact


def test_paged_kv_bare_store_unchanged():
    cfg = PagedConfig(page_size=4, n_kv_heads=1, head_dim=4, hot_pages=1)
    kv = PagedKVCache(cfg)
    kv.start(7)
    kv.append(7, np.ones((1, 4), np.float32), np.ones((1, 4), np.float32))
    assert kv._tables[7] == ["kv/7/p0"]          # unprefixed, as before
    assert kv.session_id is None
    kv.close()                                    # no-op teardown


def test_paged_kv_rejects_unnamespaceable_store():
    class Bare:
        pass
    with pytest.raises(TypeError, match="namespace"):
        PagedKVCache(PagedConfig(), Bare(), session_id="kv")


# ====================================== E2E: multi-tenant solves over SAFS
def _serial_eigenvalues(spec):
    """The same JobSpec solved alone on a fresh private store — the parity
    baseline for the shared-store run."""
    s = SolveSession(spec, TieredStore(device_budget_bytes=64 << 20), None)
    assert s.run() == DONE, s.error
    return np.array(s.result["eigenvalues"])


@pytest.mark.disk
def test_multi_tenant_e2e_with_preemption(disk_tmp):
    import os
    specs = [
        JobSpec("bg-embed", kind="eigsh", n=800, nnz=8000, nev=4,
                priority=1, tol=1e-9, max_iters=200),
        JobSpec("bg-lobpcg", kind="lobpcg", n=400, nnz=4000, nev=3,
                priority=0, tol=1e-5, max_iters=60),
        JobSpec("bg-cluster", kind="cluster", n=900, k_classes=3, nev=3,
                priority=0, tol=1e-6),
    ]
    rush = JobSpec("rush", kind="eigsh", n=300, nnz=3000, nev=2,
                   priority=5, tol=1e-5, max_iters=60)
    svc = build_service(
        backend="safs", root=os.path.join(disk_tmp, "pages"),
        device_budget=8 << 20, cache_bytes=4 << 20,
        ckpt_root=os.path.join(disk_tmp, "ckpt"), max_concurrent=1,
        poll_interval=0.005)
    try:
        for spec in specs:
            svc.submit(spec)
        # wait until the long high-ish-priority job is mid-flight, then
        # drop the rush job on the queue → the scheduler must suspend it
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            svc.scheduler.tick()
            running = svc.scheduler.stats_dict()["running"]
            if any(p["steps"] >= 1 for p in running.values()):
                break
            time.sleep(0.01)
        svc.submit(rush)
        svc.drain()
        rep = svc.report()
    finally:
        svc.close()

    assert validate_report(rep) == []
    jobs = {j["job_id"]: j for j in rep["jobs"]}
    assert len(jobs) == 4 and all(j["state"] == DONE
                                  for j in jobs.values())
    assert sum(j["preemptions"] for j in jobs.values()) >= 1
    preempted = [j for j in jobs.values() if j["preemptions"]]
    assert all(j["resumes"] >= 1 for j in preempted)
    # the rush job barely waited; spectra match private serial runs
    assert jobs["rush"]["queue_wait_s"] < jobs["bg-lobpcg"]["queue_wait_s"]
    for spec in specs + [rush]:
        got = np.array(jobs[spec.job_id]["result"]["eigenvalues"])
        np.testing.assert_allclose(got, _serial_eigenvalues(spec),
                                   rtol=1e-5)
    assert jobs["bg-cluster"]["purity"] > 0.9
    # physical accounting: per-namespace sums == backend totals, exactly
    ns, io = rep["backend"]["namespaces"], rep["backend"]["io"]
    for field in ("host_bytes_read", "host_bytes_written"):
        assert sum(d[field] for d in ns.values()) == io[field]
