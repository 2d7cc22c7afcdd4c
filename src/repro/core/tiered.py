"""TieredStore — the SSD/host-offload tier with byte-exact I/O accounting.

The paper keeps the Krylov subspace on SSD (§3.4) and fights for two
resources: read bandwidth and *write endurance* (DWPD). On a TPU the slow
tier is host DRAM reached over PCIe (`memory_kind='pinned_host'`); in this
CPU container the tier split is emulated with a pluggable storage backend
(`repro.safs.backend`):

  backend="ram"   numpy buffers in host memory (the default; tier-1 tests);
  backend="safs"  the paper's real layer — one page file per data_id under
                  `backend_opts["root"]`, an LRU page cache with write-back
                  and most-recent-block pinning, and async prefetch
                  (`TieredStore.prefetch`) overlapping reads with compute.

Either way `stats` stays byte-exact *logical* tier traffic, so the paper's
Table-3 read/write claims are validated quantitatively by the benchmarks;
with safs the backend's own `stats` additionally count physical disk bytes
(endurance — less than logical whenever the page cache absorbs re-reads).
`stats.passes` additionally counts streamed whole-subspace reads
(`begin_pass`, driven by `core.stream.SubspacePass`) — the §3.4.3 unit the
pass-fusion work minimizes; `benchmarks/bench_subspace_io.py` archives
reads-per-expansion and reads-per-restart off these counters.

Residency: the device budget decides where device-tier entries live. An
entry put on the device tier stays there until the budget needs its bytes;
then the least-recently-used unpinned entries are evicted to the slow tier
(`store.evict` spans, `IOStats.evict_bytes`). `make_room` applies the same
eviction for transient device buffers the store does not hold, such as a
restart compression's accumulators. Without an explicit budget a store
takes half the device's memory (`default_device_budget`), so a subspace
stays in fast memory while it fits and spills once it does not. Only a
caller's explicit `put(..., tier=HOST)` or `demote` places an entry on the
slow tier otherwise: `core.lobpcg` writes its [X, W, P] blocks through on
purpose, for its byte-exact pass accounting.

Policies implemented from §3.4.4:
  * most-recent-block caching — the newest subspace block is pinned in the
    device tier (it is about to be re-read by reorthogonalization), and the
    newest *spilled* block of the subspace keeps its pages pinned in the
    page cache (`host_pin`, driven by MultiVector.append_block — an
    explicit lifecycle, so unrelated LRU demotions cannot steal the pin);
  * data identifiers — a transposed view shares its parent's identifier so
    cached bytes are recognized (we key the cache by `data_id`, not by
    object);
  * write-avoidance — demotion only writes when the block is dirty.

Multi-tenancy (serving layer, paper §3.4's shared page cache writ large —
FlashGraph runs many graph workloads over one SSD cache):
  * `namespace(session_id)` returns a `StoreNamespace` facade that prefixes
    every key with `"<sid>::"`, keeps per-namespace `IOStats`, and exposes
    the full store duck-API, so solvers run unmodified inside a session;
  * per-namespace device budgets (`set_namespace_budget`) let an arbiter
    split one global device budget across live sessions — a session
    overflowing its allotment demotes its *own* LRU entries first;
  * one host-pin slot *per namespace*: concurrent sessions cannot steal
    each other's §3.4.4 most-recent-block page pin;
  * `drop_namespace(sid)` retires a session — entries and backend pages
    are deleted, the namespace's IOStats survive for post-mortem reports;
  * every public method is serialized by one reentrant lock, and `IOStats`
    increments go through `IOStats.add` (its own lock), so two sessions
    hammering one store reconcile their counters exactly.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace

DEVICE = "device"
HOST = "host"  # the "SSD" tier

NS_SEP = "::"  # session prefix in qualified ids: "<session_id>::<name>"

# share of the device's memory a store's device tier may hold when the
# caller gives no budget; the rest is left to the operator's image and
# its temporaries
DEFAULT_BUDGET_SHARE = 0.5


def default_device_budget() -> int:
    """DEFAULT_BUDGET_SHARE of the first local device's memory
    (`memory_stats()["bytes_limit"]`). A device that reports no limit,
    as the CPU does, holds its arrays in host memory, so the host's
    physical memory counts instead."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit") or (os.sysconf("SC_PHYS_PAGES")
                                         * os.sysconf("SC_PAGE_SIZE"))
    return int(limit * DEFAULT_BUDGET_SHARE)


def ns_of(data_id: str) -> str:
    """Namespace (session id) of a qualified id; "" for root-owned ids."""
    i = data_id.find(NS_SEP)
    return data_id[:i] if i >= 0 else ""


class ReadOnlyError(RuntimeError):
    """Write attempted against a read-only store entry (streamed matrix
    image chunks: per-chunk dirty tracking is not implemented, so a write
    would silently diverge from the on-disk image)."""


@dataclasses.dataclass
class IOStats:
    host_bytes_read: int = 0       # "SSD" reads (paper Table 3: 145 TB)
    host_bytes_written: int = 0    # "SSD" writes (paper Table 3: 4 TB)
    host_reads: int = 0
    host_writes: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    passes: int = 0                # streamed whole-subspace reads (§3.4.3)
    pass_bytes_read: int = 0       # host bytes read INSIDE those passes
    retries: int = 0               # transient-I/O retries absorbed (safs)
    retry_sleep_ms: float = 0.0    # cumulative backoff slept in retries
    #                                (bounded per op by max_total_sleep)
    evict_bytes: int = 0           # bytes the device budget moved to the
    #                                slow tier (LRU evictions)

    def __post_init__(self):
        # not a dataclass field: asdict/eq stay counter-only, and every
        # instance gets its own lock even through dataclasses.replace
        self._lock = threading.Lock()

    def add(self, **deltas: int) -> None:
        """Atomically bump counters. One instance is shared between the
        page cache, the write-behind retire thread and the backend's
        caller threads (three different outer locks) — unsynchronized
        `+=` there loses updates under load."""
        with self._lock:
            for k, v in deltas.items():
                setattr(self, k, getattr(self, k) + v)

    def bytes_per_pass(self) -> float:
        """Average slow-tier bytes read per streamed subspace pass — the
        §3.4.3 figure of merit (fusion shrinks `passes` while the bytes
        of the surviving passes stay put). Attributed: only bytes read
        inside SubspacePass runs count — operator tile / streamed-image
        reads sharing the store do not dilute the figure."""
        return self.pass_bytes_read / max(self.passes, 1)

    def hit_rate(self) -> float:
        """Fraction of lookups served without a slow-tier read. Every
        stats surface (logical tier, page cache, merged backend snapshot)
        reports this identically via `as_dict`."""
        return self.cache_hits / max(self.cache_hits + self.cache_misses, 1)

    def as_dict(self) -> Dict[str, float]:
        # Dict[str, float]: the raw fields are ints, but the derived
        # bytes_per_pass / hit_rate gauges are ratios
        d = dataclasses.asdict(self)
        d["bytes_per_pass"] = self.bytes_per_pass()
        d["hit_rate"] = self.hit_rate()
        return d


@dataclasses.dataclass
class _Entry:
    data_id: str
    tier: str
    device_val: Optional[jnp.ndarray]
    has_host: bool                 # backend holds a copy of data_id
    nbytes: int
    dirty: bool                    # device copy newer than host copy
    readonly: bool = False         # writes raise (streamed matrix image)
    ns: str = ""                   # owning session ("" = root)


class TieredStore:
    """Named tensor store with a device-tier budget and explicit residency.

    device_budget_bytes caps the *device* tier; putting past the budget
    demotes the least-recently-used non-pinned entries to the host tier
    (counted as SSD writes if dirty). None (the default) takes half the
    device's memory, `default_device_budget()`. `pin` marks the
    most-recent subspace block per §3.4.4. The host tier's bytes live in
    `backend` ("ram" | "safs" | a StorageBackend instance; see module
    docstring).
    """

    def __init__(self, device_budget_bytes: Optional[int] = None, *,
                 backend="ram", backend_opts: dict | None = None):
        from repro.safs.backend import make_backend  # late: avoids cycle
        self.device_budget = int(default_device_budget()
                                 if device_budget_bytes is None
                                 else device_budget_bytes)
        self.stats = IOStats()
        self.backend = make_backend(backend, **(backend_opts or {}))
        self._entries: Dict[str, _Entry] = {}
        self._lru: "OrderedDict[str, None]" = OrderedDict()  # oldest first
        self._pinned: set[str] = set()
        # page-cache pin (§3.4.4) — one slot PER NAMESPACE, so concurrent
        # sessions cannot steal each other's most-recent-block pin
        self._recent_host_ids: Dict[str, str] = {}
        self._device_nbytes = 0     # running counter — no per-op full scans
        self._lock = threading.RLock()          # serializes all public ops
        self._ns_stats: Dict[str, IOStats] = {}
        self._ns_budget: Dict[str, int] = {}    # per-session device caps
        self._ns_device: Dict[str, int] = {}    # device bytes per session
        self._namespaces: Dict[str, "StoreNamespace"] = {}

    # -- multi-tenancy ---------------------------------------------------------
    def namespace(self, session_id: str) -> "StoreNamespace":
        """Session-scoped facade: keys prefixed `"<sid>::"`, IOStats split
        per session, optional per-session device budget. Re-entering the
        same id (e.g. a preempted job resuming) returns a facade over the
        same accumulated stats."""
        if not session_id or NS_SEP in session_id:
            raise ValueError(f"invalid session id {session_id!r}")
        with self._lock:
            ns = self._namespaces.get(session_id)
            if ns is None:
                ns = StoreNamespace(self, session_id)
                self._namespaces[session_id] = ns
            return ns

    def set_namespace_budget(self, session_id: str,
                             nbytes: Optional[int]) -> None:
        """Cap a session's device-tier bytes (None lifts the cap). The
        arbiter recomputes these on admit/finish; shrinking a live
        session's allotment demotes its own LRU entries immediately."""
        with self._lock:
            if nbytes is None:
                self._ns_budget.pop(session_id, None)
                return
            self._ns_budget[session_id] = int(nbytes)
            self._evict_for(0, session_id)

    def namespace_budget(self, session_id: str) -> Optional[int]:
        with self._lock:
            return self._ns_budget.get(session_id)

    def drop_namespace(self, session_id: str) -> None:
        """Retire a session: delete its entries and backend pages, release
        its pins and budget. Its IOStats survive (post-mortem reporting —
        the serve report reconciles them against backend totals)."""
        with self._lock:
            for name in [n for n, e in self._entries.items()
                         if e.ns == session_id]:
                self.delete(name)
            rid = self._recent_host_ids.pop(session_id, None)
            if rid is not None:
                self.backend.unpin(rid)
            self._ns_budget.pop(session_id, None)
            self._ns_device.pop(session_id, None)
            self._namespaces.pop(session_id, None)
            drop = getattr(self.backend, "drop_namespace", None)
            if drop is not None:
                drop(session_id)

    def namespace_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-session logical IOStats snapshots (includes retired
        sessions — stats outlive `drop_namespace`)."""
        with self._lock:
            return {sid: st.as_dict() for sid, st in self._ns_stats.items()}

    def _ns_io(self, sid: str) -> IOStats:
        st = self._ns_stats.get(sid)
        if st is None:
            st = self._ns_stats.setdefault(sid, IOStats())
        return st

    def _acct(self, ns: str, **deltas: int) -> None:
        """Bump the store-wide counters, and the owning session's split.
        Parent totals therefore equal root traffic plus the namespace
        sums exactly — the reconciliation the serve report asserts."""
        self.stats.add(**deltas)
        if ns:
            self._ns_io(ns).add(**deltas)

    # -- residency accounting -------------------------------------------------
    def device_bytes(self) -> int:
        return self._device_nbytes

    def host_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values()
                       if e.has_host)

    def _touch(self, name: str) -> None:
        if name in self._lru:
            self._lru.move_to_end(name)
        else:
            self._lru[name] = None

    def _evict(self, name: str) -> None:
        e = self._entries[name]
        with trace.span("store.evict", block=name, bytes=e.nbytes):
            self.demote(name)
        self._acct(e.ns, evict_bytes=e.nbytes)

    def _evict_for(self, incoming: int, ns: str = "") -> None:
        # a capped session overflowing its allotment demotes its OWN
        # least-recently-used entries first — it cannot push another
        # session's working set off the device tier
        budget = self._ns_budget.get(ns)
        if budget is not None:
            while self._ns_device.get(ns, 0) + incoming > budget:
                victim = next(
                    (n for n in self._lru
                     if self._entries[n].tier == DEVICE
                     and self._entries[n].ns == ns
                     and n not in self._pinned), None)
                if victim is None:
                    break
                self._evict(victim)
        if self._device_nbytes + incoming <= self.device_budget:
            return
        for name in list(self._lru):                # oldest first
            if self._device_nbytes + incoming <= self.device_budget:
                break
            e = self._entries[name]
            if e.tier == DEVICE and name not in self._pinned:
                self._evict(name)

    def _drop_entry(self, name: str, e: "_Entry") -> None:
        # an entry leaving the table (delete / overwrite) releases its
        # device residency from the running counter
        if e.tier == DEVICE:
            self._device_nbytes -= e.nbytes
            if e.ns:
                self._ns_device[e.ns] = (
                    self._ns_device.get(e.ns, 0) - e.nbytes)

    def _add_device(self, e: "_Entry") -> None:
        self._device_nbytes += e.nbytes
        if e.ns:
            self._ns_device[e.ns] = self._ns_device.get(e.ns, 0) + e.nbytes

    # -- core API --------------------------------------------------------------
    def put(self, name: str, value: jnp.ndarray, *, tier: str = DEVICE,
            data_id: str | None = None, readonly: bool = False) -> None:
        with self._lock:
            ns = ns_of(name)
            prev = self._entries.get(name)
            if prev is not None and prev.readonly:
                raise ReadOnlyError(
                    f"store entry {name!r} is read-only (streamed matrix "
                    f"image chunk; per-chunk dirty tracking is not "
                    f"implemented — rebuild the operator instead of "
                    f"writing through it)")
            nbytes = int(np.prod(value.shape)) * value.dtype.itemsize
            if prev is not None:
                # retire the stale entry wholly before eviction runs, so
                # _evict_for can neither demote the about-to-be-replaced
                # bytes nor double-release them from the running counter
                self._drop_entry(name, prev)
                del self._entries[name]
                self._lru.pop(name, None)
            if tier == DEVICE:
                self._evict_for(nbytes, ns)
                e = _Entry(data_id or name, DEVICE, jnp.asarray(value),
                           False, nbytes, True, readonly, ns)
                self._entries[name] = e
                self._add_device(e)
            else:
                e = _Entry(data_id or name, HOST, None, True, nbytes,
                           False, readonly, ns)
                with trace.span("store.put", block=name, bytes=nbytes):
                    self.backend.store(e.data_id, np.asarray(value))
                self._acct(ns, host_bytes_written=nbytes, host_writes=1)
                self._entries[name] = e
            self._touch(name)

    def get(self, name: str) -> jnp.ndarray:
        """Read a tensor; host-tier reads are counted as SSD reads."""
        with self._lock:
            e = self._entries[name]
            self._touch(name)
            if e.tier == DEVICE:
                self._acct(e.ns, cache_hits=1)
                return e.device_val
            self._acct(e.ns, cache_misses=1, host_bytes_read=e.nbytes,
                       host_reads=1)
            # span on the slow-tier branch only: device hits are free and
            # would dominate the trace with noise
            with trace.span("store.get", block=name, bytes=e.nbytes):
                return jnp.asarray(self.backend.load(e.data_id))

    def promote(self, name: str) -> jnp.ndarray:
        """Move to device tier (counted read if it was on host)."""
        with self._lock:
            e = self._entries[name]
            if e.tier == DEVICE:
                return e.device_val
            val = self.get(name)
            self._evict_for(e.nbytes, e.ns)
            e.device_val, e.tier, e.dirty = val, DEVICE, False
            self._add_device(e)
            return val

    def demote(self, name: str) -> None:
        """Move to host tier; writes only if dirty (write-avoidance)."""
        with self._lock:
            e = self._entries[name]
            if e.tier == HOST:
                return
            if e.dirty or not e.has_host:
                with trace.span("store.demote", block=name, bytes=e.nbytes):
                    self.backend.store(e.data_id, np.asarray(e.device_val))
                e.has_host = True
                self._acct(e.ns, host_bytes_written=e.nbytes, host_writes=1)
            e.device_val, e.tier, e.dirty = None, HOST, False
            self._device_nbytes -= e.nbytes
            if e.ns:
                self._ns_device[e.ns] = (
                    self._ns_device.get(e.ns, 0) - e.nbytes)

    def host_pin(self, name: str) -> None:
        """Pin `name`'s pages in the backend page cache until the next
        host_pin *from the same namespace* supersedes it — the §3.4.4
        "cache the most recent dense matrix" policy, one slot per session
        so concurrent solves keep their own pins. The pin is owned by the
        subspace append lifecycle (MultiVector.append_block pins the block
        it just demoted): plain LRU demotions must NOT move it, or
        restart-compression's output spills steal the pin from the block
        reorthogonalization is about to re-read (the page cache then never
        hits on the solver path)."""
        with self._lock:
            e = self._entries[name]
            cur = self._recent_host_ids.get(e.ns)
            if cur == e.data_id:
                return
            with trace.span("store.host_pin", block=name):
                if cur is not None:
                    self.backend.unpin(cur)
                self.backend.pin(e.data_id)
            self._recent_host_ids[e.ns] = e.data_id

    def pin(self, name: str) -> None:
        """Pin in device tier — the most-recent-block cache of §3.4.4."""
        with self._lock:
            self.promote(name)
            self._pinned.add(name)

    def unpin(self, name: str) -> None:
        with self._lock:
            self._pinned.discard(name)

    def delete(self, name: str) -> None:
        with self._lock:
            e = self._entries.pop(name, None)
            if e is not None:
                self._drop_entry(name, e)
            self._lru.pop(name, None)
            self._pinned.discard(name)
            if e is not None and not any(o.data_id == e.data_id
                                         for o in self._entries.values()):
                with trace.span("store.delete", block=name):
                    self.backend.delete(e.data_id)
                    if self._recent_host_ids.get(e.ns) == e.data_id:
                        self.backend.unpin(e.data_id)
                        del self._recent_host_ids[e.ns]

    def names(self):
        with self._lock:
            return list(self._entries)

    def tier_of(self, name: str) -> str:
        with self._lock:
            return self._entries[name].tier

    # -- checkpoint plumbing ----------------------------------------------------
    def sync_device_entries(self, ns: Optional[str] = None) -> None:
        """Write device-tier entries with no current host copy through to
        the backend (residency unchanged — the entry just becomes clean-
        with-host-copy, like after a promote). `ckpt.save_safs` calls this
        before snapshotting page files so the §3.4.4-pinned newest block
        is not silently missing from the snapshot."""
        with self._lock:
            for e in self._entries.values():
                if ns is not None and e.ns != ns:
                    continue
                if e.tier == DEVICE and (e.dirty or not e.has_host):
                    self.backend.store(e.data_id, np.asarray(e.device_val))
                    e.has_host, e.dirty = True, False

    def data_ids(self, ns: Optional[str] = None) -> list[str]:
        """Backend ids owned by this store (optionally one namespace) —
        the set `ckpt.save_safs` snapshots. On a shared backend this is
        deliberately NOT `backend.data_ids()`: a session's checkpoint must
        not capture other sessions' page files."""
        with self._lock:
            out, seen = [], set()
            for e in self._entries.values():
                if ns is not None and e.ns != ns:
                    continue
                if e.has_host and e.data_id not in seen:
                    seen.add(e.data_id)
                    out.append(e.data_id)
            return out

    def resolve_data_id(self, name: str) -> str:
        """Qualified backend id for a logical name (identity at root; the
        namespace facade prefixes). Checkpoint restore uses this to find a
        block's page file inside a snapshot."""
        return name

    # -- budget hooks -----------------------------------------------------------
    def make_room(self, nbytes: int, ns: str = "") -> None:
        """Evict least-recently-used unpinned entries until `nbytes` of
        device buffers the store does not hold (a restart compression's
        accumulators, `MultiVector.compress`) fit beside the resident
        entries under the device budget. Pinned entries stay, so the
        budget can be exceeded by them alone."""
        with self._lock:
            self._evict_for(int(nbytes), ns)

    def compress_acc_bytes(self) -> Optional[int]:
        """Per-store override for the fused-compress transient-accumulator
        cap (`core.multivector.COMPRESS_PASS_ACC_BYTES`). None = keep the
        global default; namespaces under an arbiter allotment return a
        scaled cap so a small-budget session chunks its compress pass."""
        return None

    def account_read(self, nbytes: int, *, reads: int = 1) -> None:
        """Attribute an out-of-band slow-tier read (e.g. the operator's
        non-streamed matrix image) to this store's counters. Namespaced
        facades route it to their session split too — direct `stats.x +=`
        from callers would silently skip the parent/session dual books."""
        self._acct("", host_bytes_read=int(nbytes), host_reads=reads)

    # -- streaming helpers ------------------------------------------------------
    def begin_pass(self) -> int:
        """Mark the start of one streamed whole-subspace read (called by
        `core.stream.SubspacePass.run`). `stats.passes` then counts the
        §3.4.3 unit of cost — full passes over the on-SSD subspace.
        Returns the host_bytes_read watermark; hand it back to `end_pass`
        so `pass_bytes_read` attributes exactly the bytes the pass itself
        streamed (matrix-image reads sharing the store stay excluded)."""
        self.stats.add(passes=1)
        return self.stats.host_bytes_read

    def end_pass(self, read_watermark: int) -> None:
        """Close the pass opened by `begin_pass`, attributing the bytes
        read since the watermark to `stats.pass_bytes_read`."""
        self.stats.add(pass_bytes_read=(self.stats.host_bytes_read
                                        - read_watermark))

    def prefetch(self, names: Iterable[str]) -> None:
        """Hint the backend to stage host-tier entries' pages ahead of the
        next grouped pass (async; a no-op on the ram backend)."""
        with self._lock:
            ids = [self._entries[n].data_id for n in names
                   if n in self._entries and self._entries[n].tier == HOST]
        if ids:
            with trace.span("store.prefetch", n=len(ids), first=ids[0]):
                self.backend.prefetch(ids)

    def stream(self, names: Iterable[str], *, readahead: int = 2):
        """Yield `get(name)` for each name while keeping the next
        `readahead` entries' pages in flight on the backend's readahead
        pool — the generic sequential-scan driver (SSD-streamed SpMM
        walks the matrix-image chunks with it; grouped MultiVector passes
        use the same pattern via `prefetch`). On the ram backend it
        degenerates to a plain `get` loop."""
        names = list(names)
        for i, nm in enumerate(names):
            if readahead > 0:
                self.prefetch(names[i + 1:i + 1 + readahead])
            yield self.get(nm)

    def flush(self) -> None:
        """Force dirty host-tier pages down to the physical medium."""
        self.backend.flush()

    def close(self) -> None:
        with trace.span("store.close"):
            self.backend.close()

    def reset_stats(self) -> IOStats:
        old, self.stats = self.stats, IOStats()
        return old


class StoreNamespace:
    """Session-scoped view of a shared `TieredStore`.

    Mirrors the full store duck-API (put/get/promote/demote/pin/host_pin/
    begin_pass/stream/...), prefixing every key with `"<sid>::"` and
    splitting IOStats per session, so `MultiVector`, `SubspacePass`,
    `GraphOperator` and every solver run unmodified inside a session.
    `close()` retires the whole namespace (entries + backend pages); the
    session's stats survive on the parent for post-mortem reporting.

    Pass accounting is namespace-local: `begin_pass` watermarks the
    *session's* host_bytes_read and `end_pass` attributes the delta to
    both the session and the parent — under concurrency a parent-level
    watermark would blame one session's pass for another's bytes.
    """

    def __init__(self, parent: TieredStore, session_id: str):
        self._parent = parent
        self.session_id = session_id
        self._prefix = session_id + NS_SEP
        with parent._lock:
            self._stats = parent._ns_io(session_id)

    # -- naming ----------------------------------------------------------------
    def _q(self, name: str) -> str:
        return self._prefix + name

    def resolve_data_id(self, name: str) -> str:
        return self._q(name)

    # -- shared-resource views ---------------------------------------------------
    @property
    def stats(self) -> IOStats:
        return self._stats

    @property
    def backend(self):
        return self._parent.backend

    @property
    def parent(self) -> TieredStore:
        return self._parent

    @property
    def device_budget(self) -> int:
        b = self._parent._ns_budget.get(self.session_id)
        return self._parent.device_budget if b is None else b

    # -- core API ----------------------------------------------------------------
    def put(self, name, value, *, tier=DEVICE, data_id=None,
            readonly=False) -> None:
        self._parent.put(self._q(name), value, tier=tier,
                         data_id=self._q(data_id) if data_id else None,
                         readonly=readonly)

    def get(self, name):
        return self._parent.get(self._q(name))

    def promote(self, name):
        return self._parent.promote(self._q(name))

    def demote(self, name) -> None:
        self._parent.demote(self._q(name))

    def host_pin(self, name) -> None:
        self._parent.host_pin(self._q(name))

    def pin(self, name) -> None:
        self._parent.pin(self._q(name))

    def unpin(self, name) -> None:
        self._parent.unpin(self._q(name))

    def delete(self, name) -> None:
        self._parent.delete(self._q(name))

    def names(self):
        with self._parent._lock:
            return [n[len(self._prefix):] for n, e in
                    self._parent._entries.items()
                    if e.ns == self.session_id]

    def tier_of(self, name) -> str:
        return self._parent.tier_of(self._q(name))

    def device_bytes(self) -> int:
        with self._parent._lock:
            return self._parent._ns_device.get(self.session_id, 0)

    def host_bytes(self) -> int:
        with self._parent._lock:
            return sum(e.nbytes for e in self._parent._entries.values()
                       if e.ns == self.session_id and e.has_host)

    # -- checkpoint plumbing ------------------------------------------------------
    def sync_device_entries(self) -> None:
        self._parent.sync_device_entries(ns=self.session_id)

    def data_ids(self) -> list[str]:
        return self._parent.data_ids(ns=self.session_id)

    # -- budget hooks --------------------------------------------------------------
    def make_room(self, nbytes: int) -> None:
        self._parent.make_room(nbytes, self.session_id)

    def compress_acc_bytes(self) -> Optional[int]:
        """Fused-compress transient cap scaled to this session's arbiter
        allotment (half the device allotment, floored at 1 MiB), so a
        small-budget session chunks its compress pass instead of blowing
        past its share. None (no cap set) keeps the global default."""
        budget = self._parent._ns_budget.get(self.session_id)
        if budget is None:
            return None
        return max(budget // 2, 1 << 20)

    def account_read(self, nbytes: int, *, reads: int = 1) -> None:
        self._parent._acct(self.session_id, host_bytes_read=int(nbytes),
                           host_reads=reads)

    # -- streaming helpers ---------------------------------------------------------
    def begin_pass(self) -> int:
        with self._parent._lock:
            self._stats.add(passes=1)
            self._parent.stats.add(passes=1)
            return self._stats.host_bytes_read

    def end_pass(self, read_watermark: int) -> None:
        delta = self._stats.host_bytes_read - read_watermark
        self._stats.add(pass_bytes_read=delta)
        self._parent.stats.add(pass_bytes_read=delta)

    def prefetch(self, names: Iterable[str]) -> None:
        self._parent.prefetch([self._q(n) for n in names])

    def stream(self, names: Iterable[str], *, readahead: int = 2):
        names = list(names)
        for i, nm in enumerate(names):
            if readahead > 0:
                self.prefetch(names[i + 1:i + 1 + readahead])
            yield self.get(nm)

    def flush(self) -> None:
        self._parent.flush()

    def close(self) -> None:
        """Session end: drop the namespace (entries + backend pages). The
        shared backend stays open — the parent owns its lifecycle."""
        self._parent.drop_namespace(self.session_id)

    def reset_stats(self) -> IOStats:
        with self._parent._lock:
            old = self._stats
            self._stats = IOStats()
            self._parent._ns_stats[self.session_id] = self._stats
            return old
