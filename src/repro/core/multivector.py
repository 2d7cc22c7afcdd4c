"""Out-of-core TAS MultiVector — the paper's §3.4 vector subspace.

The Krylov subspace S ∈ R^{n×m} is stored as NB column blocks of width b
(one "TAS matrix" per block, each a separate object in the TieredStore — the
analogue of one SAFS file per matrix, §3.4.1). The eleven Anasazi MultiVector
operations of Table 1 are implemented block-streamed.

I/O discipline (§3.4.3 pass minimization): every whole-subspace operation is
expressed as a `core.stream.SubspacePass` — ONE block-streamed read feeding
any number of consumers per block visit, with the full pass's block list
announced to `TieredStore.prefetch` up front so the backend's readahead
window always has the true access pattern (this replaced the old per-group
`_prefetch_group` hints; the small reductions mv_dot / mv_norm / clone_view
previously streamed with no readahead at all). Pass-level rules:

  * one `TieredStore.get` per block per pass, shared by all consumers —
    `IOStats.passes` counts the streamed reads, so bytes-per-pass is
    byte-exact and benchmarkable (`benchmarks/bench_subspace_io.py`);
  * MvScale is *lazy* — a scalar per block folded into the shared
    materialization (the paper's lazy evaluation, §3.4.4), zero I/O;
  * `project_out` fuses a whole CGS step (h = Vᵀw, w ← w − V h) into one
    read — `ortho.bcgs2(fused=True)` runs CGS2 in 2 subspace reads where
    the unfused path pays 4;
  * `compress` computes ALL restart output blocks in one streamed read
    (multi-accumulator TSGEMM) instead of one full pass per output block;
  * blocks stay in the device tier up to the store's device budget and
    are evicted LRU past it; the newest block is pinned there
    (most-recent-block cache) and the newest spilled block's pages stay
    pinned in the backend page cache (§3.4.4);
  * transpose/CloneView share `data_id` with their parent so the cache
    recognizes identical bytes.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stream import SubspacePass
from repro.core.tiered import TieredStore, DEVICE, HOST
from repro.kernels import ops as kops


@dataclasses.dataclass
class _Block:
    name: str
    ncols: int
    scale: float = 1.0   # lazy MvScale factor


# Transient device-accumulator budget for one fused compress pass: every
# output block of the pass stays resident (k·n·4 bytes for k columns), so
# an unbounded single pass would OOM a billion-row restart. Under this cap
# any laptop/bench-scale compress is still exactly one pass; past it the
# output column groups chunk into ceil(k_keep·n·4 / cap) passes — still
# far below the pre-fusion one-pass-per-output-block.
COMPRESS_PASS_ACC_BYTES = 1 << 30


class MultiVector:
    """A tall-and-skinny (n × m) matrix as a sequence of column blocks."""

    _counter = 0
    _counter_lock = threading.Lock()   # concurrent sessions auto-name MVs

    def __init__(self, store: TieredStore | None, n: int, *,
                 name: str | None = None, group_size: int = 8,
                 readahead: int = 2, impl: kops.Impl = "auto",
                 backend="ram", backend_opts: dict | None = None):
        if name is None:
            with MultiVector._counter_lock:
                MultiVector._counter += 1
                name = f"mv{MultiVector._counter}"
        else:
            # A resumed solve recreates MultiVectors under their
            # checkpointed auto-names; keep the counter ahead of them so
            # later auto-named instances can't collide in a shared store.
            m = re.fullmatch(r"mv(\d+)", name)
            if m:
                with MultiVector._counter_lock:
                    MultiVector._counter = max(MultiVector._counter,
                                               int(m.group(1)))
        if store is None:  # own store on the requested backend ("ram"|"safs")
            store = TieredStore(backend=backend, backend_opts=backend_opts)
        self.store = store
        self.n = n
        self.name = name
        self.group_size = group_size
        self.readahead = max(1, int(readahead))  # groups announced ahead
        self.impl = impl
        self._blocks: List[_Block] = []

    # ------------------------------------------------------------------ basics
    @property
    def ncols(self) -> int:
        return sum(b.ncols for b in self._blocks)

    @property
    def nblocks(self) -> int:
        return len(self._blocks)

    def block_widths(self) -> List[int]:
        return [b.ncols for b in self._blocks]

    def block_names(self) -> List[str]:
        """Store names of the blocks, in column order (stable identity —
        operators mirroring the subspace on-device key their shard cache
        on these)."""
        return [b.name for b in self._blocks]

    def _block_name(self, i: int) -> str:
        return self._blocks[i].name

    def block(self, i: int) -> jnp.ndarray:
        """Materialize block i (applies any lazy scale)."""
        b = self._blocks[i]
        val = self.store.get(b.name)
        if b.scale != 1.0:
            val = b.scale * val
        return val

    def append_block(self, arr: jnp.ndarray, *, pin_recent: bool = True) -> None:
        """Append a new rightmost block on the device tier. The store's
        device budget alone decides which older blocks stay there: past
        it, the store evicts least-recently-used unpinned entries.

        With pin_recent the new block is pinned (most-recent-block cache,
        §3.4.4) and the previous one unpinned, and the pages of this
        subspace's newest spilled block — the one most recently evicted,
        which every CGS2 pass re-reads from the slow tier — are pinned in
        the backend page cache until a later append moves the pin."""
        assert arr.shape[0] == self.n, (arr.shape, self.n)
        idx = len(self._blocks)
        name = f"{self.name}/b{idx}"
        if pin_recent and idx > 0:
            self.store.unpin(self._blocks[-1].name)
        self.store.put(name, jnp.asarray(arr, jnp.float32))
        if pin_recent:
            self.store.pin(name)
            spilled = [b.name for b in self._blocks
                       if self.store.tier_of(b.name) == HOST]
            if spilled:
                self.store.host_pin(spilled[-1])
        self._blocks.append(_Block(name, int(arr.shape[1])))

    def set_block(self, i: int, arr: jnp.ndarray) -> None:
        """Anasazi SetBlock: overwrite one block in place."""
        b = self._blocks[i]
        assert arr.shape == (self.n, b.ncols)
        self.store.put(b.name, jnp.asarray(arr, jnp.float32))
        b.scale = 1.0

    def delete(self) -> None:
        for b in self._blocks:
            self.store.delete(b.name)
        self._blocks.clear()

    # --------------------------------------------------------------- Table 1
    def mv_random(self, key: jax.Array, widths: Sequence[int]) -> None:
        """MvRandom: (re)initialize blocks with random values."""
        self.delete()
        for w in widths:
            key, sub = jax.random.split(key)
            self.append_block(jax.random.normal(sub, (self.n, w), jnp.float32))

    def mv_scale(self, factors: Sequence[float] | float) -> None:
        """MvScale1 — lazy: fold the scalar into block metadata (zero I/O)."""
        if np.isscalar(factors):
            for b in self._blocks:
                b.scale *= float(factors)
        else:
            assert len(factors) == self.nblocks
            for b, f in zip(self._blocks, factors):
                b.scale *= float(f)

    def mv_scale_diag(self, vec: jnp.ndarray) -> None:
        """MvScale2: BB <- AA diag(vec) — materializes (per-column scales).
        One streamed pass (full block list announced to the readahead
        window up front); each visit writes its scaled block back in
        place. Previously this was a bare get/put loop with no prefetch
        announcement at all."""
        if self.nblocks == 0:
            return
        offs, off = [], 0
        for b in self._blocks:
            offs.append(off)
            off += b.ncols

        p = SubspacePass(self)

        def scale(i, blk, peers):
            w = self._blocks[i].ncols
            self.set_block(i, blk * vec[offs[i]:offs[i] + w][None, :])

        p.add_visit(scale, axis=None)
        p.run()

    def mv_times_mat(self, small: jnp.ndarray, *, alpha: float = 1.0,
                     beta: float = 0.0, c0: jnp.ndarray | None = None
                     ) -> jnp.ndarray:
        """MvTimesMatAddMv: returns alpha * self @ small + beta * c0, where
        small is (m, k). One streamed pass over the blocks."""
        m, k = small.shape
        assert m == self.ncols, (m, self.ncols)
        if self.nblocks == 0:
            acc = jnp.zeros((self.n, k), jnp.float32)
        else:
            p = SubspacePass(self)
            h = p.add_matmul(small, alpha=alpha)
            p.run()
            (acc,) = h.value
        if c0 is not None and beta != 0.0:
            acc = acc + beta * c0
        return acc

    def mv_trans_mv(self, other: jnp.ndarray, *, alpha: float = 1.0
                    ) -> jnp.ndarray:
        """MvTransMv: alpha * selfᵀ @ other → (m, k) small matrix.
        One streamed pass; the right operand is shared across visits
        (§3.4.3 shared-I/O optimization — it stays in the device tier)."""
        p = SubspacePass(self)
        h = p.add_gram(other, alpha=alpha)
        p.run()
        return h.value

    def project_out(self, w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """One *fused* CGS step in a single streamed read: per block visit
        h_i = V_iᵀw then w ← w − V_i h_i (block-MGS update order; the
        telescoping w₀ = Σ V_i h_i + w keeps W = V·h + w exact). Returns
        (h, w). The unfused equivalent (mv_trans_mv + mv_times_mat) reads
        the subspace twice."""
        p = SubspacePass(self)
        h = p.add_project(w)
        p.run()
        return h.value

    def mv_add_mv(self, alpha: float, other: "MultiVector", beta: float
                  ) -> "MultiVector":
        """MvAddMv: C <- alpha*A + beta*B (blockwise, same block structure),
        both operands streamed in lockstep with readahead."""
        assert self.block_widths() == other.block_widths()
        out = MultiVector(self.store, self.n, group_size=self.group_size,
                          readahead=self.readahead, impl=self.impl)
        p = SubspacePass(self, peers=[other])

        def emit(i, blk, peers):
            out.append_block(alpha * blk + beta * peers[0], pin_recent=False)

        p.add_visit(emit, axis=None)
        p.run()
        return out

    def mv_dot(self, other: "MultiVector") -> jnp.ndarray:
        """MvDot: columnwise dot products vec[i] = selfᵀ[:,i] · other[:,i]."""
        assert self.block_widths() == other.block_widths()
        p = SubspacePass(self, peers=[other])
        h = p.add_dot()
        p.run()
        return h.value

    def mv_norm(self) -> jnp.ndarray:
        """MvNorm: column 2-norms."""
        p = SubspacePass(self)
        h = p.add_norm()
        p.run()
        return h.value

    def clone_view(self, idxs: Sequence[int]) -> jnp.ndarray:
        """CloneView: gather a set of columns (materialized, one pass)."""
        want = set(int(i) for i in idxs)
        offs, off = [], 0
        for b in self._blocks:
            offs.append(off)
            off += b.ncols
        p = SubspacePass(self)

        def pick(i, blk, peers):
            local = [j for j in range(blk.shape[1]) if offs[i] + j in want]
            return blk[:, local] if local else None

        h = p.add_visit(pick, axis=1)
        p.run()
        return h.value

    def conv_layout(self) -> jnp.ndarray:
        """ConvLayout: column-major subspace block → row-major operand for
        SpMM. On TPU this is a logical no-op (XLA layouts); kept for API
        fidelity. Returns the most recent block materialized."""
        return self.block(self.nblocks - 1)

    # ------------------------------------------------------------ restart ops
    def compress(self, q: jnp.ndarray, new_widths: Sequence[int], *,
                 fused: bool = True, pass_acc_bytes: int | None = None
                 ) -> "MultiVector":
        """V_new = V @ Q for restart compression (Krylov–Schur). Q is
        (m, m_new); output blocks of widths new_widths. This is the big
        out-of-core GEMM of the restart step.

        fused=True (default): ONE streamed read computes every output
        block via multi-accumulator TSGEMM — the subspace is read exactly
        once regardless of k_keep. The pass's output accumulators stay
        device-resident (k·n·4 bytes of fast memory, the paper's TAS
        working-set assumption); when k_keep·n·4 exceeds `pass_acc_bytes`
        (default COMPRESS_PASS_ACC_BYTES, 1 GiB) the output column groups
        chunk into the minimum number of passes that fit the budget.
        Before each pass allocates its accumulators the store evicts
        least-recently-used blocks until they fit beside the resident
        ones under its device budget (`TieredStore.make_room`); that
        moves blocks, never the column groups, so the programs and the
        result are the same at every budget.
        fused=False keeps the pre-fusion path — one full grouped pass
        *per output block* (k_keep/b subspace reads) — for parity tests
        and the bench_subspace_io before/after column."""
        assert q.shape[0] == self.ncols
        assert sum(new_widths) == q.shape[1]
        out = MultiVector(self.store, self.n, group_size=self.group_size,
                          readahead=self.readahead, impl=self.impl)
        if fused and self.nblocks:
            budget = pass_acc_bytes
            if budget is None:
                # a session under an arbiter allotment caps the transient
                # accumulators at its share of the device budget (the
                # namespace facade reports it); a plain store keeps the
                # global 1 GiB default
                cap = getattr(self.store, "compress_acc_bytes",
                              lambda: None)()
                budget = (COMPRESS_PASS_ACC_BYTES if cap is None
                          else min(COMPRESS_PASS_ACC_BYTES, cap))
            groups: List[List[int]] = [[]]
            acc = 0
            for w in new_widths:
                if groups[-1] and (acc + w) * self.n * 4 > budget:
                    groups.append([])
                    acc = 0
                groups[-1].append(w)
                acc += w
            off = 0
            for gw in groups:
                k = sum(gw)
                self.store.make_room(k * self.n * 4)
                p = SubspacePass(self)
                h = p.add_matmul(q[:, off:off + k], gw)
                p.run()
                for blk in h.value:
                    out.append_block(blk, pin_recent=False)
                off += k
        else:
            off = 0
            for w in new_widths:
                self.store.make_room(w * self.n * 4)
                blk = self.mv_times_mat(q[:, off:off + w])
                out.append_block(blk, pin_recent=False)
                off += w
        return out

    def to_dense(self) -> jnp.ndarray:
        if self.nblocks == 0:
            return jnp.zeros((self.n, 0), jnp.float32)
        p = SubspacePass(self)
        h = p.add_visit(lambda i, blk, peers: blk, axis=1)
        p.run()
        return h.value
