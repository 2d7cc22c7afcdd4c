"""Fused streamed subspace passes — §3.4.3's pass minimization made a type.

The paper's cost model is brutal and simple: reorthogonalization dominates
SEM runtime (>90%) and its cost is *streamed reads of the on-SSD subspace*.
The cheapest bandwidth is the bytes you never read, so every whole-subspace
operation should piggyback on the same block visit instead of walking the
subspace again. `SubspacePass` is that plan: attach any number of consumers
(Gram against a device-resident operand, multi-accumulator TSGEMM, a fused
project-out update, dot/norm reductions, arbitrary per-block visitors),
then `run()` streams each block of the MultiVector **exactly once**,
handing the materialized block to every consumer in attachment order.

I/O discipline per pass:

  * the full pass's block list is announced to `TieredStore.prefetch` up
    front (the backend's readahead window bounds how much actually
    queues), and the window is re-offered as the walk advances — this
    replaces the ad-hoc per-group `_prefetch_group` calls, so *every*
    subspace walk gets readahead, including the small reductions
    (mv_dot / mv_norm / clone_view) that previously had none;
  * one `TieredStore.get` per block per pass, shared by all consumers
    (lazy MvScale factors are applied once, to the shared value);
  * `TieredStore.begin_pass()` is called once per run, so
    `IOStats.passes` counts streamed subspace reads and bytes-per-pass
    falls out of the byte-exact counters (benchmarks/bench_subspace_io.py
    archives reads-per-expansion and reads-per-restart off these).

Peers: a pass may walk other MultiVectors in lockstep (mv_dot, mv_add_mv);
their blocks are interleaved into the announced list and materialized at
the same visit.

Dispatch: the Gram, TSGEMM and project-out consumers each visit a block
with ONE compiled program (`_gram_visit`, `_matmul_visit`,
`_project_visit`), so the zero-padding, the row slice of the small
operand and both kernels of a visit are one host dispatch rather than a
chain of eager ops. The row offset is traced, so one program serves every
block index; the Gram and project-out programs serve every basis size,
the TSGEMM one every basis of its small operand's row count (a restart
compresses at one). Generic `add_visit` callbacks stay eager; the
`pass.subspace` span counts both kinds (`compiled_visits`,
`eager_visits`).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops
from repro.obs import trace


# ------------------------------------------------------- compiled visits
@functools.partial(jax.jit, static_argnames=("impl",))
def _gram_visit(block, other, alpha, *, impl):
    """alpha * blockᵀ @ other."""
    return kops.gram(block, other, alpha=alpha, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",), donate_argnums=(3,))
def _matmul_visit(block, small, r0, accs, alpha, *, impl):
    """accs[j] += alpha * block @ small[r0:r0+b, cols_j] for every output
    accumulator; the column groups follow the accumulators' widths."""
    rows = lax.dynamic_slice_in_dim(small, r0, block.shape[1], axis=0)
    out, c = [], 0
    for acc in accs:
        w = acc.shape[1]
        out.append(kops.tsgemm(block, rows[:, c:c + w], alpha=alpha,
                               beta=1.0, c0=acc, impl=impl))
        c += w
    return tuple(out)


def _project(block, w, *, impl):
    """h = blockᵀw, then w − block @ h: one block-MGS step."""
    h = kops.gram(block, w, impl=impl)
    return h, kops.tsgemm(block, h, alpha=-1.0, beta=1.0, c0=w, impl=impl)


# the pass owns w after its first visit and donates it from then on; the
# first visit leaves the caller's array intact
_project_visit = jax.jit(_project, static_argnames=("impl",),
                         donate_argnums=(1,))
_project_first_visit = jax.jit(_project, static_argnames=("impl",))


class Handle:
    """Result slot for one consumer; filled when the pass runs."""

    __slots__ = ("_value", "_ready")

    def __init__(self):
        self._ready = False
        self._value = None

    def _set(self, v) -> None:
        self._value = v
        self._ready = True

    @property
    def value(self):
        if not self._ready:
            raise RuntimeError("SubspacePass consumer read before run()")
        return self._value


class _Consumer:
    handle: Handle
    compiled = True     # one compiled program per visit

    def visit(self, i: int, block: jnp.ndarray,
              peers: Sequence[jnp.ndarray]) -> None:
        raise NotImplementedError

    def finalize(self):
        raise NotImplementedError


class _Gram(_Consumer):
    """MvTransMv: alpha * Vᵀ @ other, other device-resident (§3.4.3 shared
    I/O — the right operand is read zero times from the slow tier)."""

    def __init__(self, other, alpha, impl):
        self.other, self.alpha, self.impl = other, alpha, impl
        self.parts: List[jnp.ndarray] = []
        self.handle = Handle()

    def visit(self, i, block, peers):
        self.parts.append(_gram_visit(block, self.other, self.alpha,
                                      impl=self.impl))

    def finalize(self):
        if not self.parts:
            return jnp.zeros((0, self.other.shape[1]), jnp.float32)
        return jnp.concatenate(self.parts, axis=0)


class _Matmul(_Consumer):
    """MvTimesMatAddMv with N output accumulators: one streamed read
    computes every column group of `small` (restart compression computes
    all k_keep/b output blocks in the same visit — the pre-PR path paid
    one full subspace pass per output block)."""

    def __init__(self, small, row_offsets, out_widths, alpha, n, impl):
        self.small = jnp.asarray(small, jnp.float32)
        self.row_offsets = row_offsets      # block index -> row offset
        self.alpha, self.impl = alpha, impl
        self.accs = tuple(jnp.zeros((n, w), jnp.float32) for w in out_widths)
        self.handle = Handle()

    def visit(self, i, block, peers):
        self.accs = _matmul_visit(block, self.small, self.row_offsets[i],
                                  self.accs, self.alpha, impl=self.impl)

    def finalize(self):
        return list(self.accs)


class _Project(_Consumer):
    """Fused BCGS pass: per visit h_i = V_iᵀw, then w ← w − V_i h_i in the
    *same* read — one streamed pass where the unfused CGS pass pays two
    (MvTransMv + MvTimesMatAddMv). Block-MGS update order; the telescoping
    w₀ = Σ V_i h_i + w_final keeps the Krylov invariant exact."""

    def __init__(self, w, impl):
        self.w, self.impl = w, impl
        self.parts: List[jnp.ndarray] = []
        self.handle = Handle()

    def visit(self, i, block, peers):
        fn = _project_visit if self.parts else _project_first_visit
        h_i, self.w = fn(block, self.w, impl=self.impl)
        self.parts.append(h_i)

    def finalize(self):
        if not self.parts:
            h = jnp.zeros((0, self.w.shape[1]), jnp.float32)
        else:
            h = jnp.concatenate(self.parts, axis=0)
        return h, self.w


class _Visit(_Consumer):
    """Generic per-block visitor: fn(i, block, peers) -> part or None;
    finalize concatenates collected parts along `axis` (or returns them
    raw with axis=None). mv_add_mv / clone_view / to_dense ride this."""

    compiled = False    # the callback's own ops, each dispatched eagerly

    def __init__(self, fn, axis: Optional[int]):
        self.fn, self.axis = fn, axis
        self.parts: List = []
        self.handle = Handle()

    def visit(self, i, block, peers):
        part = self.fn(i, block, peers)
        if part is not None:
            self.parts.append(part)

    def finalize(self):
        if self.axis is None:
            return self.parts
        return jnp.concatenate(self.parts, axis=self.axis)


class SubspacePass:
    """One planned streamed read of a MultiVector feeding many consumers.

    Usage::

        p = SubspacePass(v)
        h = p.add_gram(w)          # handles fill at run()
        p.run()
        g = h.value

    `peers` are MultiVectors with the same block structure walked in
    lockstep (their blocks arrive as the `peers` argument of each visit).
    `readahead` is the number of *store names* kept announced ahead of the
    walk; it defaults to the MultiVector's group-level readahead
    (`readahead * group_size` blocks — the same depth the retired
    `_prefetch_group` maintained).

    `block_ids` restricts the walk to a subset of blocks (in the given
    order); visitors still receive the *original* block index. LOBPCG's
    residual pass reads only the X block of its [X, W, P] basis this way
    instead of paying a full-basis read.
    """

    def __init__(self, mv, *, peers: Sequence = (),
                 readahead: int | None = None,
                 block_ids: Sequence[int] | None = None):
        self.mv = mv
        self.peers = list(peers)
        for p in self.peers:
            assert p.nblocks == mv.nblocks, (p.nblocks, mv.nblocks)
        self.block_ids = (list(range(mv.nblocks)) if block_ids is None
                          else [int(i) for i in block_ids])
        for i in self.block_ids:
            assert 0 <= i < mv.nblocks, (i, mv.nblocks)
        self.store = mv.store
        if readahead is None:
            readahead = mv.readahead * mv.group_size * (1 + len(self.peers))
        self.readahead = max(0, int(readahead))
        self._consumers: List[_Consumer] = []
        self._ran = False

    # ------------------------------------------------------------ consumers
    def _attach(self, c: _Consumer) -> Handle:
        self._consumers.append(c)
        return c.handle

    def add_gram(self, other: jnp.ndarray, *, alpha: float = 1.0) -> Handle:
        """h = alpha * selfᵀ @ other → (m, k)."""
        return self._attach(_Gram(other, alpha, self.mv.impl))

    def add_matmul(self, small: jnp.ndarray,
                   out_widths: Sequence[int] | None = None, *,
                   alpha: float = 1.0) -> Handle:
        """accs[j] = alpha * self @ small[:, cols_j] — a list of output
        accumulators, one per entry of out_widths (default: one output of
        small's full width). All outputs stay device-resident for the
        pass, so a caller splitting very wide products should bound
        out_widths per pass (MultiVector.compress does). On a restricted
        walk (`block_ids`), `small`'s rows span the visited blocks only,
        stacked in walk order."""
        m, k = small.shape
        widths = self.mv.block_widths()
        m_visited = sum(widths[i] for i in self.block_ids)
        assert m == m_visited, (m, m_visited)
        if out_widths is None:
            out_widths = [k]
        assert sum(out_widths) == k, (out_widths, k)
        offsets, off = {}, 0
        for i in self.block_ids:
            offsets[i] = off
            off += widths[i]
        return self._attach(_Matmul(small, offsets, out_widths, alpha,
                                    self.mv.n, self.mv.impl))

    def add_project(self, w: jnp.ndarray) -> Handle:
        """Fused CGS step: returns (h, w − self @ h) from one read."""
        return self._attach(_Project(w, self.mv.impl))

    def add_dot(self) -> Handle:
        """Columnwise dots against peer 0 (MvDot)."""
        assert self.peers, "add_dot needs a peer MultiVector"
        return self.add_visit(
            lambda i, blk, peers: jnp.sum(blk * peers[0], axis=0), axis=0)

    def add_norm(self) -> Handle:
        """Column 2-norms (MvNorm)."""
        return self.add_visit(
            lambda i, blk, peers: jnp.sqrt(jnp.sum(blk ** 2, axis=0)),
            axis=0)

    def add_visit(self, fn: Callable, *, axis: Optional[int] = 0) -> Handle:
        return self._attach(_Visit(fn, axis))

    # ------------------------------------------------------------------ run
    def _names(self) -> List[str]:
        names = []
        for i in self.block_ids:
            names.append(self.mv._block_name(i))
            for p in self.peers:
                names.append(p._block_name(i))
        return names

    def run(self) -> None:
        """Stream every block once; fill all consumer handles. Single-use:
        consumers accumulate state across visits, so re-running would
        silently double every result — build a fresh pass instead."""
        if self._ran:
            raise RuntimeError("SubspacePass already ran; build a new pass")
        self._ran = True
        mv = self.mv
        names = self._names()
        read0 = self.store.begin_pass()
        # the span's `bytes` attribute is the same host_bytes_read delta
        # end_pass attributes to pass_bytes_read — the report reconciles
        # the two accountants byte-exactly
        with trace.span("pass.subspace", blocks=len(self.block_ids),
                        consumers=len(self._consumers),
                        peers=len(self.peers)) as sp:
            if names:
                self.store.prefetch(names)  # whole pass announced up front
            pos = 0
            for i in self.block_ids:
                if self.readahead:
                    # re-offer the window: ids past the backend's readahead
                    # depth were dropped at announce time and re-queue here
                    self.store.prefetch(
                        names[pos + 1:pos + 1 + self.readahead])
                block = self._materialize(mv, i)
                pos += 1
                pblocks = []
                for p in self.peers:
                    pblocks.append(self._materialize(p, i))
                    pos += 1
                for c in self._consumers:
                    c.visit(i, block, pblocks)
            self.store.end_pass(read0)
            n_compiled = sum(c.compiled for c in self._consumers)
            sp.set(bytes=self.store.stats.host_bytes_read - read0,
                   compiled_visits=n_compiled * len(self.block_ids),
                   eager_visits=(len(self._consumers) - n_compiled)
                   * len(self.block_ids))
        for c in self._consumers:
            c.handle._set(c.finalize())

    @staticmethod
    def _materialize(mv, i: int) -> jnp.ndarray:
        """One store read per block per pass, shared by all consumers
        (lazy MvScale applied once, here)."""
        b = mv._blocks[i]
        val = mv.store.get(b.name)
        if b.scale != 1.0:
            val = b.scale * val
        return val
