"""Device-resident execution of a compiled netlist level plan.

The host-side numpy walk in :mod:`repro.core.garble` pays a Python round
trip per level: gather labels, dispatch XOR/INV/AND batches separately,
scatter, repeat. This module compiles the whole walk — wire store,
gathers, FreeXOR/INV/Half-Gate, scatters — into ONE ``jax.jit`` call per
``(netlist, instances, impl)``: a ``lax.scan`` over the plan's fixed-shape
*chunks* (see :class:`~repro.core.netlist.LevelPlan`) whose body evaluates
one padded level. Because every chunk has the same (and_width, free_width)
shape, the executable contains a single level body regardless of netlist
depth, so XLA compile time stays flat in circuit size.

The body is built around what profiling the scan showed matters on a CPU
host (and costs nothing on TPU):

* the wire store is **liveness-compacted** (default): the plan recycles a
  gate's row once its fanout is consumed, so the carry tracks the peak
  live label set instead of the gate count and gathers stay
  cache-resident (a production softmax row shrinks ~10x);
* each chunk commits with ONE contiguous ``dynamic_update_slice`` of its
  ``perm``-ordered lane block per carry — a scattered store or a second
  dynamic write on the same carry forces XLA to copy that carry every
  step;
* garble tables are emitted **packed**: dense table-store carries written
  with one contiguous slice per chunk at the plan's ``table_base``
  offsets — not through the scan's stacked ys, which padded every chunk
  to ``and_width`` rows and materialized ``K×Ca`` garbage rows at
  preprocessing-scale instance counts;
* two **instance regimes** (same threshold as the plan's width regimes):

  - *throughput* (I > 8): the store is **planar** ``(4, n_rows, I)`` —
    one plane per label word — so the Half-Gate cipher consumes gathered
    ``(lanes, I)`` planes with ZERO per-chunk transposes, and hashes go
    through :func:`repro.kernels.halfgate.ref.eval_and_split` /
    ``garble_and_split``: one un-concatenated hash call per label group.
    The previous 2N/4N-lane batched pass looked cheaper but XLA
    duplicates a multiply-consumed concat+slice chain into every
    consumer fusion — the compiled body executed the ARX permutation ~3x
    over, which is why garbling used to LOSE to the numpy oracle at
    I=256;
  - *latency* (I <= 8, e.g. one online request): the store is row-major
    ``(n_rows, I, 4)`` and the cipher runs on flat concatenated planes
    (:func:`~repro.kernels.halfgate.ref.eval_and_planar`). At tiny
    batches per-op dispatch dominates and the fused 2N/4N pass wins;
    planar gathers of 1-word rows lose the old layout's contiguous
    16-byte label reads (measured ~2x at I=1);

* per-chunk gathers can be **double-buffered** (``prefetch``): the scan
  carry holds the current chunk's pre-gathered block and the body issues
  the NEXT chunk's gather speculatively against the pre-write store —
  pinned alongside the cipher with ``lax.optimization_barrier``, then
  patched from the freshly computed write block for the lanes the
  current chunk just produced (the paper's speculation-against-memory-
  stall). On XLA:CPU the pre-write gather defeats the carry's in-place
  aliasing (measured ~8x regression: the store is copied every step), so
  prefetch defaults ON only for the real-TPU ``"pallas"`` impl; both
  settings are bit-exact;
* the ``"jit"`` impl hashes only the AND block (XOR/INV lanes are one
  vector XOR: INV second inputs read the zero dummy row, so there is no
  per-lane select anywhere); the ``"pallas"``/``"pallas_interpret"``
  impls hand the concatenated block to the fused ``kernels/level_eval``
  pass on evaluate, and the AND block alone on garble (free-lane table
  rows are zero by construction — shipping them through the kernel
  tripled the garble lane's output volume for nothing).

The wire store lives entirely inside the executable (scan carry — XLA
updates it in place), so a cached evaluate performs zero per-level
host<->device transfers: one launch in, output labels out.

Executors are cached on the plan, keyed by ``(instances, impl)``;
``n_traces`` counts actual retraces (it only advances while jax traces the
body) and ``n_eval_calls`` / ``n_garble_calls`` count invocations, which
is what the cache-hit and single-dispatch tests assert on.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.netlist import (
    LATENCY_MAX_INSTANCES,
    LevelPlan,
    Netlist,
    OP_AND,
    OP_PAD,
    compile_level_plan,
)
from repro.kernels.halfgate import ref as HG
from repro.kernels.level_eval.level_eval import (
    eval_level_pallas,
    garble_level_pallas,
)

U32 = jnp.uint32
I32 = jnp.int32


def _planes(x):
    """(4, lanes, I) planar block -> 4-tuple of (lanes, I) planes."""
    return (x[0], x[1], x[2], x[3])


def _flat_planar(x):
    """(lanes, I, 4) packed block -> 4-tuple of flat (lanes*I,) planes."""
    p = x.transpose(2, 0, 1).reshape(4, -1)
    return (p[0], p[1], p[2], p[3])


def _flat_packed(planes, lanes, instances):
    return jnp.stack(planes, 0).reshape(4, lanes, instances).transpose(1, 2, 0)


class LevelExecutor:
    """One compiled evaluate/garble walk for a fixed (plan, I, impl).

    ``prefetch=None`` resolves to True only on the real-TPU ``"pallas"``
    impl (see module docstring); any explicit value wins. Both settings
    are bit-exact — prefetch is purely a scheduling change. The store
    layout (planar vs row-major, see module docstring) follows the
    instance regime and is likewise invisible in the results.
    """

    def __init__(self, plan: LevelPlan, instances: int, impl: str,
                 prefetch: Optional[bool] = None):
        if impl not in ("jit", "pallas", "pallas_interpret"):
            raise ValueError(f"device executor impl {impl!r}")
        self.plan = plan
        self.instances = int(instances)
        self.impl = impl
        self.prefetch = (impl == "pallas") if prefetch is None \
            else bool(prefetch)
        self.planar = self.instances > LATENCY_MAX_INSTANCES
        self.n_traces = 0
        self.n_eval_calls = 0
        self.n_garble_calls = 0
        self.n_src = len(plan.source_ids)
        # per-chunk scan operands: device-resident once, reused every
        # call; the four wire-read index blocks are fused into ONE array
        # so the body issues a single gather per chunk (per-step thunk
        # count dominates small-batch walks). With prefetch the xs carry
        # the NEXT chunk's indices (rolled by one): the body consumes the
        # pre-gathered block from the carry and issues chunk k+1's load.
        # Arrays a body doesn't touch are dead-code-eliminated.
        widx = np.concatenate(
            [plan.and_in0, plan.and_in1, plan.free_in0, plan.free_in1],
            axis=1)
        self._widx0 = jnp.asarray(widx[0], I32)
        self._xs = (
            jnp.asarray(plan.base, I32),
            jnp.asarray(plan.table_base, I32),
            jnp.asarray(np.roll(widx, -1, axis=0) if self.prefetch
                        else widx, I32),
            jnp.asarray(plan.and_slot, I32),
            jnp.asarray(plan.perm, I32),
            jnp.asarray(
                np.where(plan.and_slot < plan.n_and, OP_AND, OP_PAD), U32),
            jnp.asarray(plan.free_inv, U32),
            jnp.asarray(plan.free_ops, U32),
        )
        self._outs = jnp.asarray(plan.out_rows, I32)
        self._wire_rows = jnp.asarray(plan.wire_rows, I32)
        self._and_rows = jnp.asarray(plan.and_rows, I32)
        self._eval = jax.jit(self._eval_fn)
        self._garble = jax.jit(self._garble_fn,
                               static_argnames=("keep_wires",))

    # ------------------------------------------------------------------
    # layout adapters: 'block' shapes are (4, lanes, I) planar or
    # (lanes, I, 4) row-major depending on the regime
    # ------------------------------------------------------------------
    def _store_init(self, labels):
        """labels (I, n, 4) -> zero store with rows [0, n) filled."""
        I = self.instances
        if self.planar:
            w = jnp.zeros((4, self.plan.n_rows, I), U32)
            blk = labels.astype(U32).transpose(2, 1, 0)
        else:
            w = jnp.zeros((self.plan.n_rows, I, 4), U32)
            blk = labels.astype(U32).transpose(1, 0, 2)
        return lax.dynamic_update_slice(w, blk, (I32(0), I32(0), I32(0)))

    def _gather(self, w, rows):
        return w[:, rows] if self.planar else w[rows]

    def _commit(self, w, out, off):
        at = (I32(0), off, I32(0)) if self.planar else \
            (off, I32(0), I32(0))
        return lax.dynamic_update_slice(w, out, at)

    def _rows_out(self, w, rows):
        """Store rows -> (I, n, 4) result layout."""
        return w[:, rows].transpose(2, 1, 0) if self.planar else \
            w[rows].transpose(1, 0, 2)

    def _split_block(self, g):
        """Gathered block -> (a, b, fa, fb) sub-blocks along lanes."""
        ca, cf = self.plan.and_width, self.plan.free_width
        if self.planar:
            return (g[:, :ca], g[:, ca:2 * ca],
                    g[:, 2 * ca:2 * ca + cf], g[:, 2 * ca + cf:])
        return (g[:ca], g[ca:2 * ca],
                g[2 * ca:2 * ca + cf], g[2 * ca + cf:])

    def _cat_perm(self, and_out, free_out, pm):
        if self.planar:
            return jnp.concatenate([and_out, free_out], 1)[:, pm]
        return jnp.concatenate([and_out, free_out], 0)[pm]

    def _free_xor(self, fa, fb):
        return fa ^ fb

    def _free_inv_r(self, free_out, finv, rb):
        """Garbler: XOR R onto the INV lanes. rb broadcasts per layout."""
        mask = finv[None, :, None] if self.planar else finv[:, None, None]
        return jnp.where(mask != 0, free_out ^ rb, free_out)

    def _to_kernel(self, x):
        """block -> (4, lanes*I) word planes, lane-major (the kernel's
        layout; the planar store's blocks already are, as a reshape)."""
        if self.planar:
            return x.reshape(4, -1)
        return x.transpose(2, 0, 1).reshape(4, -1)

    def _from_kernel(self, x, lanes):
        I = self.instances
        if self.planar:
            return x.reshape(4, lanes, I)
        return x.reshape(4, lanes, I).transpose(1, 2, 0)

    # ------------------------------------------------------------------
    # fused-kernel bodies (pallas / pallas_interpret)
    # ------------------------------------------------------------------
    def _lanes(self, per_lane):
        """(lanes,) per-lane scalar -> flat (lanes*I,) lane-major vector."""
        I = self.instances
        return jnp.broadcast_to(per_lane[:, None],
                                (per_lane.shape[0], I)).reshape(-1)

    def _fused_eval(self, and_ops, a, b, tg, te, slot, fops, fa, fb):
        """Concatenated AND+free block through the fused level kernel.

        Blocks go to the kernel as (4, G) word planes: a reshape of the
        planar store's blocks; the row-major (latency) store transposes
        its few lanes. The CPU ``"jit"`` impl never takes this path.
        """
        I = self.instances
        ca, cf = self.plan.and_width, self.plan.free_width
        ops = self._lanes(jnp.concatenate([and_ops, fops]))
        tw = self._lanes(jnp.concatenate(
            [slot.astype(U32), jnp.zeros((cf,), U32)]))
        z = jnp.zeros((4, cf * I), U32)
        o = eval_level_pallas(
            ops,
            jnp.concatenate([self._to_kernel(a), self._to_kernel(fa)], 1),
            jnp.concatenate([self._to_kernel(b), self._to_kernel(fb)], 1),
            jnp.concatenate([self._to_kernel(tg), z], 1),
            jnp.concatenate([self._to_kernel(te), z], 1),
            tw,
            interpret=(self.impl == "pallas_interpret"),
        )
        o = self._from_kernel(o, ca + cf)
        if self.planar:
            return o[:, :ca], o[:, ca:]
        return o[:ca], o[ca:]

    def _fused_garble(self, and_ops, a0, b0, slot, rb, finv, fa, fb):
        """Garble lane: ONLY the AND block goes through the fused kernel.

        Free lanes are one vector XOR (INV lanes XOR R on top) — their
        table rows are zero by construction, so shipping them through the
        kernel's 3-output garble lane was pure wasted volume.
        """
        I = self.instances
        ca = self.plan.and_width
        ops = self._lanes(and_ops)
        tw = self._lanes(slot.astype(U32))
        if self.planar:
            rf = jnp.broadcast_to(rb, (4, ca, I))
        else:
            rf = jnp.broadcast_to(rb, (ca, I, 4))
        c0, tg, te = garble_level_pallas(
            ops, self._to_kernel(a0), self._to_kernel(b0),
            self._to_kernel(rf), tw,
            interpret=(self.impl == "pallas_interpret"),
        )
        free_out = self._free_inv_r(fa ^ fb, finv, rb)
        return (self._from_kernel(c0, ca), free_out,
                self._from_kernel(tg, ca), self._from_kernel(te, ca))

    # ------------------------------------------------------------------
    # the double-buffered gather
    # ------------------------------------------------------------------
    def _spec_gather_commit(self, w, out, off, widx_nxt):
        """Commit chunk k's block; return (new store, chunk k+1's block).

        The next chunk's gather is issued against the PRE-write store —
        ``optimization_barrier`` pins it next to the cipher output so the
        load overlaps the hash instead of queueing behind the store
        commit — then the lanes chunk k itself just produced (rows inside
        the freshly written window) are forwarded from the write block.
        Rows outside the window are final by the plan's liveness
        invariant, so the speculative value is the true value.
        """
        stride = self.plan.and_width + self.plan.free_width
        spec = self._gather(w, widx_nxt)
        out, spec = lax.optimization_barrier((out, spec))
        w = self._commit(w, out, off)
        rel = jnp.clip(widx_nxt - off, 0, stride - 1)
        hit = (widx_nxt >= off) & (widx_nxt < off + stride)
        if self.planar:
            g_nxt = jnp.where(hit[None, :, None], out[:, rel], spec)
        else:
            g_nxt = jnp.where(hit[:, None, None], out[rel], spec)
        return w, g_nxt

    # ------------------------------------------------------------------
    # evaluate
    # ------------------------------------------------------------------
    def _eval_fn(self, active: jnp.ndarray, tables: jnp.ndarray):
        """active (I, n_src, 4); tables (I, >=nAND, 2, 4) -> (I, n_out, 4)."""
        self.n_traces += 1  # python side effect: advances only on retrace
        I, ca = self.instances, self.plan.and_width
        cf = self.plan.free_width
        # (4, 2, nA, I) planar / (nA, 2, I, 4) row-major table views
        tabT = (jnp.transpose(tables.astype(U32), (3, 2, 1, 0))
                if self.planar
                else jnp.transpose(tables.astype(U32), (1, 2, 0, 3)))
        wires = self._store_init(active)

        def body(carry, xs):
            w, g = carry if self.prefetch else (carry, None)
            off, _tboff, widx, slot, pm, and_ops, _, fops = xs
            if not self.prefetch:
                g = self._gather(w, widx)  # one gather: [a|b|fa|fb]
            a, b, fa, fb = self._split_block(g)
            # pad slots gather a clamped table row; the pad tail absorbs
            # it (INV/pad free lanes read the zero dummy row)
            if self.planar:
                tt = tabT[:, :, slot]  # (4, 2, Ca, I)
                tg, te = tt[:, 0], tt[:, 1]
            else:
                tt = tabT[slot]  # (Ca, 2, I, 4)
                tg, te = tt[:, 0], tt[:, 1]
            if self.impl == "jit":
                # hash only the AND block; free lanes are one vector XOR
                # (INV passes through via b == 0)
                if self.planar:
                    tw = jnp.broadcast_to(slot.astype(U32)[:, None],
                                          (ca, I))
                    and_out = jnp.stack(HG.eval_and_split(
                        _planes(a), _planes(b),
                        _planes(tg), _planes(te), tw), 0)
                else:
                    tw = self._lanes(slot.astype(U32))
                    and_out = _flat_packed(
                        HG.eval_and_planar(
                            _flat_planar(a), _flat_planar(b),
                            _flat_planar(tg), _flat_planar(te), tw),
                        ca, I)
                free_out = self._free_xor(fa, fb)
            else:
                and_out, free_out = self._fused_eval(
                    and_ops, a, b, tg, te, slot, fops, fa, fb)
            out = self._cat_perm(and_out, free_out, pm)
            if self.prefetch:
                w, g_nxt = self._spec_gather_commit(w, out, off, widx)
                return (w, g_nxt), None
            return self._commit(w, out, off), None

        if self.prefetch:
            g0 = self._gather(wires, self._widx0)
            (wires, _), _ = lax.scan(body, (wires, g0), self._xs)
        else:
            wires, _ = lax.scan(body, wires, self._xs)
        return self._rows_out(wires, self._outs)

    def evaluate(self, active, tables) -> jnp.ndarray:
        self.n_eval_calls += 1
        return self._eval(jnp.asarray(active), jnp.asarray(tables))

    # ------------------------------------------------------------------
    # garble
    # ------------------------------------------------------------------
    def _garble_fn(self, src_labels: jnp.ndarray, r: jnp.ndarray,
                   *, keep_wires: bool = False):
        """src_labels (I, n_src, 4) fresh zero-labels; r (I, 4) offset.

        Returns (input_zero at source order, tables (I, max(nAND,1), 2, 4),
        output color bits (I, n_out)[, full wire-zero store]).
        """
        self.n_traces += 1
        I, nA = self.instances, self.plan.n_and
        ca = self.plan.and_width
        r = r.astype(U32)
        # R broadcast shaped for the regime's block layout
        rb = r.T[:, None, :] if self.planar else r[None]
        rp_flat = tuple(jnp.broadcast_to(r[None, :, k], (ca, I)).reshape(-1)
                        for k in range(4))  # latency path: planar R
        wires = self._store_init(src_labels)
        # packed table stores: one contiguous slice per chunk at
        # table_base — each its own scan carry with its own single
        # dynamic write, so XLA aliases every store in place (the old
        # ys-stack materialized K×Ca padded rows and re-gathered them on
        # exit). Planar regime: two (4, nT, I) carries; latency regime:
        # one (nT, 2, I, 4) carry.
        if self.planar:
            tabs0 = (jnp.zeros((4, self.plan.n_table_rows, I), U32),
                     jnp.zeros((4, self.plan.n_table_rows, I), U32))
        else:
            tabs0 = (jnp.zeros((self.plan.n_table_rows, 2, I, 4), U32),)

        def tab_commit(tabs, tg, te, tboff):
            if self.planar:
                return (lax.dynamic_update_slice(
                            tabs[0], tg, (I32(0), tboff, I32(0))),
                        lax.dynamic_update_slice(
                            tabs[1], te, (I32(0), tboff, I32(0))))
            blk = jnp.stack([tg, te], 1)  # (Ca, 2, I, 4)
            return (lax.dynamic_update_slice(
                tabs[0], blk, (tboff, I32(0), I32(0), I32(0))),)

        def body(carry, xs):
            if self.prefetch:
                w, tabs, g = carry[0], carry[1], carry[2]
            else:
                (w, tabs), g = carry, None
            off, tboff, widx, slot, pm, and_ops, finv, fops = xs
            if not self.prefetch:
                g = self._gather(w, widx)
            a, b, fa, fb = self._split_block(g)
            if self.impl == "jit":
                if self.planar:
                    tw = jnp.broadcast_to(slot.astype(U32)[:, None],
                                          (ca, I))
                    c0, tg, te = HG.garble_and_split(
                        _planes(a), _planes(b), _planes(rb), tw)
                    and_out = jnp.stack(c0, 0)
                    tg = jnp.stack(tg, 0)
                    te = jnp.stack(te, 0)
                else:
                    tw = self._lanes(slot.astype(U32))
                    c0, tg, te = HG.garble_and_planar(
                        _flat_planar(a), _flat_planar(b), rp_flat, tw)
                    and_out = _flat_packed(c0, ca, I)
                    tg = _flat_packed(tg, ca, I)
                    te = _flat_packed(te, ca, I)
                # free: XOR lanes a0^b0; INV lanes a0^R (b reads zero)
                free_out = self._free_inv_r(fa ^ fb, finv, rb)
            else:
                and_out, free_out, tg, te = self._fused_garble(
                    and_ops, a, b, slot, rb, finv, fa, fb)
            out = self._cat_perm(and_out, free_out, pm)
            tabs = tab_commit(tabs, tg, te, tboff)
            if self.prefetch:
                w, g_nxt = self._spec_gather_commit(w, out, off, widx)
                return (w, tabs, g_nxt), None
            return (self._commit(w, out, off), tabs), None

        if self.prefetch:
            g0 = self._gather(wires, self._widx0)
            (wires, tabs, _), _ = lax.scan(
                body, (wires, tabs0, g0), self._xs)
        else:
            (wires, tabs), _ = lax.scan(body, (wires, tabs0), self._xs)
        in_zero = (wires[:, : self.n_src].transpose(2, 1, 0)
                   if self.planar
                   else wires[: self.n_src].transpose(1, 0, 2))
        out_perm = ((wires[0, self._outs] if self.planar
                     else wires[self._outs, :, 0]) & U32(1)).T
        # packed table stores -> dense AND-slot order (I, nA, 2, 4)
        if not nA:
            tables = jnp.zeros((I, 1, 2, 4), U32)
        elif self.planar:
            tables = jnp.stack([tabs[0][:, self._and_rows],
                                tabs[1][:, self._and_rows]],
                               0).transpose(3, 2, 0, 1)
        else:
            tables = jnp.transpose(tabs[0][self._and_rows], (2, 0, 1, 3))
        if keep_wires:
            return (in_zero, tables, out_perm,
                    self._rows_out(wires, self._wire_rows))
        return in_zero, tables, out_perm

    def garble(self, src_labels, r, *, keep_wires: bool = False):
        if keep_wires and self.plan.compact:
            raise ValueError(
                "keep_wires needs the full wire store: use a "
                "compact=False plan (rows are recycled here)")
        self.n_garble_calls += 1
        return self._garble(jnp.asarray(src_labels), jnp.asarray(r),
                            keep_wires=keep_wires)


def get_executor(net: Netlist, instances: int, impl: str,
                 compact: bool = True,
                 garbling: bool = False) -> LevelExecutor:
    """Compiled-walk cache: one executor per (netlist, instances, impl).

    The plan (and thus the cache) hangs off the netlist object, so its
    lifetime matches the protocol's netlist cache and the jit executables
    are reused across every preprocess/run that touches the same shape.
    Small batches get the latency-regime plan (wider chunks, fewer scan
    steps) and store layout; large batches the tight throughput plan with
    the planar store. ``compact`` selects the liveness-compacted store
    (default; ``keep_wires`` garbling needs ``compact=False``).
    ``garbling`` picks the garble-tightened widths on AND-rich netlists
    (see ``netlist._chunk_widths``) — a separate plan whose executors are
    cached independently; plans of any width/compact combination produce
    bit-identical labels/tables, so garbling on one plan and evaluating
    on another is safe by construction.
    """
    plan = compile_level_plan(net, instances=instances, compact=compact,
                              garbling=garbling)
    key = (int(instances), impl)
    exe = plan._executors.get(key)
    if exe is None:
        exe = LevelExecutor(plan, instances, impl)
        plan._executors[key] = exe
    return exe
