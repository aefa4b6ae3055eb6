"""Batched greedy placement engine: lockstep `two_phase` over a fleet.

PR 1 fused the mapping-LP phase of a fleet sweep into one batched PDHG
solve; this module does the same for the paper's phase-2 greedy
placement (§III first/similarity fit, §V-D cross-fill), the remaining
per-instance Python in ``evaluate_many``.  ``place_many`` advances all B
instances in lockstep over their task-event schedules, wave-synchronized
at node-type phase boundaries: every instance's open nodes live in ONE
padded ``(B, N_nodes, T', D)`` array, and each lockstep step scores the
pending task of every instance against all its candidate nodes in a
single batched feasibility + similarity pass (the dot-product/best-fit
hot loop of this whole family of vector bin-packing heuristics) instead
of B Python-level ``TypePool.find_fit`` calls.  All per-step bookkeeping
(schedule pointers, purchases, capacity updates) is vectorized across
instances, so a step costs O(1) numpy dispatches regardless of B.
Each step reads and writes only its window of the time axis, the live
tasks' span union ``[t0, t1)``, not all T' slots: a task's span is
typically a small part of the trimmed timeline.  A cross-fill sub-phase
takes no step for an attempt that fits no node: it tests each
instance's pending candidates against a range-minimum table of its
pool and places the first that fits, so each of its steps places a
task in every live instance.

Wave synchronization is the engine's load-bearing trick: instances are
independent, so inserting barriers between their (own-pack, cross-fill)
phase pairs changes nothing per instance — but since nodes are only
purchased during a type's own phase, every wave's candidate pool starts
*empty* and grows at the tail.  Each wave therefore runs on a compact
``(B, W, T', D)`` pool tensor with no gathers at all (W = widest pool in
the wave, typically ~N_nodes/m), and scatters its finished type-block
back into the master array once per wave.  Without the barriers, ragged
instances drift into different phases and the per-step candidate window
spans most of the node axis.

Exactness: placements are identical to looped ``two_phase`` — same node
purchases in the same order, same ``assign``, same cost.  Three
properties make that hold:

  * the *attempt schedule* (which (task, node-type, purchase?, policy)
    triples are tried, in what order) is precomputed per instance; the
    per-instance attempt order is exactly ``two_phase``'s, and attempts
    on already-placed tasks are skipped at run time.  Filtering a
    stably-sorted superset equals stably sorting the runtime subset
    (both tie-break on ascending task id), so the dynamic order matches.
  * node ids are purchase ranks and purchases only happen in a type's
    own phase, so each type's nodes form one contiguous id block:
    ``first``-fit's "earliest purchased" is the lowest pool-local index,
    and similarity's argmax tie-break (first maximum) matches pool-local
    argmax.
  * the batched numpy scoring computes the *same float64 expressions*
    as ``TypePool.find_fit``: feasibility as ``not any(rem < dem -
    EPS)`` over the span (a bool reduction of the identical
    comparisons, on identical remaining-capacity values — elementwise
    updates never reassociate), and similarity from the same
    ``rem / cap`` values, kept time-innermost with a per-slot sum of
    squares over D beside them, through two-operand einsums: over
    contiguous time, then over D.  Masked terms are exact zeros
    (``x * 0.0``).  The step window changes none of it: a slot outside
    ``[t0, t1)`` lies outside every live task's span, so the
    full-timeline pass masks it out of feasibility, adds an exact zero
    for it to the sums, and subtracts ``dem*0`` from it, no change.  A
    node bought in the step is set full over the whole timeline (and so
    are its cached ``rem / cap`` and sum-of-squares rows) before the
    windowed update.  These sums are reassociated against the loop's
    (over time first, then D) and can differ from it in the last ulp,
    so BOTH engines quantize scores to 9 decimals before the argmax —
    reassociation noise collapses onto identical values and the
    first-max tie-break picks the same node on every path.  Within a
    step every node's row is reduced in the same order, so nodes whose
    remaining capacity is equal over the span get bit-identical scores;
    that is why the sums stay off BLAS, whose batched gemv orders a
    row's sum by the row's position and can split such a tie.
  * skipping cross-fill misses changes no placement.  A cross-fill
    sub-phase buys no node, so capacity only shrinks in it, and a miss
    mutates nothing: the candidates an instance tries before its next
    fit meet the same pool at their own turn in ``two_phase`` as they
    do when tested together.  The test itself is ``min over the span
    of rem >= dem - EPS`` per dimension, the same float comparison as
    ``find_fit``'s ``all(rem >= dem - EPS)``: a minimum is exact, and
    the table's entries are the pool's own values, updated by the same
    ``rem - dem`` subtraction.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import penalty as penalty_mod
from .batch import ProblemBatch, pack_problems
from .placement import FIT_POLICIES
from .solution import EPS, Solution

__all__ = ["place_many", "PLACEMENT_STEPPERS"]

# The lockstep stepper implementations behind ``place_many(placement=)``:
# 'lockstep' is this module's vectorized-numpy engine (one host dispatch
# per placement step); 'compiled' is ``place_step``'s on-device stepper
# (one host dispatch per node-type phase boundary), which falls back to
# 'lockstep' when a wave's pool tensor would be oversized.
PLACEMENT_STEPPERS = ("lockstep", "compiled")


@dataclasses.dataclass
class _Phases:
    """One instance's precomputed phase structure, in two_phase order."""

    type_order: np.ndarray   # (n_phases,) node-type per wave
    own: list                # own-pack task lists, sorted (start, id)
    fill: list               # cross-fill candidate lists, sorted
                             # (h_avg(u|B), id); empty when not filling
    dem_norm: np.ndarray     # (n,) find_fit demand norms (1.0 unused)


def _phases(problem, mapping: np.ndarray, fit: str,
            filling: bool) -> _Phases:
    nt = problem.node_types
    if filling:
        type_order = np.argsort(-nt.capacity_per_cost(), kind="stable")
        h_avg = penalty_mod.relative_demand(problem, "avg")
        rank = np.empty(nt.m, np.int64)
        rank[type_order] = np.arange(nt.m)
        map_rank = rank[mapping]
    else:
        type_order = np.arange(nt.m)

    dn_all = np.ones(problem.n)
    if fit == "similarity":
        # find_fit's demand norm, cached per task (static given the
        # mapping).  The row-wise einsum may differ from find_fit's BLAS
        # np.linalg.norm in the last ulp; the norm is a per-task factor
        # common to every candidate node's score, so exactly-tied nodes
        # (identical remaining capacity) stay exactly tied and the
        # argmax tie-breaking is unaffected.
        dem_n_all = problem.dem / nt.cap[mapping]
        spans = problem.end - problem.start + 1
        dn_all = np.sqrt(
            np.einsum("nd,nd->n", dem_n_all, dem_n_all)) * np.sqrt(spans)

    own, fill = [], []
    for pos, B in enumerate(type_order):
        mine = np.flatnonzero(mapping == int(B))
        own.append(mine[np.lexsort((mine, problem.start[mine]))])
        if filling:
            rest = np.flatnonzero(map_rank > pos)
            fill.append(rest[np.argsort(h_avg[rest, B], kind="stable")])
        else:
            fill.append(np.zeros(0, np.int64))
    return _Phases(type_order=type_order, own=own, fill=fill,
                   dem_norm=dn_all)


def _batch_aux(batch: ProblemBatch, phases: list[_Phases]):
    """Scoring-side arrays shared by the lockstep stepper engines.

    Returns ``(dn, capx, span_all)``: per-task demand norms (B, n) padded
    with 1.0; per-(instance, type) capacity (B, m, D) with +inf on padded
    dims so ``rem / capx`` is bit-exact on real dims and 0 on padded
    ones; and every task's span mask (B, n, T') bool.
    """
    Bn = batch.B
    dn = np.stack([
        np.pad(ph.dem_norm, (0, batch.n - len(ph.dem_norm)),
               constant_values=1.0) for ph in phases])
    dim_mask = np.zeros((Bn, batch.D), bool)
    for b, t in enumerate(batch.problems):
        dim_mask[b, : t.D] = True
    capx = np.where(dim_mask[:, None, :], batch.cap, np.inf)
    t_ids = np.arange(batch.Tp)
    span_all = ((batch.start[:, :, None] <= t_ids)
                & (t_ids <= batch.end[:, :, None]))
    return dn, capx, span_all


def _sum_sq(x: np.ndarray) -> np.ndarray:
    """Sum of squares over the D axis of ``x`` (..., D, t), one slot at a
    time in dimension order: elementwise adds, so a slot's sum depends
    only on its own D values, whatever the shape or layout around it."""
    out = np.square(x[..., 0, :])
    for d in range(1, x.shape[-2]):
        out += np.square(x[..., d, :])
    return out


class _RangeMin:
    """Sparse table of range minima over the time axis of N nodes'
    remaining capacity ``(N, T, D)``, kept current while they lose
    capacity.

    ``m[i, k, t]`` is the per-dimension minimum of ``nodes[i, t : t +
    2**k]`` for ``t <= T - 2**k`` (the rest of a level is never read);
    level 0 is the capacity itself.  A span's minimum is the minimum of
    two entries of one level (``keys`` finds them, ``min`` reads them);
    ``take`` subtracts a demand from one node over a span and
    recomputes, level by level, only the entries whose ranges meet the
    span.  The minimum is exact, so comparing it with a threshold is the
    same comparison as with every slot of the span.  The table lives in
    ``buf`` (grown when too small), so a caller that builds many tables
    can reuse one buffer.
    """

    def __init__(self, nodes: np.ndarray, buf: np.ndarray | None = None):
        N, T, D = nodes.shape
        L = T.bit_length()  # levels 0 .. floor(log2 T)
        size = N * L * T * D
        if buf is None or buf.size < size:
            buf = np.empty(size)
        self.buf = buf
        self.m = m = buf[:size].reshape(N, L, T, D)
        m[:, 0] = nodes
        for k in range(1, L):
            h, n = 1 << (k - 1), T - (1 << k) + 1
            np.minimum(m[:, k - 1, :n], m[:, k - 1, h: h + n],
                       out=m[:, k, :n])
        # floor(log2(span length)), indexed by length - 1; frexp is exact
        self.lg = np.frexp(np.arange(1, T + 1))[1] - 1
        self.slots = m.reshape(-1, D)  # one row per (node, level, t)
        self.T, self.D, self.node_rows = T, D, L * T
        # take's per-level bounds, in flat units of a node's level:
        # (half width, one past the last valid start, width - 1)
        self.bounds = [((1 << (k - 1)) * D, (T - (1 << k) + 1) * D,
                        ((1 << k) - 1) * D) for k in range(1, L)]

    @property
    def nodes(self) -> np.ndarray:
        return self.m[:, 0]

    def keys(self, s, e):
        """Rows, within a node's block of ``slots``, of the two entries
        whose minimum is the minimum over ``[s, e]``."""
        k = self.lg[e - s]
        return k * self.T + s, k * self.T + e - (1 << k) + 1

    def min(self, node, lo, hi) -> np.ndarray:
        """Minimum of node ``node`` over the span whose ``keys`` are
        ``lo``, ``hi``: the three broadcast to a shape S; returns
        S + (D,)."""
        at = node * self.node_rows
        return np.minimum(self.slots.take(at + lo, axis=0),
                          self.slots.take(at + hi, axis=0))

    def take(self, node: int, s: int, e: int, dem) -> None:
        """Subtract ``dem`` (D,) from node ``node`` over ``[s, e]`` and
        bring every level up to date."""
        levels = self.m[node]  # (L, T, D)
        levels[0, s: e + 1] -= dem
        flat = levels.reshape(len(levels), -1)
        lo, hi = s * self.D, (e + 1) * self.D
        prev = flat[0]
        for cur, (h, stop, back) in zip(flat[1:], self.bounds):
            a, z = max(lo - back, 0), min(hi, stop)
            np.minimum(prev[a:z], prev[a + h: z + h], out=cur[a:z])
            prev = cur


class _Engine:
    """Shared lockstep state across the waves of one place_many call."""

    def __init__(self, batch: ProblemBatch, phases: list[_Phases]):
        self.batch = batch
        self.phases = phases
        Bn = batch.B
        self.n_cap = 8
        # the master open-node state: node id == purchase rank
        self.rem = np.zeros((Bn, self.n_cap, batch.Tp, batch.D))
        self.node_type = np.full((Bn, self.n_cap), -1, np.int64)
        self.counts = np.zeros(Bn, np.int64)
        self.placed = np.zeros((Bn, batch.n), bool)
        self.assign = np.full((Bn, batch.n), -1, np.int64)
        self.dn, self.capx_all, self.span_all = _batch_aux(batch, phases)
        self.steps = 0  # lockstep iterations over every sub-phase
        # timeline slots the steps read (their windows) and would have
        # read without windows (T' each)
        self.window_slots = 0
        self.slots = 0
        # cross-fill attempts, and those of them skipped without a step
        self.fill_attempts = 0
        self.fill_skipped = 0
        self._table_buf = None  # _RangeMin storage, reused across waves

    def run_wave(self, k: int, fit: str, filling: bool) -> bool:
        """Own-pack + cross-fill sub-phases of every instance's k-th
        node-type, on a compact tail-growing pool tensor.  Returns
        False when no instance has a k-th phase."""
        wave = np.array([b for b, ph in enumerate(self.phases)
                         if k < len(ph.type_order)], np.int64)
        if len(wave) == 0:
            return False
        tau = np.array([self.phases[b].type_order[k] for b in wave],
                       np.int64)
        lo = self.counts[wave].copy()  # each type-block starts at the
        # current purchase rank: no node of type tau exists yet
        A = len(wave)
        pool = np.zeros((A, 8, self.batch.Tp, self.batch.D))
        w = np.zeros(A, np.int64)
        # drop already-placed tasks per sub-phase up front: a task only
        # becomes placed *between* sub-phases (each list holds distinct
        # tasks), so this is exactly two_phase's dynamic ~placed filter
        # and no skip checks are needed inside the lockstep loop
        own = [self._live(b, self.phases[b].own[k]) for b in wave]
        pool = self._run_sub(wave, tau, pool, w, own,
                             similarity=fit == "similarity")
        if filling:
            fill = [self._live(b, self.phases[b].fill[k]) for b in wave]
            self._run_fill(wave, pool, w, fill)
        # scatter the finished type-block back into the master array
        hi = int((lo + w).max())
        while hi > self.n_cap:
            self.rem = np.concatenate(
                [self.rem, np.zeros_like(self.rem)], axis=1)
            self.node_type = np.concatenate(
                [self.node_type,
                 np.full_like(self.node_type, -1)], axis=1)
            self.n_cap *= 2
        for a, b in enumerate(wave):
            if w[a]:
                self.rem[b, lo[a]: lo[a] + w[a]] = pool[a, : w[a]]
                self.node_type[b, lo[a]: lo[a] + w[a]] = tau[a]
        return True

    def _live(self, b: int, tasks: np.ndarray) -> np.ndarray:
        """Order-preserving ~placed filter (two_phase's phase entry)."""
        return tasks[~self.placed[b, tasks]]

    def _run_sub(self, wave, tau, pool, w, lists, similarity: bool):
        """Lockstep one own-pack sub-phase: one attempt list per wave
        instance, scored against the wave's compact pool each step, a
        node bought on a miss.  Returns the wave's pool tensor, grown if
        a live pool outgrew it.

        Instances leave a sub-phase permanently (their list is
        exhausted); finished pool rows are written back into the wave's
        pool tensor as their instance leaves, and the working set is
        compacted to the live rows once enough have finished, so the
        batched ops stay sized to the instances that still have
        attempts.  All per-task data (demands, spans, norms,
        placement flags) is read straight from the engine's padded
        batch arrays through the live-row -> instance map, so dropping
        rows never copies them.
        """
        batch = self.batch
        keep = np.flatnonzero(np.array([len(x) for x in lists]) > 0)
        lists = [lists[a] for a in keep]
        A = len(keep)
        if A == 0:
            return pool
        L = max(len(x) for x in lists)
        # live-row state; `keep` maps live rows back to wave rows and
        # `bsel_l` to instances
        u_pad = np.zeros((A, L), np.int64)
        lens = np.zeros(A, np.int64)
        for a, x in enumerate(lists):
            u_pad[a, : len(x)] = x
            lens[a] = len(x)
        ptr = np.zeros(A, np.int64)
        arows = np.arange(A)
        wl = w[keep].copy()
        pool_l = pool[keep]
        tau_l = tau[keep]
        bsel_l = wave[keep]
        capx = self.capx_all[bsel_l, tau_l]
        cap_rows = batch.cap[bsel_l, tau_l]      # (A, Dp), padded dims 1
        start_pad = batch.start.astype(np.int64)
        end_pad = batch.end.astype(np.int64)
        # pool_n caches pool / capx time-innermost, (A, Wcap, D, T'), and
        # q its per-slot sum of squares over D, (A, Wcap, T'), so the
        # similarity sums run over contiguous time and skip the big
        # division pass.  The window of each updated row is re-divided
        # after the update, which is bitwise what find_fit computes from
        # the current rem; padded dims divide by +inf to exact zeros
        pool_n = q = None
        if similarity:
            pool_n = np.ascontiguousarray(
                (pool_l / capx[:, None, None, :]).transpose(0, 1, 3, 2))
            q = _sum_sq(pool_n)

        def write_back(rows):
            """Store finished live rows in the wave pool (grown if the
            live pool outgrew it) and the width array."""
            nonlocal pool
            if pool_l.shape[1] > pool.shape[1]:
                grown = np.zeros(
                    (len(wave),) + pool_l.shape[1:], pool.dtype)
                grown[:, : pool.shape[1]] = pool
                pool = grown
            pool[keep[rows]] = pool_l[rows]
            w[keep[rows]] = wl[rows]

        written = np.zeros(A, bool)  # finished rows already stored
        while True:
            # lists are pre-filtered (run_wave's _live), so the pending
            # attempt is always at the pointer — no skip checks needed
            done = ptr >= lens
            fresh = done & ~written
            if fresh.any():
                write_back(np.flatnonzero(fresh))
                written |= fresh
            n_done = int(done.sum())
            if n_done == A:
                break
            if n_done >= max(4, A // 4):  # compact to the live rows
                live = np.flatnonzero(~done)
                keep = keep[live]
                u_pad, lens, ptr = u_pad[live], lens[live], ptr[live]
                wl, pool_l = wl[live], pool_l[live]
                tau_l, bsel_l = tau_l[live], bsel_l[live]
                capx, cap_rows = capx[live], cap_rows[live]
                if pool_n is not None:
                    pool_n, q = pool_n[live], q[live]
                A = len(live)
                arows = np.arange(A)
                done = np.zeros(A, bool)
                written = np.zeros(A, bool)
            if wl.max() == pool_l.shape[1]:  # grow the pool tail
                pool_l = np.concatenate(
                    [pool_l, np.zeros_like(pool_l)], axis=1)
                if pool_n is not None:
                    pool_n = np.concatenate(
                        [pool_n, np.zeros_like(pool_n)], axis=1)
                    q = np.concatenate([q, np.zeros_like(q)], axis=1)

            alive = ~done
            u_cur = u_pad[arows, np.minimum(ptr, lens - 1)]
            dem = batch.dem[bsel_l, u_cur]               # (A, Dp)
            s_cur = start_pad[bsel_l, u_cur]
            e_cur = end_pad[bsel_l, u_cur]
            # the step's window: the live rows' span union [t0, t1).
            # Every slot outside it lies outside every live task's span,
            # so the step reads and writes only the window
            t0 = int(s_cur[alive].min())
            t1 = int(e_cur[alive].max()) + 1
            self.window_slots += t1 - t0
            self.slots += batch.Tp
            span = self.span_all[bsel_l, u_cur, t0:t1]  # (A, t1 - t0)
            W = max(int(wl.max()), 1)
            node_ok = (np.arange(W)[None, :] < wl[:, None]) \
                & alive[:, None]

            # not any(rem < dem - EPS) over the span == find_fit's
            # all(rem >= dem - EPS): the same comparisons on each node's
            # contiguous ((t1-t0)*D)-flattened window (numpy's iterator
            # is ~10x faster there than on 4-D broadcasts with a tiny
            # trailing axis); the slots the window drops are masked out
            # of every live row's span anyway
            pool3 = pool_l[:, :W, t0:t1].reshape(A, W, -1)  # a view
            thr_flat = np.tile(dem - EPS, (1, t1 - t0))
            span_flat = np.repeat(span, batch.D, axis=1)
            viol = ((pool3 < thr_flat[:, None, :])
                    & span_flat[:, None, :]).any(axis=2)
            feas = ~viol & node_ok
            if similarity:
                # two-operand einsums over contiguous time, then over D:
                # every row is reduced in the same order, so nodes equal
                # over the span score bit-identically (a batched BLAS
                # gemv orders each row's sum by its position, and can
                # split such ties).  The window's dropped slots carry
                # only exact-zero terms, so the sums are unchanged
                span_f = span.astype(np.float64)
                per_d = np.einsum("bndt,bt->bnd",
                                  pool_n[:, :W, :, t0:t1], span_f)
                dot = np.einsum("bnd,bd->bn", per_d, dem / capx)
                norm2 = np.einsum("bnt,bt->bn", q[:, :W, t0:t1], span_f)
                dem_norm = self.dn[bsel_l, u_cur]
                score = dot / (dem_norm[:, None] * np.sqrt(norm2)
                               + 1e-30)
            has = feas.any(axis=1)
            if similarity:
                # find_fit's quantized tie-break: digits beyond the 9th
                # are float reassociation noise across scoring layouts
                choice = np.where(feas, np.round(score, 9),
                                  -np.inf).argmax(axis=1)
            else:
                choice = feas.argmax(axis=1)  # lowest id == earliest

            place_a = np.flatnonzero(has)     # has implies alive
            j_all = choice[place_a]
            buy_a = np.flatnonzero(~has & alive)
            if len(buy_a):
                bad = (dem[buy_a] > cap_rows[buy_a] + EPS).any(axis=1)
                if bad.any():
                    a0 = int(buy_a[int(np.flatnonzero(bad)[0])])
                    raise RuntimeError(
                        f"mapping assigned task {int(u_cur[a0])} "
                        f"to node-type {int(tau_l[a0])} it cannot fit")
                j_new = wl[buy_a]
                # a new node is full over the whole timeline, and so are
                # its cached pool / capx and q rows; the update below
                # then touches only the window of each
                pool_l[buy_a, j_new] = cap_rows[buy_a][:, None]
                if pool_n is not None:
                    full_n = (cap_rows[buy_a] / capx[buy_a])[:, :, None]
                    pool_n[buy_a, j_new] = full_n
                    q[buy_a, j_new] = _sum_sq(full_n)
                wl[buy_a] += 1
                self.counts[bsel_l[buy_a]] += 1
                place_a = np.concatenate([place_a, buy_a])
                j_all = np.concatenate([j_all, j_new])
            if len(place_a):
                sub = (dem[place_a][:, None, :]
                       * span[place_a].astype(np.float64)[:, :, None])
                # dem*1 / dem*0 inside the window: exact; outside it the
                # task's span is empty, so those slots stay as they were
                pool_l[place_a, j_all, t0:t1] -= sub
                if pool_n is not None:
                    win_n = (pool_l[place_a, j_all, t0:t1]
                             / capx[place_a][:, None, :]).transpose(0, 2, 1)
                    pool_n[place_a, j_all, :, t0:t1] = win_n
                    q[place_a, j_all, t0:t1] = _sum_sq(win_n)
                u_sel = u_cur[place_a]
                b_sel = bsel_l[place_a]
                # global node id = block start + pool-local index
                self.assign[b_sel, u_sel] = \
                    self.counts[b_sel] - wl[place_a] + j_all
                self.placed[b_sel, u_sel] = True
            ptr += alive
            self.steps += 1
        return pool

    def _run_fill(self, wave, pool, w, lists):
        """Lockstep one cross-fill sub-phase (first fit, no purchase),
        in place on the wave's pool tensor.

        No node is bought here, so a row's pool only loses capacity, and
        only where the row places a task.  Each step takes every live
        row's pending candidates in list order, tests them against the
        row's current nodes through a range-minimum table kept current
        after every placement (``_RangeMin``), and places the first that
        fits on its lowest fitting node.  The candidates before it fit
        no node now, and fitted none at their own turn in ``two_phase``
        either, since a miss changes nothing: they are skipped without a
        step.  So every step places one task in each row that still has
        one to place.
        """
        batch = self.batch
        keep = np.flatnonzero(
            (w > 0) & (np.array([len(x) for x in lists]) > 0))
        if len(keep) == 0:
            return
        A, BLOCK = len(keep), 1024  # the largest block a row tests
        lens = np.array([len(lists[a]) for a in keep])
        b_l, wl = wave[keep], w[keep]
        # the table holds each row's wl nodes in turn from first[row],
        # then one node with no capacity anywhere, which fits nothing
        # and pads every row's nodes to W
        first = np.cumsum(wl) - wl
        row_of = np.repeat(keep, wl)
        j_of = np.arange(len(row_of)) - np.repeat(first, wl)
        nodes = np.concatenate(
            [pool[row_of, j_of], np.full((1,) + pool.shape[2:], -np.inf)])
        table = _RangeMin(nodes, self._table_buf)
        self._table_buf = table.buf
        W = int(wl.max())
        node_at = np.where(np.arange(W) < wl[:, None],
                           first[:, None] + np.arange(W), len(row_of))
        # per-candidate data, padded past each list's end with BLOCK
        # candidates whose threshold no capacity meets
        u_pad = np.zeros((A, int(lens.max()) + BLOCK), np.int64)
        for r, a in enumerate(keep):
            u_pad[r, : lens[r]] = lists[a]
        bb = b_l[:, None]
        s_pad = batch.start[bb, u_pad].astype(np.int64)
        e_pad = batch.end[bb, u_pad].astype(np.int64)
        real = np.arange(u_pad.shape[1]) < lens[:, None]
        thr = np.where(real[..., None], batch.dem[bb, u_pad] - EPS, np.inf)
        lo_pad, hi_pad = table.keys(s_pad, e_pad)
        ptr = np.zeros(A, np.int64)
        hits = 0
        while True:
            rows = np.flatnonzero(ptr < lens)
            if len(rows) == 0:
                break
            # each live row's first candidate that fits, from its
            # pointer on, in blocks that double while a row finds none
            got_r, got_j, block = [], [], 8
            while len(rows):
                rr = rows[:, None]
                idx = ptr[rr] + np.arange(block)               # (P, K)
                # all(rem >= dem - EPS) over the span, as find_fit
                # compares it, through the span's exact minimum
                lowest = table.min(node_at[rr], lo_pad[rr, idx][..., None],
                                   hi_pad[rr, idx][..., None])
                fit = (lowest >= thr[rr, idx][:, :, None]).all(axis=3)
                any_node = fit.any(axis=2)                     # (P, K)
                has = any_node.any(axis=1)
                hit = any_node.argmax(axis=1)
                ptr[rows] = np.where(has, ptr[rows] + hit,
                                     np.minimum(ptr[rows] + block,
                                                lens[rows]))
                got_r.append(rows[has])
                got_j.append(fit[has, hit[has]].argmax(axis=1))
                rows = rows[~has]
                rows = rows[ptr[rows] < lens[rows]]
                block = min(2 * block, BLOCK)
            r = np.concatenate(got_r)
            if len(r) == 0:  # every pending candidate missed
                continue
            j = np.concatenate(got_j)
            at = ptr[r]
            s, e = s_pad[r, at], e_pad[r, at]
            u, b_sel = u_pad[r, at], b_l[r]
            for args in zip(node_at[r, j].tolist(), s.tolist(),
                            e.tolist(), batch.dem[b_sel, u]):
                table.take(*args)
            # global node id = block start + pool-local index
            self.assign[b_sel, u] = self.counts[b_sel] - wl[r] + j
            self.placed[b_sel, u] = True
            ptr[r] += 1
            hits += len(r)
            self.steps += 1
            self.window_slots += int(e.max()) + 1 - int(s.min())
            self.slots += batch.Tp
        pool[row_of, j_of] = table.nodes[:-1]
        self.fill_attempts += int(lens.sum())
        self.fill_skipped += int(lens.sum()) - hits


def place_many(problems, mappings, fit: str = "first",
               filling: bool = False, meta: dict | None = None,
               placement: str = "lockstep",
               telemetry: dict | None = None) -> list[Solution]:
    """Batched ``two_phase`` over B instances; placements are identical.

    ``problems`` is a sequence of ``Problem``s or an already-packed
    ``ProblemBatch`` (instances are timeline-trimmed either way, like
    every placement entry point); ``mappings[b]`` is instance b's
    task -> node-type mapping in trimmed coordinates.  Returns one
    ``Solution`` per instance, equal (node purchases, ``assign``, cost)
    to ``two_phase(batch.problems[b], mappings[b], fit, filling)``.

    ``placement`` picks the lockstep stepper: ``'lockstep'`` (default)
    is this module's vectorized-numpy engine, one host dispatch per
    placement step; ``'compiled'`` runs each node-type phase as one
    on-device ``lax.scan`` (``repro.core.place_step``) so the host
    dispatches only at phase boundaries — placements are bit-identical,
    and oversized pools fall back to the numpy engine automatically.
    ``telemetry``, when a dict, is filled in place with the stepper
    actually used, wave count, per-wave seconds, the numpy engine's
    lockstep step count, the timeline slots its steps read
    (``window_slots``) against T' a step (``slots``), its cross-fill
    attempts (``fill_attempts``) and those skipped without a step
    (``fill_skipped``), and (compiled) device-dispatch counts.

    >>> import numpy as np
    >>> from repro.core import place_many, two_phase
    >>> from repro.workload import SyntheticSpec, synthetic_instance
    >>> ps = [synthetic_instance(SyntheticSpec(n=12, m=2, D=2, T=6,
    ...                                        seed=s)) for s in (0, 1)]
    >>> maps = [np.zeros(12, np.int64), np.ones(12, np.int64)]
    >>> sols = place_many(ps, maps, fit="similarity")
    >>> want = two_phase(ps[0], maps[0], fit="similarity")
    >>> bool(np.array_equal(sols[0].assign, want.assign))
    True
    """
    if fit not in FIT_POLICIES:
        raise ValueError(f"fit must be one of {FIT_POLICIES}")
    if placement not in PLACEMENT_STEPPERS:
        raise ValueError(
            f"placement must be one of {PLACEMENT_STEPPERS}, "
            f"got {placement!r}")
    batch = problems if isinstance(problems, ProblemBatch) \
        else pack_problems(problems)
    if len(mappings) != batch.B:
        raise ValueError("need exactly one mapping per instance")
    phases = [_phases(t, np.asarray(mp, np.int64), fit, filling)
              for t, mp in zip(batch.problems, mappings)]
    if placement == "compiled":
        from . import place_step

        sols = place_step.run_compiled(batch, phases, fit=fit,
                                       filling=filling, meta=meta,
                                       telemetry=telemetry)
        if sols is not None:
            return sols
        # oversized pool: place_step declined (and recorded why in
        # telemetry); fall through to the numpy lockstep engine
    eng = _Engine(batch, phases)
    wave_s = []
    k = 0
    while True:
        t0 = time.perf_counter()
        if not eng.run_wave(k, fit, filling):
            break
        wave_s.append(time.perf_counter() - t0)
        k += 1
    if telemetry is not None:
        telemetry.setdefault("engine", "lockstep")
        telemetry["waves"] = len(wave_s)
        telemetry["wave_s"] = wave_s
        telemetry["steps"] = eng.steps
        telemetry["window_slots"] = eng.window_slots
        telemetry["slots"] = eng.slots
        telemetry["fill_attempts"] = eng.fill_attempts
        telemetry["fill_skipped"] = eng.fill_skipped

    out = []
    for b, t in enumerate(batch.problems):
        assert eng.placed[b, : t.n].all(), \
            "place_many must place every task"
        out.append(Solution(
            node_type=eng.node_type[b, : eng.counts[b]].copy(),
            assign=eng.assign[b, : t.n].copy(),
            meta=dict(meta or {}, fit=fit, filling=filling),
        ))
    return out
