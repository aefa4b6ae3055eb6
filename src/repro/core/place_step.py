"""Compiled on-device lockstep placement stepper.

``place_batch.place_many`` already advances all B instances' greedy
placement (paper §III first/similarity fit, §V-D cross-fill) in
lockstep, but it re-enters Python between every placement step: one
step costs O(1) *numpy dispatches*, which ROADMAP lists as the
remaining bottleneck on small/medium batches.  This module makes the
same move for the placement phase that PR 3's PDLP-style engine made
for the LP phase — the inner loop compiles end-to-end, and the host
dispatches at *node-type phase boundaries* instead of once per step.

Execution model.  Two plans share one jitted sub-phase (``lax.scan``
over the attempt cursor — the numpy engine advances every live lane's
pointer each step, so the lockstep loop is exactly a scan over attempt
index with lanes masked by their list lengths):

  * **type-parallel** (``filling=False``): every (instance, node-type)
    phase is independent — types partition the tasks and pools never
    interact — so ALL phases run concurrently as scan lanes and the
    host dispatches ONCE for the whole placement.  Global node ids are
    reconstructed afterwards from the per-type node counts
    (``two_phase`` numbers each type's purchases as one contiguous
    block in type order).
  * **wave-sequential** (``filling=True``): cross-fill makes wave k+1's
    task lists depend on wave k's placements, so waves run in the numpy
    engine's order — one own-pack and one cross-fill dispatch per
    node-type phase boundary.

Each scan step scores the pending task of every lane against all its
candidate nodes in one batched feasibility + similarity pass
(``fit_scores_step``) and picks nodes with the engines' shared argmax
tie-break; purchases and capacity updates are masked tensor updates
inside the scan.  The scan is split into static *chunks* replicating
the numpy engine's work-saving slices (see ``_plan_chunks``): the live
time window and node prefix of each chunk are statically known, so the
per-step tensors stay close to the work the numpy engine touches.

Exactness.  Placements are bit-identical to ``two_phase`` and the
numpy lockstep engine:

  * the whole sub-phase is traced under ``jax.enable_x64(True)``
    so every elementwise expression (feasibility comparisons against
    ``dem - EPS``, capacity subtractions ``rem - dem`` over the span,
    the ``rem / capx`` normalizations) is the same float64 operation on
    the same values — elementwise ops never reassociate, so they match
    the numpy engines bit for bit;
  * similarity reduction sums (the dot/norm reductions) may differ from
    numpy's in the last ulp, exactly as the numpy engine's differ from
    ``find_fit``'s — all engines therefore quantize scores to 9
    decimals before the argmax.  The quantum is passed as a *runtime*
    operand so XLA cannot fold the division into a multiply-by-
    reciprocal (which is not bit-equal to ``np.round(score, 9)``);
  * ``jnp.argmax`` and ``np.argmax`` both take the first maximum, and
    node ids are purchase ranks in both engines, so tie-breaks agree.

Performance envelope.  The stepper eliminates the per-step host
round-trip: total dispatches drop from O(placement steps) to 1
(type-parallel) or O(phases) (wave-sequential).  On CPU hosts the win
is bounded by XLA's own elementwise kernels (~2x slower per element
than numpy's on small f64 tensors), so compiled ~matches the numpy
engine there and is pinned >=2x against the per-instance loop; on TPU
the same trace lowers to fused Mosaic kernels without the handicap.
A call whose pool tensor would exceed ``MAX_POOL_CELLS`` falls back to
the numpy lockstep engine (``run_compiled`` returns None and records
the reason in the telemetry dict).
"""

from __future__ import annotations

import functools
import time

import numpy as np

from .solution import EPS, Solution

__all__ = ["run_compiled", "fit_scores_step", "MAX_POOL_CELLS"]

# Fall back to the numpy lockstep engine when a wave's padded pool
# tensor (B, N_cap, T', D) would exceed this many float64 elements: the
# scan materializes a few same-shaped temporaries per step, and past
# this size the compiled stepper's dispatch savings no longer pay for
# the padded arithmetic.
MAX_POOL_CELLS = 1 << 24

_QUANTUM = 1e9  # the engines' shared 9-decimal tie-break quantization


def _pow2(x: int, floor: int = 8) -> int:
    return max(floor, 1 << (int(x) - 1).bit_length()) if x else floor


def _pad4(x: int) -> int:
    return max(4, (int(x) + 3) & ~3)


def fit_scores_step(rem, dem, span, capx, dem_norm, scored: bool = False,
                    quantum=None, eps: float = EPS):
    """Feasibility and similarity of every lane's pending task against
    each of its open nodes: the scoring pass of one compiled step.

    A pure-jnp function meant to be *traced* — it takes and returns
    ``jnp`` arrays, does no host conversion or padding, and is safe
    inside ``lax.while_loop`` / ``lax.scan`` bodies (the sub-phase scan
    calls it once per placement step).

    All slot-carrying operands arrive flattened to one contiguous
    reduction axis K = T*D (slot k = t*D + d), the same layout trick
    the numpy engine uses for its feasibility scan: the similarity dot
    then lowers to a batched mat-vec over a contiguous axis instead of
    a 4-D einsum with a tiny trailing dimension, which CPU/TPU backends
    vectorize an order of magnitude better.

    rem:      (B, N, K) open-node remaining capacity.
    dem:      (B, K) the pending task's demand, tiled over timeslots.
    span:     (B, K) bool, True inside each instance's task span.
    capx:     (B, K) node-type capacity tiled over slots, +inf on
              padded dims, so ``rem / capx`` is exact on real dims and
              0 on padded ones.
    dem_norm: (B,) the precomputed per-task demand norm of the
              similarity denominator.
    quantum:  similarity tie-break quantization as a *runtime* scalar
              (1e9 for the engines' shared 9-decimal rounding).  Passing
              it as an operand keeps XLA from folding the division into
              a multiply-by-reciprocal, which is not bit-equal to the
              host engines' ``np.round(score, 9)``.

    Returns ``(feas, score)``, both (B, N): feasibility is the same
    elementwise float comparison the host engines evaluate
    (``not any(rem < dem - eps)`` over the span), and ``score`` is the
    quantized cosine similarity (zeros when ``scored`` is False).  In a
    float64 trace (``jax.enable_x64(True)``) every elementwise
    term is bit-identical to the numpy engines; the reduction sums may
    differ in the last ulp, which the shared quantization collapses.
    """
    import jax
    import jax.numpy as jnp

    thr = dem - eps
    viol = ((rem < thr[:, None, :]) & span[:, None, :]).any(axis=2)
    feas = ~viol
    if not scored:
        return feas, jnp.zeros(feas.shape, rem.dtype)
    span_f = span.astype(rem.dtype)
    rem_n = rem / capx[:, None, :]
    q = (dem / capx) * span_f                 # exact: dem_n * {0, 1}
    dot = jnp.einsum("bnk,bk->bn", rem_n, q,  # batched mat-vec
                     precision=jax.lax.Precision.HIGHEST)
    rm = rem_n * span_f[:, None, :]
    norm2 = (rm * rm).sum(axis=2)
    score = dot / (dem_norm[:, None] * jnp.sqrt(norm2) + 1e-30)
    if quantum is not None:
        score = jnp.rint(score * quantum) / quantum
    return feas, score


def _make_sub_phase():
    """Build the jitted sub-phase scan (deferred so importing this
    module never imports jax eagerly on the fallback-only path)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit,
                       static_argnames=("purchase", "similarity",
                                        "chunks"),
                       donate_argnums=(0,))
    def sub_phase(pool, w, lens, dem_seq, s_seq, e_seq, dn_seq, capx,
                  cap_rows, quantum, purchase: bool, similarity: bool,
                  chunks: tuple):
        """One lockstep sub-phase as a sequence of compiled scan chunks.

        pool:     (B, N_cap, K) f64 open-node remaining capacity with
                  the (T', D) slot axes flattened to K = T'*D (slot
                  k = t*D + d), the mat-vec-friendly scoring layout.
        w:        (B,) i32 open-node counts (pool widths).
        lens:     (B,) i32 attempt-list lengths (0 = lane idle).
        dem_seq:  (L, B, D) f64 per-attempt demands.
        s_seq:    (L, B) i32 per-attempt span starts (inclusive).
        e_seq:    (L, B) i32 per-attempt span ends (inclusive).
        dn_seq:   (L, B) f64 per-attempt demand norms.
        capx:     (B, D) f64 capacity, +inf on padded dims.
        cap_rows: (B, D) f64 capacity as opened-node rows (padded 1.0).
        quantum:  () f64 runtime tie-break quantum.
        chunks:   static ``(l0, l1, n_hi, t0, t1)`` tuples from
                  ``_plan_chunks``: attempt steps [l0, l1) only ever
                  see pool rows < n_hi and timeslots [t0, t1).

        The chunk plan replicates the numpy engine's two work-saving
        slices with *static* shapes: attempts are start-sorted, so a
        chunk's spans land in a narrow statically-known time window,
        and a lane's width grows by at most one node per step, so a
        chunk's live node prefix is statically bounded too.  Each chunk
        scans a static slice ``pool[:, :n_hi, t0*D:t1*D]`` — everything
        outside is provably untouched (spans inside the window, rows
        past ``n_hi`` masked infeasible) — and writes it back.  The
        pool arrives with EVERY row initialized to full capacity, so
        opening a node is just the width increment (a zero-initialized
        pool would leave an opened row blank outside the opening
        chunk's window); demand subtraction is a masked elementwise
        update rather than a scatter, which CPU backends lower to
        scalar loops as costly as a full pass.

        Returns (pool, w, bad, j_rec): ``bad`` is each lane's first
        attempt index whose task cannot fit the node-type (-1 = none),
        ``j_rec`` (L, B) the pool-local node index each attempt placed
        into (-1 = no placement).
        """
        B, n_cap, K = pool.shape
        D = dem_seq.shape[2]

        def flat_d(x_d, t_lo, t_hi):
            """(B, D) per-dim operand -> (B, Kw) window tiling."""
            return jnp.broadcast_to(
                x_d[:, None, :], (B, t_hi - t_lo, D)
            ).reshape(B, (t_hi - t_lo) * D)

        bad = jnp.full(w.shape, -1, jnp.int32)
        j_parts = []
        for (l0, l1, n_hi, t0, t1) in chunks:
            view = pool[:, :n_hi, t0 * D: t1 * D]
            capx_k = flat_d(capx, t0, t1)
            node_ids = jnp.arange(n_hi, dtype=jnp.int32)[None, :]
            t_ids = jnp.arange(t0, t1, dtype=jnp.int32)[None, :]

            def body(carry, xs, capx_k=capx_k, node_ids=node_ids,
                     t_ids=t_ids, t01=(t0, t1)):
                view, w, bad = carry
                dem, s, e, dn, step = xs
                active = step < lens                     # (B,)
                dem_k = flat_d(dem, *t01)
                span = (s[:, None] <= t_ids) & (t_ids <= e[:, None])
                span_k = jnp.broadcast_to(
                    span[:, :, None], (B, t01[1] - t01[0], D)
                ).reshape(B, (t01[1] - t01[0]) * D)
                feas, score = fit_scores_step(
                    view, dem_k, span_k, capx_k, dn,
                    scored=similarity, quantum=quantum, eps=EPS)
                feas = feas & (node_ids < w[:, None]) & active[:, None]
                has = feas.any(axis=1)
                if similarity:
                    choice = jnp.where(feas, score, -jnp.inf) \
                        .argmax(axis=1).astype(jnp.int32)
                else:
                    choice = jnp.argmax(feas, axis=1).astype(jnp.int32)
                if purchase:
                    buy = (~has) & active
                    bad_now = buy & (dem > cap_rows + EPS).any(axis=1)
                    bad = jnp.where(bad_now & (bad < 0), step, bad)
                    # the pool arrives cap-initialized on EVERY row
                    # (unopened rows are never read: node_ok masks
                    # them), so opening a node is just the width bump
                    j = jnp.where(has, choice, w)
                    placed = active
                    w = w + buy.astype(jnp.int32)
                else:
                    j = choice
                    placed = has
                # subtract the demand over the span from the chosen
                # row: a masked elementwise update (vectorized), not a
                # scatter — CPU/TPU backends lower scatters to scalar
                # loops that cost as much as a full pass here
                hit = placed[:, None] & (node_ids == j[:, None])
                view = view - jnp.where(
                    hit[:, :, None] & span_k[:, None, :],
                    dem_k[:, None, :], 0.0)
                j_rec = jnp.where(placed, j, -1)
                return (view, w, bad), j_rec

            steps = jnp.arange(l0, l1, dtype=jnp.int32)
            (view, w, bad), j_part = jax.lax.scan(
                body, (view, w, bad),
                (dem_seq[l0:l1], s_seq[l0:l1], e_seq[l0:l1],
                 dn_seq[l0:l1], steps))
            pool = pool.at[:, :n_hi, t0 * D: t1 * D].set(view)
            j_parts.append(j_part)
        if not j_parts:
            j_rec = jnp.full((0, B), -1, jnp.int32)
        else:
            j_rec = jnp.concatenate(j_parts, axis=0)
        return pool, w, bad, j_rec

    return sub_phase


_SUB_PHASE = None


def _sub_phase_fn():
    global _SUB_PHASE
    if _SUB_PHASE is None:
        _SUB_PHASE = _make_sub_phase()
    return _SUB_PHASE


def _pad_lists(lists, L: int):
    """(B, L) attempt-index padding + (B,) i32 lengths."""
    B = len(lists)
    u_pad = np.zeros((B, L), np.int64)
    lens = np.zeros(B, np.int32)
    for b, x in enumerate(lists):
        u_pad[b, : len(x)] = x
        lens[b] = len(x)
    return u_pad, lens


# Scan-chunk length of the compiled stepper: every CHUNK steps the
# node-prefix and time-window slices are re-tightened (smaller = less
# padded arithmetic, more unrolled scans to compile).
CHUNK = 8


def _plan_chunks(lens, s_seq, e_seq, n_cap: int, Tp: int,
                 w0_max: int, grows: bool, chunk: int = CHUNK) -> tuple:
    """Static per-chunk slice bounds for ``sub_phase``.

    Chunk c covers attempt steps [l0, l1).  Because attempt lists are
    start-sorted and a lane opens at most one node per step, the steps
    of one chunk provably touch only pool rows < ``w0_max + l1`` and
    the timeslots spanned by the chunk's live attempts; both bounds are
    known on the host, so each chunk scans a *static* slice.  Windows
    quantize to multiples of 4 slots and prefixes to powers of two so
    near-identical plans share compiled programs.
    """
    lens = np.asarray(lens)
    Lr = int(lens.max()) if len(lens) else 0
    steps = np.arange(Lr)[:, None]
    chunks = []
    for l0 in range(0, Lr, chunk):
        l1 = min(l0 + chunk, Lr)
        act = steps[l0:l1] < lens[None, :]
        if not act.any():
            break
        t0 = int(s_seq[l0:l1][act].min()) // 4 * 4
        t1 = min(Tp, (int(e_seq[l0:l1][act].max()) + 4) // 4 * 4)
        n_hi = min(n_cap, _pow2(w0_max + (l1 if grows else 0), floor=4))
        chunks.append((l0, l1, n_hi, t0, t1))
    return tuple(chunks)


class _Driver:
    """Shared host state of one ``run_compiled`` call."""

    def __init__(self, batch, phases, fit: str):
        from .place_batch import _batch_aux

        self.batch = batch
        self.phases = phases
        self.B, self.n = batch.B, batch.n
        self.Tpp = _pad4(batch.Tp)  # slot padding is cheap; nodes not
        self.K = self.Tpp * batch.D
        self.dn, self.capx_all, _ = _batch_aux(batch, phases)
        self.similarity = fit == "similarity"
        self.quantum = np.float64(_QUANTUM)
        self.counts = np.zeros(self.B, np.int64)
        self.placed = np.zeros((self.B, self.n), bool)
        self.assign = np.full((self.B, self.n), -1, np.int64)
        self.sub_phase = _sub_phase_fn()
        self.dispatches = 0

    def gather(self, lists, L, b_of, tau_of):
        """Per-attempt scan inputs for one sub-phase: lane a is
        instance ``b_of[a]`` packing node-type ``tau_of[a]``."""
        batch = self.batch
        u_pad, lens = _pad_lists(lists, L)
        lidx = b_of[:, None]
        dem_seq = np.ascontiguousarray(
            batch.dem[lidx, u_pad].transpose(1, 0, 2))
        s_seq = np.ascontiguousarray(
            batch.start[lidx, u_pad].T.astype(np.int32))
        e_seq = np.ascontiguousarray(
            batch.end[lidx, u_pad].T.astype(np.int32))
        dn_seq = np.ascontiguousarray(self.dn[lidx, u_pad].T)
        capx = self.capx_all[b_of, tau_of]
        cap_rows = batch.cap[b_of, tau_of]
        return u_pad, lens, dem_seq, s_seq, e_seq, dn_seq, capx, \
            cap_rows

    def cap_pool(self, cap_rows, n_cap: int):
        """Cap-initialized (A, n_cap, K) pool: every row starts at full
        capacity, so opening a node inside the scan is just the width
        increment (unopened rows are never read or written)."""
        cap_k = np.tile(cap_rows, (1, self.Tpp))         # (A, K)
        return np.ascontiguousarray(np.broadcast_to(
            cap_k[:, None, :], (len(cap_rows), n_cap, self.K)))

    def dispatch(self, pool, w, gathered, purchase: bool,
                 similarity: bool, w0_max: int):
        (_, lens, dem_seq, s_seq, e_seq, dn_seq, capx,
         cap_rows) = gathered
        chunks = _plan_chunks(lens, s_seq, e_seq, pool.shape[1],
                              self.Tpp, w0_max, grows=purchase)
        out = self.sub_phase(pool, w, lens, dem_seq, s_seq, e_seq,
                             dn_seq, capx, cap_rows, self.quantum,
                             purchase=purchase, similarity=similarity,
                             chunks=chunks)
        self.dispatches += 1
        return out

    def apply(self, j_rec, u_pad, b_of, base):
        """Fold one sub-phase's (L, Ap) node choices into assign:
        lane a's attempt l placed task ``u_pad[a, l]`` into global node
        ``base[a] + j_rec[l, a]``."""
        A = len(b_of)
        j_al = np.asarray(j_rec).T[:A]        # (A, L)
        a_hit, l_hit = np.nonzero(j_al >= 0)
        u_hit = u_pad[a_hit, l_hit]
        b_hit = b_of[a_hit]
        self.assign[b_hit, u_hit] = base[a_hit] + j_al[a_hit, l_hit]
        self.placed[b_hit, u_hit] = True

    def raise_bad(self, bad, u_pad, b_of, tau_of, phase_of=None):
        """Raise the sequential engines' infeasible-mapping error.

        ``phase_of`` orders lanes by type-phase position (type-parallel
        runs every phase at once, but the sequential engines hit the
        earliest (phase, step, lane) first, so the reported task must
        match theirs)."""
        bad = np.asarray(bad)[: len(b_of)]
        hit = np.flatnonzero(bad >= 0)
        if len(hit):
            if phase_of is None:
                a = int(hit[np.argmin(bad[hit])])
            else:
                a = int(min(hit, key=lambda i: (phase_of[i], bad[i], i)))
            u = int(u_pad[a, bad[a]])
            raise RuntimeError(
                f"mapping assigned task {u} to node-type "
                f"{int(tau_of[a])} it cannot fit")

    def solutions(self, node_type, meta, fit, filling):
        out = []
        for b, t in enumerate(self.batch.problems):
            assert self.placed[b, : t.n].all(), \
                "compiled stepper must place every task"
            out.append(Solution(
                node_type=node_type[b, : self.counts[b]].copy(),
                assign=self.assign[b, : t.n].copy(),
                meta=dict(meta or {}, fit=fit, filling=filling),
            ))
        return out


def _run_type_parallel(drv: _Driver, max_pool_cells: int):
    """filling=False: every (instance, node-type) phase is independent
    (types partition the tasks and pools never interact), so ALL waves
    run concurrently as one scan over (instance, type) lanes — a single
    device dispatch for the entire placement.  Global node ids are
    reconstructed afterwards: ``two_phase`` numbers each type's
    purchases as one contiguous block in type order, so the block
    offsets are the exclusive prefix sums of the per-type node counts.
    Returns None when the lane-pool tensor would be oversized."""
    phases, B = drv.phases, drv.B
    lanes = [(b, k) for b in range(B)
             for k in range(len(phases[b].type_order))
             if len(phases[b].own[k])]
    if not lanes:
        return [], np.full((B, 1), -1, np.int64)
    lists = [phases[b].own[k] for b, k in lanes]
    b_of = np.array([b for b, _ in lanes], np.int64)
    k_of = np.array([k for _, k in lanes], np.int64)
    tau_of = np.array([int(phases[b].type_order[k]) for b, k in lanes],
                      np.int64)
    L = max(len(x) for x in lists)
    if len(lanes) * L * drv.K > max_pool_cells:
        return None
    gathered = drv.gather(lists, L, b_of, tau_of)
    u_pad = gathered[0]
    pool0 = drv.cap_pool(gathered[-1], L)
    w0 = np.zeros(len(lanes), np.int32)
    _, w, bad, j_rec = drv.dispatch(pool0, w0, gathered, purchase=True,
                                    similarity=drv.similarity,
                                    w0_max=0)
    drv.raise_bad(bad, u_pad, b_of, tau_of, phase_of=k_of)
    w_np = np.asarray(w)[: len(lanes)].astype(np.int64)
    # per-instance node blocks in type order -> purchase-rank offsets
    m = drv.batch.m
    per_type = np.zeros((B, m), np.int64)
    per_type[b_of, tau_of] = w_np
    offsets = np.cumsum(per_type, axis=1) - per_type  # exclusive
    drv.counts = per_type.sum(axis=1)
    drv.apply(j_rec, u_pad, b_of, offsets[b_of, tau_of])
    node_type = np.full((B, max(1, int(drv.counts.max()))), -1,
                        np.int64)
    for (b, tau, cnt) in zip(b_of, tau_of, w_np):
        if cnt:
            off = offsets[b, tau]
            node_type[b, off: off + cnt] = tau
    return [1.0], node_type  # one fused "wave"


def _run_waves(drv: _Driver, filling: bool):
    """filling=True: wave-synchronized phases (the numpy engine's order)
    — cross-fill makes wave k+1's task lists depend on wave k's
    placements, so waves dispatch sequentially: one own-pack and one
    cross-fill scan per node-type phase boundary."""
    phases, B = drv.phases, drv.B
    node_cap = 8
    node_type = np.full((B, node_cap), -1, np.int64)
    wave_s: list[float] = []
    k = 0
    while True:
        wave = {b for b, ph in enumerate(phases)
                if k < len(ph.type_order)}
        if not wave:
            break
        t0 = time.perf_counter()
        tau = np.zeros(B, np.int64)
        for b in wave:
            tau[b] = phases[b].type_order[k]
        own = [phases[b].own[k][~drv.placed[b, phases[b].own[k]]]
               if b in wave else np.zeros(0, np.int64)
               for b in range(B)]
        lo = drv.counts.copy()
        b_all = np.arange(B)
        pool = w = None
        if any(len(x) for x in own):
            L = max(len(x) for x in own)
            gathered = drv.gather(own, L, b_all, tau)
            u_pad = gathered[0]
            pool0 = drv.cap_pool(gathered[-1], L)
            w0 = np.zeros(B, np.int32)
            pool, w, bad, j_rec = drv.dispatch(
                pool0, w0, gathered, purchase=True,
                similarity=drv.similarity, w0_max=0)
            drv.raise_bad(bad, u_pad, b_all, tau)
            w_np = np.asarray(w)[:B].astype(np.int64)
            drv.apply(j_rec, u_pad, b_all, lo)
            drv.counts += w_np
            while int(drv.counts.max()) > node_cap:
                node_type = np.concatenate(
                    [node_type, np.full_like(node_type, -1)], axis=1)
                node_cap *= 2
            for b in wave:
                if w_np[b]:
                    node_type[b, lo[b]: lo[b] + w_np[b]] = tau[b]
        if filling and pool is not None:
            w_host = np.asarray(w)[:B]
            fill = [phases[b].fill[k][~drv.placed[b, phases[b].fill[k]]]
                    if b in wave and w_host[b] > 0
                    else np.zeros(0, np.int64)
                    for b in range(B)]
            if any(len(x) for x in fill):
                L = max(len(x) for x in fill)
                gathered = drv.gather(fill, L, b_all, tau)
                pool, w, _, j_rec = drv.dispatch(
                    pool, w, gathered, purchase=False, similarity=False,
                    w0_max=int(w_host.max()))
                drv.apply(j_rec, gathered[0], b_all, lo)
        wave_s.append(time.perf_counter() - t0)
        k += 1
    return wave_s, node_type


def run_compiled(batch, phases, fit: str, filling: bool,
                 meta: dict | None = None,
                 telemetry: dict | None = None,
                 max_pool_cells: int | None = None):
    """Compiled-stepper body of ``place_many(placement='compiled')``.

    Takes the packed ``ProblemBatch`` and the per-instance ``_Phases``
    the caller already built; returns one ``Solution`` per instance
    (bit-identical to the numpy lockstep engine and ``two_phase``), or
    None when the padded pool tensor would exceed ``max_pool_cells``
    (the caller then runs the numpy engine on the same phases).

    filling=False runs the *type-parallel* plan: one device dispatch
    for the whole placement (every (instance, type) phase is an
    independent scan lane).  filling=True runs wave-synchronized, one
    own-pack + one cross-fill dispatch per node-type phase boundary.
    """
    import jax

    if max_pool_cells is None:
        max_pool_cells = MAX_POOL_CELLS
    drv = _Driver(batch, phases, fit)
    # wave-mode budget: the widest wave's padded pool
    max_own = max((len(ph.own[k]) for ph in phases
                   for k in range(len(ph.type_order))), default=0)
    if batch.B * max_own * drv.K > max_pool_cells:
        if telemetry is not None:
            telemetry["engine"] = "lockstep-fallback"
            telemetry["fallback"] = (
                "padded pool would exceed "
                f"{max_pool_cells} cells; using the numpy engine")
        return None

    t0 = time.perf_counter()
    with jax.enable_x64(True):
        if filling:
            wave_s, node_type = _run_waves(drv, filling)
            mode = "wave-sequential"
        else:
            out = _run_type_parallel(drv, max_pool_cells)
            if out is None:  # lane pool oversized: waves fit the budget
                wave_s, node_type = _run_waves(drv, filling)
                mode = "wave-sequential"
            else:
                wave_s, node_type = out
                wave_s = [time.perf_counter() - t0] * len(wave_s)
                mode = "type-parallel"

    if telemetry is not None:
        telemetry["engine"] = "compiled"
        telemetry["mode"] = mode
        telemetry["waves"] = len(wave_s)
        telemetry["wave_s"] = wave_s
        telemetry["dispatches"] = drv.dispatches

    return drv.solutions(node_type, meta, fit, filling)
