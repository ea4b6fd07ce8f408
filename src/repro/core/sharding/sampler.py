"""Mesh-sharded Algorithm-1 rounds: the fused union loop under ``shard_map``.

:class:`ShardedUnionSampler` scales the fused device engine
(:class:`~repro.core.backends.jax_backend.JaxUnionSampler`) across a 1-axis
device mesh.  One round, per shard:

1. **replicated cover selection** — every shard derives the same per-slot
   categorical picks from the shared round key and histograms them into the
   global per-piece targets (no communication; the histogram covers all
   global slots of the round),
2. **local candidate draws** — each shard draws its per-join batch of
   i.i.d. EW tree candidates from the *whole* join under its own fold-in
   key (replicated roots — see
   :class:`~repro.core.sharding.catalog.ShardedTreeJoin` for why root-range
   pieces would bias fixed-shape consumption); cyclic joins run the §8.2
   skeleton draw + residual-edge verification entirely inside this local
   step (residual sorted-key indexes are replicated non-root node state,
   like every other child index),
3. **one fingerprint exchange** — earlier-piece membership probes are
   resolved by hash-partition ownership: all shards ``all_gather`` the
   candidates' per-relation fingerprints, the owner shard answers each
   probe against its local sorted index, and one ``psum_scatter``
   (reduce-scatter) ORs the owner verdicts and hands each shard exactly its
   own candidates' segment.  Residual relations are ordinary base relations
   of their join, so their row fingerprints are hash-partitioned and ride
   this same exchange — cyclic cover pieces add **zero** extra collectives,
4. **local compaction** — accepted candidates are rank-scattered to the
   front of each shard's ``(B_j, A+1)`` row matrix (attributes + home
   piece id), exactly like the unsharded engine.

With ``fused_rounds="device"`` (default) the *entire multi-round loop* runs
inside one ``shard_map``'d ``lax.while_loop`` program: per-shard ring-buffer
surplus banks, the global shortfall vector and dead-piece flags as
replicated carry, and one extra (tiny) ``all_gather`` of the per-shard
``(count, accepted, ok, residual)`` matrices per round from which **every**
shard computes the same global water-filling allocation — which shard
serves how much of each piece's target from bank and fresh rows — plus its
own rows' global output offsets, with no further collectives.  Each shard
scatters its rows directly to their final global positions in a private
output buffer; the host ORs the disjoint buffers once per ``sample(n)``
call.  ``fused_rounds="host"`` drives the same shard_map'd round program
from the inherited host loop (one sync per round) for parity testing.

Exactness: each emitted sample is an i.i.d. ``1/|U|`` draw — the same
argument as the unsharded engine, because every shard's candidates are
i.i.d. uniform over the whole join, so their cover-accepted subsequences
are i.i.d. uniform over the cover piece, exchangeable across shards, and
any deterministic consumption order (shard-major water filling, per-shard
FIFO banking) is unbiased.  With a 1-device mesh both modes degenerate to
the unsharded programs op-for-op, which the equivalence tests pin bit for
bit against ``JaxUnionSampler``.  With ``world > 1`` the device loop's
banking is per-shard FIFO (capacity ``surplus_cap // world`` each) while
the host-mode twin banks globally — both unbiased by exchangeability, but
only ``world == 1`` is bit-identical across the two modes once banks are
exercised.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..backends.jax_backend import (PIECE_STAT_FIELDS, _STAT_FIELDS,
                                    JaxUnionSampler, _cover_cum,
                                    _emit_and_bank, _piece_batches, fp32_jnp)
from .. import planner
from .catalog import ShardedCatalog


def _window_probe(s1, s2, n_own, qq1, qq2, kmax: int):
    """Sorted-fingerprint probe with a static duplicate window (per shard)."""
    lo = jnp.searchsorted(s1, qq1, side="left")
    m = jnp.zeros(qq1.shape, bool)
    cap = s1.shape[0]
    for k in range(kmax):       # duplicate window (tiny, static)
        pos = jnp.minimum(lo + k, cap - 1)
        m = m | ((lo + k < n_own) & (s1[pos] == qq1) & (s2[pos] == qq2))
    return m


class ShardedUnionSampler(JaxUnionSampler):
    """Algorithm-1 top-up rounds over a device mesh.

    ``round_batch`` is the *per-shard* selection-slot budget; the global
    round capacity is ``world * round_batch`` and per-join draw batches are
    cover-balanced per shard (``world ×`` the unsharded schedule).  The
    host-loop twin (selection carry, global surplus banking, dead-piece
    detection, final shuffle) is inherited unchanged from
    :class:`JaxUnionSampler`; the device mode replaces the whole loop with
    the ``shard_map``'d persistent program built here.
    """

    _narrows_residual = False          # the mesh round draws full rows

    def __init__(self, scat: ShardedCatalog, cover, seed: int = 0,
                 round_batch: int = 4096, dead_rounds: int = 8,
                 max_rounds: int = 4096, surplus_cap: Optional[int] = None,
                 stats=None, fused_rounds: str = "device",
                 balance: str = "cover", balance_slack: float = 1.5,
                 predicate=None, plan: str = "static"):
        self.scat = scat
        self.mesh = scat.mesh
        self.saxis = scat.axis
        self.world = scat.world
        self.shard_batch = int(round_batch)
        super().__init__(scat.backend, cover, seed=seed,
                         round_batch=self.shard_batch * self.world,
                         dead_rounds=dead_rounds, max_rounds=max_rounds,
                         surplus_cap=surplus_cap, stats=stats,
                         fused_rounds=fused_rounds, balance=balance,
                         balance_slack=balance_slack, predicate=predicate,
                         plan=plan)
        # per-shard cover-balanced draw widths; the global schedule (used by
        # the stats accounting) is world× that, and collapses to the
        # unsharded schedule on a 1-device mesh (bitwise-parity pin)
        base = np.maximum(np.asarray(cover.selection_probs(), np.float64), 0)
        self.shard_piece_batches = _piece_batches(
            base, self.shard_batch, balance, balance_slack)
        if self.plan == "adaptive":
            # demand-matched widths per shard (same rule as the unsharded
            # engine at shard granularity), so the world× global schedule
            # stays an exact multiple of the per-shard draw widths and
            # collapses to the unsharded one on a 1-device mesh
            self.shard_piece_batches = planner.alloc_batches(
                self.shard_piece_batches, base,
                planner.seed_rates(cover, self._tree_specs())[:, 0],
                planner.adaptive_slot(self.shard_batch))
        self.piece_batches = tuple(self.world * b
                                   for b in self.shard_piece_batches)
        # the planner constants derive from piece_batches, which this
        # subclass just rescaled — rebuild them on the global schedule
        self._setup_planner()
        self.strees = [scat.trees[n] for n in self.order]
        self.smems = [scat.members[n] for n in self.order]
        self._dtrees = [t.tree for t in self.strees]
        # the catalog argument of the programs: per-shard root slices and
        # membership partitions, plus the replicated non-root node indexes
        self._state = {"roots": [t.state() for t in self.strees],
                       "mem": [m.state() for m in self.smems],
                       "trees": [t.nodes for t in self.strees]}
        self._state_spec = {"roots": P(self.saxis), "mem": P(self.saxis),
                            "trees": P()}
        # flat probe plan: (join j, earlier piece q, relation ridx, ...)
        self._probe_plan: List[Tuple[int, int, int, Tuple[str, ...], int]] = []
        for j in range(len(self.order)):
            for q in range(j):
                for ridx, r in enumerate(self.smems[q].rels):
                    self._probe_plan.append((j, q, ridx, r.attrs, r.kmax))
        self._round_prog = self._build_round_prog()
        self._round_jit = self._sharded_round      # host-loop entry point

    # -- device-input hooks ---------------------------------------------------
    def _catalog_args(self):
        """The mesh programs' catalog argument: per-shard roots and
        membership partitions plus the replicated node indexes."""
        return self._state

    def _ensure_device_inputs(self) -> None:
        """No-op: the sharded engine's tree/membership state is prebuilt in
        ``self._state`` (hash-partitioned device arrays), so nothing lazy
        may leak into a trace."""

    # -- the shard-local round core (traceable) -------------------------------
    def _shard_round_core(self, key: jax.Array, probs_cum, carry_need,
                          extra_target, st, sid, ema=None, gcount=None):
        """One round on one shard: replicated picks, local draws, the
        fingerprint exchange, local acceptance + matrix compaction.

        Returns ``(mats, okc, resc, accc, predc, killc, need)`` where
        ``mats[j]`` is this shard's accepted-compacted ``(B_j, A+1)`` row
        matrix and the count vectors are per-shard; ``need`` is the
        replicated global target.  Under ``plan="adaptive"`` the replicated
        EMAs and global bank occupancy come in, the replicated **global**
        budget goes out as an eighth element, and each shard draws its
        near-equal split of it.
        """
        nj = len(self.order)
        world = self.world
        adaptive = self.plan == "adaptive"
        bs = self.shard_piece_batches
        kpick, *jks = jax.random.split(key, nj + 1)
        # (1) replicated multinomial cover selection over all global slots
        u = jax.random.uniform(kpick, (self._slot_width,))
        pick = jnp.clip(jnp.searchsorted(probs_cum, u, side="right"
                                         ).astype(jnp.int32), 0, nj - 1)
        valid = (jnp.arange(self._slot_width)
                 < extra_target).astype(jnp.int32)
        need = carry_need + jnp.zeros((nj,), jnp.int32).at[pick].add(valid)
        gbudget = bshard = None
        if adaptive:
            # replicated global budget from replicated counts (no
            # collectives), split across shards so the per-shard shares sum
            # exactly to the global budget; world=1 degenerates to the
            # unsharded budget bit for bit
            gbudget = planner.budget_for(
                need, gcount, ema[:, 0],
                jnp.asarray(self._pbatch_i32), self._drain_w, jnp)
            bshard = (gbudget // world
                      + (sid < (gbudget % world)).astype(jnp.int32))

        # (2) local i.i.d. whole-join draws (replicated roots, per-shard
        # fold-in keys; §8.2 residual edges verify locally — their sorted
        # indexes are replicated non-root node state)
        rows_j, ok_j, wok_j, killc = [], [], [], []
        for j in range(nj):
            rst = st["roots"][j]
            prefix = rst["prefix"][0]
            cols = {a: c[0] for a, c in rst["cols"].items()}
            kd = (jks[j] if world == 1          # bit-for-bit unsharded
                  else jax.random.fold_in(jks[j], sid))
            rows, ok, wok, skel = self._dtrees[j].draw_with_root(
                kd, bs[j], prefix, cols, rst["n_root"][0], st["trees"][j],
                skeleton=True)
            if bshard is not None:
                elig = jnp.arange(bs[j]) < bshard[j]
                ok = ok & elig
                wok = wok & elig
                skel = None if skel is None else skel & elig
            killc.append(jnp.int32(0) if skel is None
                         else jnp.sum(skel) - jnp.sum(ok))
            rows_j.append(rows)
            ok_j.append(ok)
            wok_j.append(wok)

        # (3) one fingerprint exchange answers every earlier-piece probe
        found = self._exchange_probes(rows_j, st, sid)

        # (4) local acceptance (fused §8.3 predicate mask first) +
        # rank-scatter compaction (home id rides as the last matrix column,
        # exactly like the unsharded round)
        mats, okc, resc, accc, predc = [], [], [], [], []
        p = 0
        for j in range(nj):
            acc = ok_j[j]
            resc.append(jnp.sum(wok_j[j]) - jnp.sum(acc))
            pf = self._pred_fns[j]
            if pf is None:
                predc.append(jnp.int32(0))
            else:
                pok = pf(rows_j[j])
                predc.append(jnp.sum(acc & ~pok).astype(jnp.int32))
                acc = acc & pok
            for q in range(j):
                contained = jnp.ones((bs[j],), bool)
                for _ in range(len(self.smems[q].rels)):
                    contained = contained & found[p][: bs[j]]
                    p += 1
                # a rejection-predicate piece q contains the candidate only
                # if its own reject_preds also hold (the union-wide
                # predicate is excluded: candidates already passed it)
                cpf = self._cont_pred_fns[q]
                if cpf is not None:
                    contained = contained & cpf(rows_j[j])
                acc = acc & ~contained
            dst = jnp.where(acc, jnp.cumsum(acc) - 1, bs[j])
            mat = jnp.stack([rows_j[j][a].astype(jnp.int32)
                             for a in self.attrs]
                            + [jnp.full(bs[j], j, jnp.int32)], axis=1)
            mats.append(jnp.zeros((bs[j], mat.shape[1]), jnp.int32)
                        .at[dst].set(mat, mode="drop"))
            okc.append(jnp.sum(wok_j[j]))
            accc.append(jnp.sum(acc))
        out = (mats, jnp.stack(okc).astype(jnp.int32),
               jnp.stack(resc).astype(jnp.int32),
               jnp.stack(accc).astype(jnp.int32),
               jnp.stack(predc).astype(jnp.int32),
               jnp.stack(killc).astype(jnp.int32), need)
        if adaptive:
            out = out + (gbudget.astype(jnp.int32),)
        return out

    def _exchange_probes(self, rows_j, st, sid):
        """All earlier-piece membership probes in one collective exchange.

        ``world == 1`` degenerates to fully local probes (no collectives,
        bit-equal to :meth:`DeviceJoinMembership.contains`).  Otherwise the
        per-join probe vectors are padded to the widest draw batch so one
        ``all_gather`` + one ``psum_scatter`` covers every (join, earlier
        piece, relation) triple; pad verdicts are sliced off before use.
        """
        # named scope: the exchange shows up as one block in profiler traces
        # (jax.named_scope is trace-time metadata — zero runtime cost)
        with jax.named_scope("fingerprint_exchange"):
            return self._exchange_probes_impl(rows_j, st, sid)

    def _exchange_probes_impl(self, rows_j, st, sid):
        plan = self._probe_plan
        if not plan:
            return []
        world, axis = self.world, self.saxis
        if world == 1:
            out = []
            for (j, q, ridx, attrs, kmax) in plan:
                mst = st["mem"][q][ridx]
                out.append(_window_probe(
                    mst["fp1"][0], mst["fp2"][0], mst["n_owned"][0],
                    fp32_jnp([rows_j[j][a] for a in attrs], salt=1),
                    fp32_jnp([rows_j[j][a] for a in attrs], salt=2),
                    kmax))
            return out
        bs = self.shard_piece_batches
        bmax = max(bs[j] for (j, _q, _r, _a, _k) in plan)

        def padded(vec):
            if vec.shape[0] == bmax:
                return vec
            return jnp.concatenate(
                [vec, jnp.zeros((bmax - vec.shape[0],), vec.dtype)])

        q1 = jnp.stack([padded(fp32_jnp([rows_j[j][a] for a in attrs],
                                        salt=1))
                        for (j, q, ridx, attrs, kmax) in plan])
        q2 = jnp.stack([padded(fp32_jnp([rows_j[j][a] for a in attrs],
                                        salt=2))
                        for (j, q, ridx, attrs, kmax) in plan])
        n_probe = len(plan)
        gn = world * bmax
        g1 = jnp.transpose(jax.lax.all_gather(q1, axis),
                           (1, 0, 2)).reshape(n_probe, gn)
        g2 = jnp.transpose(jax.lax.all_gather(q2, axis),
                           (1, 0, 2)).reshape(n_probe, gn)
        hits = []
        for pi, (j, q, ridx, attrs, kmax) in enumerate(plan):
            mst = st["mem"][q][ridx]
            m = _window_probe(mst["fp1"][0], mst["fp2"][0],
                              mst["n_owned"][0], g1[pi], g2[pi], kmax)
            # only the fp owner may answer (hash-partition ownership)
            m = m & ((g1[pi] % jnp.uint32(world)).astype(jnp.int32) == sid)
            hits.append(m.astype(jnp.int32))
        scat = jax.lax.psum_scatter(jnp.stack(hits), axis,
                                    scatter_dimension=1, tiled=True)
        return [scat[pi] > 0 for pi in range(n_probe)]

    # -- host-mode round program (fused_rounds="host") ------------------------
    def _build_round_prog(self):
        mesh, axis = self.mesh, self.saxis
        adaptive = self.plan == "adaptive"

        if adaptive:
            def round_fn(probs_base, dead, carry_need, extra_target, key,
                         st, ema, gcount):
                sid = jax.lax.axis_index(axis)
                probs_cum, bad = _cover_cum(probs_base, dead)
                mats, okc, resc, accc, predc, killc, need, gb = \
                    self._shard_round_core(key, probs_cum, carry_need,
                                           extra_target, st, sid, ema,
                                           gcount)
                return ([m[None] for m in mats], okc[None], resc[None],
                        accc[None], predc[None], killc[None], need[None],
                        gb[None], bad[None])

            in_specs = (P(), P(), P(), P(), P(), self._state_spec, P(), P())
        else:
            def round_fn(probs_base, dead, carry_need, extra_target, key,
                         st):
                sid = jax.lax.axis_index(axis)
                probs_cum, bad = _cover_cum(probs_base, dead)
                mats, okc, resc, accc, predc, killc, need = \
                    self._shard_round_core(key, probs_cum, carry_need,
                                           extra_target, st, sid)
                return ([m[None] for m in mats], okc[None], resc[None],
                        accc[None], predc[None], killc[None], need[None],
                        bad[None])

            in_specs = (P(), P(), P(), P(), P(), self._state_spec)

        return jax.jit(jax.shard_map(
            round_fn, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(axis), check_vma=False))

    def _sharded_round(self, probs_base, dead, carry_need, extra_target,
                       key, ema=None, bank_count=None, *, cat):
        """Run one mesh round; adapt it to the host-loop contract.

        ``cols[j]``'s first ``accc[j]`` rows are the accepted rows in
        shard-major order — the same consumption order the device loop's
        water-filling allocation uses for fresh rows.  ``bank_count`` under
        ``plan="adaptive"`` is the host loop's *global* bank occupancy — the
        same quantity the device loop carries replicated as ``gcount``.
        """
        budget = None
        if self.plan == "adaptive":
            (mats, okc, resc, accc, predc, killc, need, budget,
             bad) = self._round_prog(
                probs_base, dead, carry_need, extra_target, key,
                cat, ema, bank_count)
            budget = np.asarray(budget)[0]
        else:
            (mats, okc, resc, accc, predc, killc, need,
             bad) = self._round_prog(probs_base, dead, carry_need,
                                     extra_target, key, cat)
        okc = np.asarray(okc)
        resc = np.asarray(resc)
        accc = np.asarray(accc)                     # (world, nj)
        predc = np.asarray(predc)
        cols: List[np.ndarray] = []
        a1 = len(self.attrs) + 1
        for j in range(len(self.order)):
            m = np.asarray(mats[j])                 # (world, B_j, A+1)
            if self.world == 1:
                cols.append(m[0])
                continue
            g = np.zeros((self.world * m.shape[1], a1), np.int32)
            pos = 0
            for s in range(self.world):
                a = int(accc[s, j])
                g[pos:pos + a] = m[s, :a]
                pos += a
            cols.append(g)
        # the mesh round draws full rows: no residual survivor is dropped
        out = (cols, okc.sum(axis=0), resc.sum(axis=0), accc.sum(axis=0),
               predc.sum(axis=0), np.asarray(killc).sum(axis=0),
               np.zeros(len(self.order), np.int64), np.asarray(need)[0])
        if budget is not None:
            out = out + (budget,)
        return out + (bool(np.asarray(bad)[0]),)

    # -- the persistent device loop (fused_rounds="device") -------------------
    def _init_state(self):
        nj = len(self.order)
        cap = max(1, self.surplus_cap // self.world)
        st = {
            "key": self.key,
            "owed": jnp.zeros(nj, jnp.int32),
            "dead": jnp.zeros(nj, dtype=bool),
            "streak": jnp.zeros(nj, jnp.int32),
            "bank": jnp.zeros((self.world, nj, cap, len(self.attrs) + 1),
                              jnp.int32),
            "bank_head": jnp.zeros((self.world, nj), jnp.int32),
            "bank_count": jnp.zeros((self.world, nj), jnp.int32),
        }
        if self.plan == "adaptive":
            st["ema"] = jnp.asarray(self._ema_seed)
            # replicated global bank occupancy at round start (the per-shard
            # counts are sharded carry, so the budget reads this instead)
            st["gcount"] = jnp.zeros(nj, jnp.int32)
        return st

    def _out_buffer(self, C: int):
        """Per-shard output buffers: each shard scatters its rows at their
        final global positions; the disjoint buffers merge by summation."""
        return jnp.zeros((self.world, C, len(self.attrs) + 1), jnp.int32)

    def _merge_out(self, out) -> np.ndarray:
        arr = np.asarray(out)
        return arr[0] if self.world == 1 else arr.sum(axis=0)

    def _build_loop(self, C: int):
        mesh, axis, world = self.mesh, self.saxis, self.world
        cap = max(1, self.surplus_cap // world)
        W = min(self._drain_w, cap)
        bt = int(sum(self.piece_batches))
        adaptive = self.plan == "adaptive"
        max_rounds = jnp.int32(self.max_rounds)
        dead_rounds = jnp.int32(self.dead_rounds)

        pbatch = jnp.asarray(self.piece_batches, jnp.int32)
        shifts = jnp.asarray(self._ema_shifts)

        def loop_fn(shr, rep, out, n, probs_base, st):
            self._trace_events.append(("loop", C, self.plan))
            sid = jax.lax.axis_index(axis)

            def cond(c):
                total, rounds, fail = c[8], c[9], c[10]
                return (total < n) & (rounds < max_rounds) & ~fail

            def body(c):
                (key, owed, dead, streak, bank, head, count, out,
                 total, rounds, fail, stats, pstats) = c[:13]
                probs_cum, bad = _cover_cum(probs_base, dead)
                key2, kround = jax.random.split(key)
                extra = jnp.clip(n - total - jnp.sum(owed),
                                 0, self._slot_width)
                if adaptive:
                    ema, gcount = c[13], c[14]
                    (mats, okc_s, resc_s, accc_s, predc_s, killc_s, need,
                     gb) = self._shard_round_core(
                        kround, probs_cum, owed, extra, st, sid, ema,
                        gcount)
                else:
                    gb = None
                    (mats, okc_s, resc_s, accc_s, predc_s, killc_s,
                     need) = self._shard_round_core(
                        kround, probs_cum, owed, extra, st, sid)
                # one tiny exchange: per-shard (bank count, accepted, ok,
                # residual, predicate-reject, residual-kill) matrices — every
                # shard then computes the same global water-filling
                # allocation AND its own rows' global output offsets with no
                # further collectives
                gat = jax.lax.all_gather(
                    jnp.stack([count, accc_s, okc_s, resc_s, predc_s,
                               killc_s]), axis)
                counts_w, acc_w = gat[:, 0], gat[:, 1]     # (world, nj)
                okg = jnp.sum(gat[:, 2])
                resg = jnp.sum(gat[:, 3])
                predg = jnp.sum(gat[:, 4])
                accg_v = jnp.sum(acc_w, axis=0)            # (nj,) global
                tot_count = jnp.sum(counts_w, axis=0)
                # bank take (FIFO, capped) → fresh take → carried shortfall
                dtg = jnp.minimum(jnp.minimum(need, tot_count),
                                  self._drain_w)
                ftg = jnp.minimum(need - dtg, accg_v)
                # shard-major water filling: shard s serves the slice of the
                # global take that lands in its segment of the prefix sums
                cpref = jnp.cumsum(counts_w, axis=0) - counts_w
                dt_w = jnp.clip(dtg[None] - cpref, 0, counts_w)
                apref = jnp.cumsum(acc_w, axis=0) - acc_w
                ft_w = jnp.clip(ftg[None] - apref, 0, acc_w)
                takeg = dtg + ftg
                seg = total + jnp.cumsum(takeg) - takeg
                bank_base = seg + (jnp.cumsum(dt_w, axis=0) - dt_w)[sid]
                fresh_base = (seg + dtg
                              + (jnp.cumsum(ft_w, axis=0) - ft_w)[sid])
                out2, _, bank2, head2, count2 = _emit_and_bank(
                    out, total, bank, head, count, mats,
                    dt_w[sid], ft_w[sid], accc_s, cap, C, W,
                    bank_base=bank_base, fresh_base=fresh_base)
                total2 = total + jnp.sum(takeg)
                # global post-round bank occupancy for the dead-piece rules
                # (derivable on every shard from the gathered matrices)
                push_w = jnp.minimum(acc_w - ft_w,
                                     cap - (counts_w - dt_w))
                countg2 = jnp.sum(counts_w - dt_w + push_w, axis=0)
                shortfall = need - dtg - ftg
                dropped = jnp.sum(jnp.where(dead, shortfall, 0))
                shortfall = jnp.where(dead, 0, shortfall)
                trig = (shortfall > 0) & (accg_v == 0) & (countg2 == 0)
                streak2 = jnp.where(dead, streak,
                                    jnp.where(trig, streak + 1, 0))
                newly = ~dead & (streak2 >= dead_rounds)
                dropped = dropped + jnp.sum(jnp.where(newly, shortfall, 0))
                shortfall = jnp.where(newly, 0, shortfall)
                drawn = jnp.sum(gb) if adaptive else jnp.int32(bt)
                stats2 = stats + jnp.stack(
                    [drawn.astype(jnp.int32), drawn.astype(jnp.int32),
                     (okg - resg - predg - jnp.sum(accg_v))
                     .astype(jnp.int32),
                     resg.astype(jnp.int32),
                     predg.astype(jnp.int32),
                     dropped.astype(jnp.int32)])
                pstats2 = jnp.stack(
                    [pstats[:, 0] + (gb if adaptive else pbatch),
                     pstats[:, 1] + accg_v.astype(jnp.int32),
                     pstats[:, 2] + jnp.sum(gat[:, 3], axis=0)
                                       .astype(jnp.int32),
                     pstats[:, 3] + jnp.sum(gat[:, 5], axis=0)
                                       .astype(jnp.int32),
                     pstats[:, 4],              # no survivor width here
                     pstats[:, 5] + dtg.astype(jnp.int32),
                     jnp.maximum(pstats[:, 6], countg2.astype(jnp.int32))],
                    axis=1)
                nxt = (key2, shortfall.astype(jnp.int32), dead | newly,
                       streak2.astype(jnp.int32), bank2,
                       head2.astype(jnp.int32), count2.astype(jnp.int32),
                       out2, total2, rounds + 1, fail | bad, stats2,
                       pstats2)
                if adaptive:
                    # EMA step from the already-gathered global counts —
                    # zero extra collectives; the post-round global bank
                    # occupancy doubles as next round's budget input
                    okg_v = jnp.sum(gat[:, 2], axis=0)
                    resg_v = jnp.sum(gat[:, 3], axis=0)
                    predg_v = jnp.sum(gat[:, 4], axis=0)
                    counts4 = jnp.stack(
                        [accg_v, okg_v, resg_v, predg_v],
                        axis=1).astype(jnp.int32)
                    ema2 = planner.ema_update(ema, gb, counts4, shifts, jnp)
                    nxt = nxt + (ema2, countg2.astype(jnp.int32))
                return nxt

            init = (rep["key"], rep["owed"], rep["dead"], rep["streak"],
                    shr["bank"][0], shr["bank_head"][0],
                    shr["bank_count"][0], out[0],
                    jnp.int32(0), jnp.int32(0), jnp.bool_(False),
                    jnp.zeros(len(_STAT_FIELDS), jnp.int32),
                    jnp.zeros((len(self.order), len(PIECE_STAT_FIELDS)),
                              jnp.int32))
            if adaptive:
                init = init + (rep["ema"], rep["gcount"])
            fin = jax.lax.while_loop(cond, body, init)
            (key, owed, dead, streak, bank, head, count, out2,
             total, rounds, fail, stats, pstats) = fin[:13]
            rep2 = {"key": key[None], "owed": owed[None],
                    "dead": dead[None], "streak": streak[None]}
            if adaptive:
                rep2["ema"] = fin[13][None]
                rep2["gcount"] = fin[14][None]
            return ({"bank": bank[None], "bank_head": head[None],
                     "bank_count": count[None]},
                    rep2,
                    out2[None], total[None], rounds[None], fail[None],
                    stats[None], pstats[None])

        shr_spec = {"bank": P(axis), "bank_head": P(axis),
                    "bank_count": P(axis)}
        rep_keys = ("key", "owed", "dead", "streak")
        if adaptive:
            rep_keys = rep_keys + ("ema", "gcount")
        rep_spec = {k: P() for k in rep_keys}
        prog = jax.jit(jax.shard_map(
            loop_fn, mesh=mesh,
            in_specs=(shr_spec, rep_spec, P(axis), P(), P(),
                      self._state_spec),
            out_specs=P(axis), check_vma=False),
            donate_argnums=(0, 2))

        def run(state, out, n, probs_base, cat):
            shr = {k: state[k] for k in ("bank", "bank_head", "bank_count")}
            rep = {k: state[k] for k in rep_keys}
            shr2, rep2, out2, total, rounds, fail, stats, pstats = prog(
                shr, rep, out, n, probs_base, cat)
            state2 = dict(shr2)
            state2.update({k: v[0] for k, v in rep2.items()})
            return (state2, out2, total[0], rounds[0], fail[0], stats[0],
                    pstats[0])

        # expose the jitted program and its arg plumbing so the static
        # analyzer (repro.analysis.jaxpr_audit) can lower it without running
        run._prog = prog
        run._rep_keys = rep_keys
        return run
