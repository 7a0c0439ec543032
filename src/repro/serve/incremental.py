"""Resident incremental aggregation: O(batch) micro-batch folding.

A dashboard that re-runs ``GroupAgg`` after every ingested micro-batch
pays O(table) per refresh — the whole history re-reads, re-slots, and
re-aggregates even though only ``batch`` rows changed.  This module
keeps the fused (C, R, S) moment tensor and the keyslot slot table
RESIDENT per (plan, table) pair, so a micro-batch costs:

* one ``keyslot.slot_ids_extend`` over the batch's key words — resident
  keys resolve to their existing dense slot, new keys claim the next
  ids, and resident keys NEVER renumber (the winner-always-places
  invariant keeps probe paths consistent across calls);
* one ``fused_segment_agg`` pass over the batch rows
  (``layout='unsorted'``, O(batch) rows);
* one ``core.aggregate.fold_moments`` merge of the batch tensor into
  the resident tensor — the shard_merge collective algebra applied
  host-side: sum/count add, min/max extremize, and the PR-4 index rows
  merge as the lexicographic (key, global_row) extremum.

**Tie-order parity.**  Index rows are globalized to TABLE POSITIONS
before folding (``launch.sharded_agg.sharded_fold_batch`` does the same
on a mesh).  Appended rows fill previously-invalid positions, and a
position only ever transitions invalid → valid, so no position recorded
in the resident index rows can be claimed again: folding N micro-batches
picks exactly the row a one-shot recompute over the final table picks,
including first-attaining ties (positions order the rows both ways).
The same uniqueness makes the payload update sound: a slot's merged
index row differs from its resident value exactly when the batch won it.

**Eligibility** mirrors ``engine._group_agg``'s fused gates — every agg
must be a fused moment (sum/count/min/max/mean/argmin/argmax), count and
mean need the capacity inside f32-exact range, arg-extrema need
``index_moment_ok`` plus an f32-exactly-embeddable key dtype — and the
plan must be a ``GroupAgg`` directly over a catalog ``Scan`` with a
resolvable dense bound.  Anything else (and ``REPRO_INCR_AGG=off``)
falls back to a full recompute at snapshot time; capacity growth can
revoke eligibility mid-stream (``IncrementalIneligible``), which the
server treats the same way.

**Growth.**  A batch whose keys outgrow the resident bucket raises
``GroupBoundOverflow`` *before* any state commits; the server's
double-and-retry then calls ``grow``: the resident key table re-slots
into a doubled bucket (an old→new dense-id permutation), and moments,
payloads, and representatives scatter across it over identity fills.

``snapshot`` finalizes the resident tensor to a result ``Table`` with
the exact decode of ``engine._group_agg_fused`` — no history re-read.

**Epoch publication.**  All resident state lives in ONE immutable
``Epoch`` (moments, ``SlotState``, owner, payloads, the watermark table
and its version, a monotone epoch counter).  ``seed``/``fold``/``grow``
build the complete successor epoch first and commit it with a single
reference assignment — atomic under the GIL — so a concurrent reader
that captures ``current_epoch()`` always decodes a pre-commit or
post-commit generation, never a torn mix, WITHOUT any lock.  The
``fold_publish`` fault site fires between build and swap (modeling a
crash there): the published epoch stays the pre-fold one.  Invariants
(checked by tests): ``epoch_id`` increases by exactly 1 per commit, and
the ``version`` watermark never moves backwards.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import fold_moments
from repro.core.executors import _f32_exact_key_dtype, _index_row_to_pick
from repro.kernels.segment_agg import (ARGMAX_ROW, ARGMIN_ROW, NEG_INF,
                                       POS_INF, _index_tie, _row_fills,
                                       fused_segment_agg, has_index_moments,
                                       index_moment_ok, moment_rows,
                                       normalize_moments)
from repro.configs import flags
from repro.relational.group_bound import resolve_group_bound
from repro.relational.keyslot import (check_slot_overflow, fresh_slot_state,
                                      key_words_for, overflow_extended,
                                      slot_ids_extend, slot_state_build,
                                      sortfree_result)
from repro.relational.plan import GroupAgg, Plan, Scan
from repro.relational.table import Table
from repro.reliability import faults

__all__ = ["Epoch", "IncrementalIneligible", "ResidentAgg",
           "incremental_enabled"]

_ARG_OPS = ("argmin", "argmax")
_FUSED_OPS = ("sum", "min", "max", "count", "mean", "argmin", "argmax")

#: f32-exact ceiling shared with the engine's count/mean gate
_F32_EXACT = 1 << 24


def incremental_enabled() -> bool:
    """Kill switch for resident incremental aggregation (default: on).
    ``REPRO_INCR_AGG=off`` makes ``AggServer.ingest`` a plain
    ``append_rows`` and ``AggServer.snapshot`` a full recompute — the
    mutation API keeps working, only the O(batch) fold path disarms."""
    return flags.enabled("REPRO_INCR_AGG")


class IncrementalIneligible(RuntimeError):
    """The resident state can no longer serve this plan incrementally
    (capacity outgrew an f32-exactness gate, or the bucket hit the row
    capacity); the server drops the residency and snapshots recompute."""


@dataclass(frozen=True)
class Epoch:
    """One published generation of resident state — IMMUTABLE.  A reader
    that captured this object can decode a complete, internally
    consistent snapshot at ``version`` with no further synchronization:
    every field was built before the epoch was published, and commits
    replace the whole object, never a field.  ``table`` is the catalog
    table the epoch folded to (append-only successors keep its valid
    rows bit-identical, so decoding against it is exact at the
    watermark)."""
    state: object                       # keyslot.SlotState (never mutated)
    moments: jax.Array                  # (C, nrows, bound + 1)
    owner: jax.Array                    # (bound,) representative positions
    payloads: Mapping[str, jax.Array]   # arg agg name → (bound + 1,)
    bound: int                          # dense bucket the arrays are sized by
    version: int                        # table-version watermark folded to
    epoch_id: int                       # +1 per commit (seed/fold/grow)
    folds: int                          # committed folds since seed
    table: Table                        # catalog table AT the watermark


def _backend() -> Optional[str]:
    """Backend for the resident fused passes: the engine's choice, with
    the per-op-jnp default mapped to the jnp moment-tensor path (the
    resident algebra needs the (C, R, S) tensor either way).  None means
    the fused path is killed outright (``REPRO_GROUPAGG_FUSED=off``) and
    residency is inadmissible."""
    from repro.relational.engine import _groupagg_fused_backend
    b = _groupagg_fused_backend()
    if b == "off":
        return None
    return "jnp" if b is None else b


class ResidentAgg:
    """Resident fold state for one (GroupAgg plan, catalog table) pair.

    Holds the (C, R, S) moment tensor (S = bucket + overflow slot), the
    incremental ``SlotState``, the per-slot representative table
    positions (``owner``), and one resolved payload value per
    arg-extremum agg.  All mutation is transactional: ``fold`` computes
    every successor array *before* committing any of them, so an
    exception mid-fold (an injected fault, a backend failure, an
    overflow) leaves the resident state exactly as it was.
    """

    def __init__(self, plan: GroupAgg, name: str, keys: Tuple[str, ...],
                 bound: int, backend: str):
        self.plan = plan
        self.name = name
        self.keys = keys
        self.aggs = tuple(plan.aggs)
        self.bound = int(bound)
        self.backend = backend
        self.inferred = False          # server stamps: bound growable?
        # moment layout — byte-for-byte the engine._group_agg_fused
        # construction, so the resident decode matches the one-shot one
        self.value_cols = list(dict.fromkeys(
            (col[0] if op in _ARG_OPS else col)
            for _, op, col in self.aggs if col is not None))
        self.col_idx = {c: i for i, c in enumerate(self.value_cols)}
        ms: List[set] = [set() for _ in range(max(1, len(self.value_cols)))]
        for _, op, col in self.aggs:
            if op in _ARG_OPS:
                ms[self.col_idx[col[0]]].update(
                    ("min", "argmin_first") if op == "argmin"
                    else ("max", "argmax_first"))
                continue
            i = self.col_idx.get(col, 0)
            ms[i].update({"mean": ("sum", "count"),
                          "count": ("count",)}.get(op, (op,)))
        self.norm = normalize_moments(
            tuple(tuple(sorted(s)) for s in ms),
            max(1, len(self.value_cols)))
        self.nrows = moment_rows(self.norm)
        #: the ONE mutable cell: the currently published epoch (None
        #: before seed).  Writes are single reference assignments —
        #: atomic under the GIL — done only by seed/fold/grow/the
        #: version setter; readers capture it once (``current_epoch``)
        self._epoch: Optional[Epoch] = None
        # the local fold math jits once per (batch shape, bucket) — a
        # sustained ingest stream pays kernel time, not eager dispatch
        self._fold_jit = jax.jit(self._fold_math,
                                 static_argnames=("backend",))

    # -- epoch accessors ---------------------------------------------------
    def current_epoch(self) -> Optional[Epoch]:
        """The published epoch — capture ONCE and read only its fields;
        a second call may already observe a successor."""
        return self._epoch

    @property
    def state(self):
        ep = self._epoch
        return None if ep is None else ep.state

    @property
    def moments(self) -> Optional[jax.Array]:
        ep = self._epoch
        return None if ep is None else ep.moments

    @property
    def owner(self) -> Optional[jax.Array]:
        ep = self._epoch
        return None if ep is None else ep.owner

    @property
    def payloads(self) -> Dict[str, jax.Array]:
        ep = self._epoch
        return {} if ep is None else dict(ep.payloads)

    @property
    def folds(self) -> int:
        ep = self._epoch
        return 0 if ep is None else ep.folds

    @property
    def version(self) -> Optional[int]:
        ep = self._epoch
        return None if ep is None else ep.version

    @version.setter
    def version(self, v: int) -> None:
        """Advance the watermark without changing state (an append chain
        that contributed zero rows) — still a full epoch commit, so the
        epoch-id invariant keeps counting."""
        ep = self._epoch
        if ep is None or ep.version == v:
            return
        self._epoch = dataclasses.replace(ep, version=v,
                                          epoch_id=ep.epoch_id + 1)

    # -- admission ---------------------------------------------------------
    @classmethod
    def admit(cls, plan: Plan, name: str, keys: Tuple[str, ...],
              table: Table, bound: int) -> Optional["ResidentAgg"]:
        """A ResidentAgg when every agg of ``plan`` passes the fused
        gates against ``table``; None when the plan must recompute."""
        if not isinstance(plan, GroupAgg) or not isinstance(plan.child, Scan):
            return None
        backend = _backend()
        if backend is None:
            return None
        cap = table.capacity
        for _, op, col in plan.aggs:
            if op not in _FUSED_OPS:
                return None
            if op in ("count", "mean") and cap >= _F32_EXACT:
                return None
            if op in _ARG_OPS:
                if not index_moment_ok(cap):
                    return None
                if not _f32_exact_key_dtype(table.columns[col[0]].dtype):
                    return None
                d = table.columns[col[1]].dtype
                if not (d == jnp.bool_ or (jnp.issubdtype(d, jnp.floating)
                                           and jnp.dtype(d).itemsize <= 4)
                        or jnp.issubdtype(d, jnp.integer)):
                    return None
                continue
            if col is not None:
                d = table.columns[col].dtype
                if not (jnp.issubdtype(d, jnp.floating)
                        and jnp.dtype(d).itemsize <= 4):
                    return None
        return cls(plan, name, keys, bound, backend)

    # -- gates that depend on the (growing) capacity -----------------------
    def _check_caps(self, cap: int) -> None:
        if any(op in ("count", "mean") for _, op, _ in self.aggs) \
                and cap >= _F32_EXACT:
            raise IncrementalIneligible(
                f"table capacity {cap} outgrew the f32-exact count range")
        if has_index_moments(self.norm) and not index_moment_ok(cap):
            raise IncrementalIneligible(
                f"table capacity {cap} outgrew the f32-exact index range")

    def _vals(self, columns: Mapping[str, jax.Array], n: int) -> jax.Array:
        if not self.value_cols:
            return jnp.zeros((n, 1), jnp.float32)
        return jnp.stack([jnp.asarray(columns[c]).astype(jnp.float32)
                          for c in self.value_cols], axis=1)

    def _needed_cols(self) -> List[str]:
        need = list(self.keys) + list(self.value_cols)
        for _, op, col in self.aggs:
            if op in _ARG_OPS:
                need.append(col[1])
        return list(dict.fromkeys(need))

    def _arg_aggs(self):
        for name, op, col in self.aggs:
            if op in _ARG_OPS:
                yield (name, op == "argmin", self.col_idx[col[0]], col[1])

    def _globalize(self, fused_b: jax.Array, pos: jax.Array,
                   nb: int) -> jax.Array:
        """Rewrite the batch tensor's index rows from batch-local row
        indices to table positions (the resident numbering)."""
        if self.nrows == 4:
            return fused_b
        posf = jnp.asarray(pos, jnp.float32)
        cols = []
        for c in range(fused_b.shape[0]):
            rows = []
            for which, row in (("argmin", ARGMIN_ROW), ("argmax", ARGMAX_ROW)):
                tie_first = _index_tie(self.norm[c], which)
                if tie_first is None:
                    rows.append(jnp.full_like(fused_b[c, row], POS_INF))
                    continue
                ident = POS_INF if tie_first else NEG_INF
                lp = fused_b[c, row]
                inr = (lp >= 0) & (lp < nb)
                safe = jnp.clip(lp, 0, nb - 1).astype(jnp.int32)
                rows.append(jnp.where(inr, jnp.take(posf, safe), ident))
            cols.append(jnp.stack(rows))
        return jnp.concatenate([fused_b[:, :4], jnp.stack(cols)], axis=1)

    @jax.named_scope("resident.fold")
    def _fold_math(self, vals_b, seg, pos, moments, owner, new_owner,
                   payloads, pvs, *, backend):
        """The pure-array local fold: batch fused pass → globalize →
        fold → payload/owner merges.  Shapes fix everything else, so the
        jit wrapper retraces only when the batch size or the resident
        bucket changes.  Its device ops are named ``resident.fold`` in a
        profiler trace."""
        nb = vals_b.shape[0]
        ns = moments.shape[2]
        bvalid = jnp.ones((nb,), bool)
        fused_b = fused_segment_agg(vals_b, seg, bvalid[:, None], ns,
                                    backend=backend, moments=self.norm,
                                    layout="unsorted")
        batch_moments = self._globalize(fused_b, pos, nb)
        merged = fold_moments(moments, batch_moments, moments=self.norm)
        out_payloads = []
        for (name, minimize, i, _pc), pv, p in zip(self._arg_aggs(),
                                                   pvs, payloads):
            row = ARGMIN_ROW if minimize else ARGMAX_ROW
            tie_first = _index_tie(self.norm[i],
                                   "argmin" if minimize else "argmax")
            pick = _index_row_to_pick(fused_b[i, row], nb, tie_first)
            got = (pick >= 0) & (pick < nb)
            bp = jnp.where(got,
                           jnp.take(pv, jnp.clip(pick, 0, nb - 1)),
                           jnp.zeros((), pv.dtype))
            # positions transition invalid→valid exactly once, so a batch
            # position can never equal a resident index value: inequality
            # IS "the batch row won this slot"
            wins = merged[i, row] != moments[i, row]
            out_payloads.append(jnp.where(wins, bp.astype(p.dtype), p))
        claimed = new_owner < nb
        owner2 = jnp.where(claimed,
                           jnp.take(pos, jnp.clip(new_owner, 0, nb - 1)),
                           owner)
        return merged, owner2, tuple(out_payloads)

    # -- lifecycle ---------------------------------------------------------
    @property
    def ns(self) -> int:
        return self.bound + 1

    def seed(self, table: Table) -> None:
        """Build the resident state from the full table (one O(table)
        pass — paid once per residency, never per batch)."""
        cap = table.capacity
        self._check_caps(cap)
        seg, owner, overflowed, state = slot_state_build(
            table, self.keys, self.bound)
        check_slot_overflow(int(overflowed), self.bound)   # concrete: raises
        m = table.mask()
        fused = fused_segment_agg(self._vals(table.columns, cap), seg,
                                  m[:, None], self.ns, backend=self.backend,
                                  moments=self.norm, layout="unsorted")
        payloads = {}
        for name, minimize, i, pc in self._arg_aggs():
            row = ARGMIN_ROW if minimize else ARGMAX_ROW
            tie_first = _index_tie(self.norm[i],
                                   "argmin" if minimize else "argmax")
            pick = _index_row_to_pick(fused[i, row], cap, tie_first)
            got = (pick >= 0) & (pick < cap)
            pv = table.columns[pc]
            payloads[name] = jnp.where(
                got, jnp.take(pv, jnp.clip(pick, 0, cap - 1)),
                jnp.zeros((), pv.dtype))
        jax.block_until_ready((fused, owner))
        prev = self._epoch
        ep = Epoch(state=state, moments=fused, owner=owner,
                   payloads=payloads, bound=self.bound,
                   version=table.version,
                   epoch_id=1 if prev is None else prev.epoch_id + 1,
                   folds=0, table=table)
        self._epoch = ep        # the single atomic publication

    def fold(self, table: Table, positions, *,
             backend: Optional[str] = None) -> None:
        """Fold the micro-batch living at ``positions`` of ``table`` into
        the resident state — O(batch) work plus O(num_segments) merges.
        Raises ``GroupBoundOverflow`` (state untouched) when the batch
        keys outgrow the bucket; ``backend`` overrides the fused pass for
        the degraded (jnp) retry of the serving guard."""
        cap = table.capacity
        self._check_caps(cap)
        ep = self._epoch        # captured ONCE: the pre-fold generation
        pos = jnp.asarray(np.asarray(positions), jnp.int32)
        nb = int(pos.shape[0])
        if nb == 0:
            if ep is not None and ep.version != table.version:
                self._epoch = dataclasses.replace(
                    ep, version=table.version, epoch_id=ep.epoch_id + 1,
                    table=table)
            return
        be = backend or self.backend
        bcols = {c: jnp.take(table.columns[c], pos)
                 for c in self._needed_cols()}
        bvalid = jnp.ones((nb,), bool)
        words = key_words_for(bcols[k] for k in self.keys)
        seg, new_owner, overflowed, new_state = slot_ids_extend(
            words, bvalid, ep.state)
        check_slot_overflow(int(overflowed), self.bound)   # concrete: raises
        vals_b = self._vals(bcols, nb)
        arg_names = [name for name, *_rest in self._arg_aggs()]

        from repro.launch.sharded_agg import table_row_split
        route = table_row_split(table)
        if route is not None:
            from repro.launch.sharded_agg import sharded_fold_batch
            specs = tuple((i, minimize, (bcols[pc],))
                          for _, minimize, i, pc in self._arg_aggs())
            batch_moments, picks = sharded_fold_batch(
                vals_b, seg, bvalid[:, None], pos, self.ns,
                mesh=route[0], axis=route[1], backend=be,
                moments=self.norm, payloads=specs)
            batch_pick = {name: picks[j][0] for j, (name, *_rest)
                          in enumerate(self._arg_aggs())}
            merged = fold_moments(ep.moments, batch_moments,
                                  moments=self.norm)
            payload_vals = []
            for name, minimize, i, _pc in self._arg_aggs():
                row = ARGMIN_ROW if minimize else ARGMAX_ROW
                # positions transition invalid→valid exactly once, so a
                # batch position can never equal a resident index value:
                # inequality IS "the batch row won this slot"
                wins = merged[i, row] != ep.moments[i, row]
                p = ep.payloads[name]
                payload_vals.append(jnp.where(
                    wins, batch_pick[name].astype(p.dtype), p))
            claimed = new_owner < nb
            owner = jnp.where(claimed,
                              jnp.take(pos,
                                       jnp.clip(new_owner, 0, nb - 1)),
                              ep.owner)
        else:
            merged, owner, payload_vals = self._fold_jit(
                vals_b, seg, pos, ep.moments, ep.owner, new_owner,
                tuple(ep.payloads[n] for n in arg_names),
                tuple(bcols[pc] for _, _, _, pc in self._arg_aggs()),
                backend=be)
        payloads = dict(zip(arg_names, payload_vals))
        # surface any backend failure HERE (inside the guarded fold), not
        # asynchronously at snapshot time — then build the COMPLETE
        # successor epoch and publish it with one reference swap
        jax.block_until_ready((merged, owner, tuple(payloads.values())))
        succ = Epoch(state=new_state, moments=merged, owner=owner,
                     payloads=payloads, bound=self.bound,
                     version=table.version, epoch_id=ep.epoch_id + 1,
                     folds=ep.folds + 1, table=table)
        # the crash-between-build-and-swap site: everything above is
        # garbage-collectable scratch until the assignment below runs,
        # so a failure HERE leaves readers on the pre-fold epoch
        faults.fail("fold_publish")
        self._epoch = succ

    def grow(self, table: Table) -> bool:
        """Double the resident bucket after an overflowing batch: re-slot
        the resident key table into a fresh larger state (an old→new
        dense-id permutation) and scatter moments/payloads/owners across
        it over identity fills.  False when the doubled bucket would
        reach the row capacity — the dense bound gives out and the
        residency must be dropped."""
        _, b2 = resolve_group_bound(self.bound * 2, table.capacity)
        if b2 is None or b2 <= self.bound:
            return False
        ep = self._epoch        # captured ONCE: the pre-grow generation
        cnt = int(ep.state.cnt)
        ns2 = b2 + 1
        st2 = fresh_slot_state(ep.state.ktab.shape[1], b2,
                               ep.state.expand)
        if cnt:
            segmap, _own, ovf, st2 = slot_ids_extend(
                ep.state.ktab[:cnt], jnp.ones((cnt,), bool), st2)
            if int(ovf) != 0:      # cannot happen: b2 ≥ 2·cnt
                return False
            inv_b = jnp.full((b2,), cnt, jnp.int32).at[segmap].set(
                jnp.arange(cnt, dtype=jnp.int32), mode="drop")
        else:
            inv_b = jnp.full((b2,), cnt, jnp.int32)
        occ_b = inv_b < cnt
        inv = jnp.concatenate([inv_b, jnp.full((1,), cnt, jnp.int32)])
        occ = jnp.concatenate([occ_b, jnp.zeros((1,), bool)])
        safe = jnp.clip(inv, 0, max(cnt - 1, 0))
        fills = jnp.asarray(_row_fills(self.norm), jnp.float32).reshape(
            ep.moments.shape[0], self.nrows)
        moments2 = jnp.where(occ[None, None, :],
                             ep.moments[:, :, safe], fills[:, :, None])
        payloads2 = {
            name: jnp.where(occ, jnp.take(p, safe),
                            jnp.zeros((), p.dtype))
            for name, p in ep.payloads.items()}
        owner2 = jnp.where(
            occ_b,
            jnp.take(ep.owner, jnp.clip(inv_b, 0, self.bound - 1)),
            jnp.int32(-1))
        jax.block_until_ready((moments2, owner2))
        self.bound = b2
        self._epoch = dataclasses.replace(
            ep, state=st2, moments=moments2, owner=owner2,
            payloads=payloads2, bound=b2, epoch_id=ep.epoch_id + 1)
        return True

    def snapshot(self, table: Table) -> Table:
        """Finalize the resident tensor to the result Table — the decode
        of ``engine._group_agg_fused`` over claim-order slots, assembled
        by the shared ``sortfree_result`` epilogue.  O(num_segments); the
        table's history is never re-read."""
        return self.snapshot_epoch(self._epoch, table)

    def snapshot_epoch(self, ep: Epoch, table: Optional[Table] = None
                       ) -> Table:
        """Decode one captured epoch — reads ONLY ``ep``'s fields (plus
        the optional ``table`` override, which must be the epoch's
        watermark table or an append-descendant of it), so it is safe to
        run with no lock while folds publish successors concurrently."""
        t = ep.table if table is None else table
        cap = t.capacity
        occupied = jnp.arange(ep.bound) < ep.state.cnt
        rep_b = jnp.where(occupied, ep.owner, cap).astype(jnp.int32)
        rep, out_valid = overflow_extended(rep_b, occupied, cap)
        fused = ep.moments
        out: Dict[str, jax.Array] = {}
        for name, op, col in self.aggs:
            if op == "count":
                out[name] = fused[0, 1].astype(
                    jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
                continue
            if op in _ARG_OPS:
                out[name] = ep.payloads[name]
                continue
            i = self.col_idx[col]
            d = t.columns[col].dtype
            if op == "sum":
                out[name] = fused[i, 0].astype(d)
            elif op == "mean":
                out[name] = fused[i, 0] / jnp.maximum(fused[i, 1], 1.0)
            elif op == "min":
                out[name] = fused[i, 2].astype(d)
            else:
                out[name] = fused[i, 3].astype(d)
        return sortfree_result(t, self.keys, rep, out_valid, 0,
                               ep.bound, out)
