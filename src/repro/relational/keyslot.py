"""Sort-free dense group slotting: hash-slotted segment ids.

The grouped executors historically derived segment ids by *sorting* the
input on the group keys (``Table.sort_by`` + adjacent-difference,
``engine.segment_ids_for``) — an O(N log N) materializing step the
order-insensitive moment aggregates (sum/count/min/max and the
arg-extremum index moment, all commutative merge algebras) never need.
For those, grouping only requires a key → dense-segment *assignment*, not
a total order.  This module is that assignment: a static-capacity,
power-of-two, quadratic-probe hash table built entirely from XLA
primitives (scatter-min claims + gathers inside one ``lax.while_loop``).
The probe table is over-provisioned (``EXPAND ×`` the dense group bound
of relational/group_bound.py, so the load factor is bounded at 1/EXPAND
and probing terminates in a couple of O(N) rounds even at a full
bucket); occupied probe slots then renumber densely into ``[0, bucket)``
by one prefix sum, so everything segment-sized stays bucket-sized.

Contract, mirroring the sorted route:

* every valid row with the same group-key tuple gets the same slot in
  ``[0, bucket)``; distinct tuples get distinct slots (hash collisions
  are *resolved* by probing on full key equality, never assumed away);
* invalid rows park in the dedicated overflow slot (``bucket`` — the
  ``num_segments - 1`` slot ``resolve_group_bound`` reserves);
* the bound is *validated, not assumed* (the ``check_group_overflow``
  pattern): when the input carries more distinct keys than the bucket has
  slots, probing exhausts the table and the unplaced rows are counted —
  a concrete count raises eagerly, a traced one hands back a guard the
  caller uses to poison its outputs.

Unlike the sorted route, slot numbers are *probe-table order* (the
order the keys' winning probe slots happen to sit in the table), not
key order: the ``occupied`` mask is still a dense ``[0, #groups)``
prefix — the densifying prefix sum guarantees it — but which group owns
which slot is hash-determined, and the representative row of each group
comes from the ``owner`` table rather than from segment starts.  Key
equality is *bitwise on canonical words*:
floats compare after a −0.0 → +0.0 normalization (so ±0 share a group,
as value equality would), and NaN keys — which value equality would
splinter into one group per row — share a group per bit pattern, the
SQL-flavored choice.

Probing cost: all rows of one key share one hash, so they probe in
lockstep — the loop runs for the *maximum probe length over keys*, each
round a handful of O(N) elementwise ops plus one table-sized
scatter-min.  Quadratic probing (triangular increments, which visit
every slot of a power-of-two table) plus the 1/EXPAND load bound keeps
that maximum at a couple of rounds on real key sets; the bench shape
(50k rows, a full 512-slot bucket) slots in well under the variadic
sort it replaces.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from functools import partial
from typing import Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs import flags
from ..reliability import faults
from .group_bound import GroupBoundOverflow

__all__ = [
    "canonical_key_words", "key_words_for", "slot_ids_from_words",
    "build_probe",
    "slot_segment_ids", "check_slot_overflow", "overflow_extended",
    "sortfree_enabled", "sortfree_result", "provide_slots",
    "provided_slots", "slot_build_count", "distinct_count_sketch",
    "adaptive_expand", "adaptive_enabled", "probe_rounds",
    "SlotState", "fresh_slot_state", "slot_ids_extend",
    "slot_state_build",
]


def sortfree_enabled() -> bool:
    """Kill switch for the sort-free grouped route (default: on).  The
    route additionally requires a declared dense group bound and an
    order-insensitive call — this only gates the dispatch.
    ``REPRO_GROUPAGG_SORTFREE=off`` forces every grouped call back onto
    the sorted route."""
    return flags.enabled("REPRO_GROUPAGG_SORTFREE")


# ---------------------------------------------------------------------------
# Canonical key words: every key column becomes 1–2 uint32 words whose
# bitwise equality coincides with group equality
# ---------------------------------------------------------------------------


def canonical_key_words(col: jax.Array) -> tuple[jax.Array, ...]:
    """Lower one key column to uint32 words with group-equality semantics:
    equal keys ⇒ equal words, distinct keys ⇒ distinct words (exactly —
    no narrowing cast is ever taken, so wide-int/f64 keys slot exactly
    where the f32 kernel arg path cannot).  Floats normalize −0.0 to
    +0.0 first; 64-bit dtypes split into (hi, lo) words."""
    col = jnp.asarray(col)
    d = jnp.dtype(col.dtype)
    if d == jnp.bool_:
        return (col.astype(jnp.uint32),)
    if jnp.issubdtype(d, jnp.unsignedinteger):
        if d.itemsize <= 4:
            return (col.astype(jnp.uint32),)
        return ((col >> 32).astype(jnp.uint32), col.astype(jnp.uint32))
    if jnp.issubdtype(d, jnp.integer):
        if d.itemsize <= 4:
            return (lax.bitcast_convert_type(col.astype(jnp.int32),
                                             jnp.uint32),)
        u = lax.bitcast_convert_type(col, jnp.uint64)
        return ((u >> jnp.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32))
    if jnp.issubdtype(d, jnp.floating):
        if d.itemsize <= 4:
            f = col.astype(jnp.float32)          # f16/bf16 embed exactly
            f = jnp.where(f == 0, jnp.float32(0.0), f)
            return (lax.bitcast_convert_type(f, jnp.uint32),)
        f = jnp.where(col == 0, jnp.zeros((), d), col)
        u = lax.bitcast_convert_type(f, jnp.uint64)
        return ((u >> jnp.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32))
    raise TypeError(f"unhashable group-key dtype {d} (expected bool, "
                    "integer, or floating)")


def key_words_for(columns: Iterable[jax.Array]) -> jax.Array:
    """Stack the canonical words of every key column into one (N, K)
    uint32 matrix — the unit the slotting, the hash, and the sharded
    key-table exchange all operate on."""
    words: list[jax.Array] = []
    for c in columns:
        words.extend(canonical_key_words(c))
    return jnp.stack(words, axis=1)


# ---------------------------------------------------------------------------
# Hash + probe loop
# ---------------------------------------------------------------------------


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def _hash_words(words: jax.Array) -> jax.Array:
    """murmur3-style mix of the (N, K) word matrix into one uint32 hash
    per row (uint32 arithmetic wraps in XLA, which is the point)."""
    h = jnp.full(words.shape[:1], 0x9E3779B9, jnp.uint32)
    for k in range(words.shape[1]):
        w = words[:, k] * jnp.uint32(0xCC9E2D51)
        w = _rotl(w, 15) * jnp.uint32(0x1B873593)
        h = _rotl(h ^ w, 13) * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    h ^= jnp.uint32(words.shape[1])
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


#: probe-table expansion: the hash table has ``EXPAND × bucket`` slots,
#: bounding the load factor at 1/EXPAND by construction — probing stays a
#: couple of rounds even when the key set fills the declared bucket
#: exactly (a full table would otherwise probe O(√bucket) rounds, each an
#: O(N) scatter).  The table is scratch: occupied probe slots densify to
#: ``[0, bucket)`` by prefix-sum before anything segment-sized is built,
#: so the moment tensors never see the expansion.  This is the *ceiling*:
#: eager builds shrink it adaptively from the distinct-count sketch
#: (``adaptive_expand``) — the estimated key count, not the worst case,
#: sizes the scatter table each probe round touches.
EXPAND = 16

#: adaptive sizing targets this load factor: estimated distinct keys /
#: probe-table slots ≤ 1/8, so probing still terminates in a couple of
#: rounds even when the sketch undershoots by 2×
_TARGET_LOAD_INV = 8

#: floor on the adaptive expansion: the sketch is noisy and the probe
#: table must stay comfortably larger than the true key set (correctness
#: never depends on it — probing is exhaustive over the table and the
#: dense renumbering validates the bucket — but load > 1/2 costs rounds)
_MIN_EXPAND = 4


def adaptive_enabled() -> bool:
    """Kill switch for sketch-driven probe-table sizing (default: on).
    ``REPRO_KEYSLOT_ADAPTIVE=off`` pins the fixed ``EXPAND`` ceiling."""
    return flags.enabled("REPRO_KEYSLOT_ADAPTIVE")


def adaptive_expand(est_distinct: int, bucket: int) -> int:
    """Probe-table expansion factor from a distinct-count estimate: the
    smallest power of two keeping the estimated load factor at or below
    ``1/_TARGET_LOAD_INV``, clamped to ``[_MIN_EXPAND, EXPAND]``.  With
    the fixed ceiling a 128-slot key set probing a 4096-bucket table paid
    a 65536-slot scatter per round; the sketch sizes that table by the
    keys actually present instead (ROADMAP carried item)."""
    need = _TARGET_LOAD_INV * max(1, int(est_distinct))
    e = 1
    while e * bucket < need and e < EXPAND:
        e <<= 1
    return max(_MIN_EXPAND, min(EXPAND, e))


def slot_ids_from_words(words: jax.Array, valid: jax.Array,
                        bucket: int, expand: int = EXPAND,
                        ) -> tuple[jax.Array, jax.Array,
                                   jax.Array, jax.Array]:
    """Assign each valid row a dense slot in ``[0, bucket)`` keyed by its
    canonical word tuple.  Returns ``(seg, owner, occupied, overflowed)``:

    * ``seg``        (N,)      int32 — the slot; invalid rows AND rows
                     whose key exceeded the bucket (more distinct keys
                     than slots) hold ``bucket``, the overflow slot;
    * ``owner``      (bucket,) int32 — the representative row index that
                     claimed each slot (``N`` where the slot is empty);
    * ``occupied``   (bucket,) bool  — which slots hold a real group (a
                     dense prefix: slot numbers are claim-order);
    * ``overflowed`` ()        int32 — valid rows parked in the overflow
                     slot; nonzero means the key set overflowed the
                     bucket (``check_slot_overflow`` validates it).

    Probe round ``p`` of a row with hash ``h`` tries probe-table slot
    ``(h + p(p+1)/2) mod M`` (``M = EXPAND × bucket``; triangular
    increments visit every slot of a power-of-two table, so ``M`` rounds
    are exhaustive): empty slots are claimed by the smallest contending
    row index (scatter-min), then every prober compares its key words
    against the slot owner's — equal places, different probes on.  A
    claim winner always places on its own claim, so every non-empty slot
    is owned by a row of the key that lives there; hash collisions cost
    extra rounds, never wrong slots.  The sparse probe slots then
    renumber densely by a prefix sum over the occupancy mask — keys
    beyond the first ``bucket`` (overflow) park with the invalid rows.
    """
    if bucket & (bucket - 1) or bucket <= 0:
        raise ValueError(f"bucket must be a positive power of two, got "
                         f"{bucket}")
    if expand & (expand - 1) or expand <= 0:
        raise ValueError(f"expand must be a positive power of two, got "
                         f"{expand}")
    words = jnp.asarray(words)
    n = words.shape[0]
    m = bucket * expand
    h = _hash_words(words)
    idx = jnp.arange(n, dtype=jnp.int32)
    mask = jnp.uint32(m - 1)
    valid = jnp.asarray(valid, bool)

    def cond(st):
        _tbl, _slot, active, rnd = st
        return (rnd < m) & jnp.any(active)

    def body(st):
        # every still-active row has probed exactly `rnd` times, so the
        # probe counter IS the round counter — no per-row carry needed
        tbl, slot, active, rnd = st
        p = rnd.astype(jnp.uint32)
        cand = ((h + (p * (p + 1)) // 2) & mask).astype(jnp.int32)
        claim = jnp.full((m,), n, jnp.int32).at[cand].min(
            jnp.where(active, idx, n), mode="promise_in_bounds")
        tbl = jnp.where(tbl == n, claim, tbl)
        own = jnp.take(tbl, cand, mode="clip")
        ow = jnp.take(words, jnp.clip(own, 0, max(n - 1, 0)), axis=0,
                      mode="clip")
        eq = (own < n) & jnp.all(ow == words, axis=1)
        slot = jnp.where(active & eq, cand, slot)
        active = active & ~eq
        return tbl, slot, active, rnd + 1

    st0 = (jnp.full((m,), n, jnp.int32),
           jnp.full((n,), m, jnp.int32), valid, jnp.int32(0))
    tbl, slot, active, _rnd = lax.while_loop(cond, body, st0)
    if not isinstance(_rnd, jax.core.Tracer):
        global _LAST_ROUNDS
        _LAST_ROUNDS = int(_rnd)

    # densify: occupied probe slots renumber to [0, #groups) in slot
    # order; groups past the bucket (and probe-exhausted rows, possible
    # only when distinct keys exceed M ≥ bucket) overflow
    occ_m = tbl < n
    dense = jnp.cumsum(occ_m.astype(jnp.int32)) - 1
    d = jnp.take(dense, jnp.clip(slot, 0, m - 1), mode="clip")
    placed = ~active & valid & (d < bucket)
    seg = jnp.where(placed, d, bucket).astype(jnp.int32)
    owner = jnp.full((bucket,), n, jnp.int32).at[
        jnp.where(occ_m & (dense < bucket), dense, bucket)].set(
        tbl, mode="drop")
    occupied = jnp.arange(bucket) < jnp.minimum(dense[-1] + 1, bucket)
    overflowed = jnp.sum((valid & (seg == bucket)).astype(jnp.int32))
    return seg, owner, occupied, overflowed


# ---------------------------------------------------------------------------
# Incremental slotting: extend a resident assignment with a micro-batch.
#
# ``slot_ids_from_words`` is one-shot — its probe table is scratch, so a
# serving layer folding micro-batches would re-probe *history* on every
# arrival.  The stateful variant below keeps the probe table and a dense
# key table resident: ``fresh_slot_state`` allocates them,
# ``slot_ids_extend`` slots ONE batch against them (O(batch) work — the
# loop's scatters are table-sized but the per-round elementwise work is
# batch-sized, and history rows are never touched), and the returned
# state carries the union key set for the next batch.  Dense ids are
# *claim order across calls*: resident keys keep their ids forever
# (appends never renumber), new keys take the next ids.
# ---------------------------------------------------------------------------


class SlotState:
    """Resident slotting state: ``tbl`` (bucket×expand,) int32 maps probe
    slots to dense ids (−1 empty), ``ktab`` (bucket, K) uint32 holds each
    dense id's canonical key words, ``cnt`` is the number of dense ids
    assigned.  Treat as immutable — ``slot_ids_extend`` returns a new
    one.  A state whose extend reported ``overflowed > 0`` is NOT
    reusable for further extends: overflow keys' scratch claims are
    scrubbed to holes that sit on other keys' probe paths — the caller
    must grow the bucket and rebuild (the serving layer's
    double-and-retry does exactly this)."""

    __slots__ = ("tbl", "ktab", "cnt", "bucket", "expand")

    def __init__(self, tbl, ktab, cnt, bucket: int, expand: int):
        self.tbl = tbl
        self.ktab = ktab
        self.cnt = cnt
        self.bucket = int(bucket)
        self.expand = int(expand)


def fresh_slot_state(num_words: int, bucket: int,
                     expand: int = EXPAND) -> SlotState:
    """An empty resident slotting state for ``num_words``-word keys over a
    ``bucket``-slot dense range (same power-of-two constraints as
    ``slot_ids_from_words``)."""
    if bucket & (bucket - 1) or bucket <= 0:
        raise ValueError(f"bucket must be a positive power of two, got "
                         f"{bucket}")
    if expand & (expand - 1) or expand <= 0:
        raise ValueError(f"expand must be a positive power of two, got "
                         f"{expand}")
    m = bucket * expand
    return SlotState(jnp.full((m,), -1, jnp.int32),
                     jnp.zeros((bucket, num_words), jnp.uint32),
                     jnp.int32(0), bucket, expand)


def slot_ids_extend(words: jax.Array, valid: jax.Array,
                    state: SlotState,
                    ) -> tuple[jax.Array, jax.Array, jax.Array, SlotState]:
    """Slot one micro-batch against a resident assignment.  Returns
    ``(seg, new_owner, overflowed, new_state)``:

    * ``seg``        (N,)      int32 — dense slot per batch row (resident
                     keys resolve to their existing id, new keys claim the
                     next ids); invalid and overflowed rows hold
                     ``bucket``;
    * ``new_owner``  (bucket,) int32 — the *batch-local* row index that
                     claimed each newly assigned slot this call (``N``
                     everywhere else, including slots owned by earlier
                     calls) — the caller globalizes it with the batch
                     rows' table positions and merges into its resident
                     representative table;
    * ``overflowed`` ()        int32 — valid batch rows whose key found
                     no dense slot (the union key set outgrew the
                     bucket); nonzero also poisons ``new_state`` (see
                     ``SlotState``);
    * ``new_state``  — the state extended with this batch's keys.

    The probe loop is ``slot_ids_from_words``'s claim/verify round with
    the densifying prefix sum replaced by direct dense-id claims: a
    winner writes ``cnt + rank`` (rank = its order among this round's
    winners) into the probe table and its key words into the key table,
    so every later prober — this round or next month's batch — resolves
    by key-word equality against the id's recorded words.  A winner
    always places on its own claim, so every probe slot a placed key
    stepped over is occupied at call end: probe paths stay consistent
    across calls (absent overflow).
    """
    return _slot_extend(words, valid, state, "keyslot.extend")


def _slot_extend(words, valid, state: SlotState, scope: str):
    """``slot_ids_extend`` with the device ops named ``scope`` in the
    trace (a build and an extension run the same probe loop)."""
    bucket, expand = state.bucket, state.expand
    words = jnp.asarray(words)
    if state.ktab.shape[1] != words.shape[1]:
        raise ValueError(
            f"key-word arity changed: state has {state.ktab.shape[1]} "
            f"words, batch has {words.shape[1]}")
    seg, new_owner, overflowed, tbl, ktab, cnt = _extend_probe(
        words, jnp.asarray(valid, bool), jnp.asarray(state.tbl),
        jnp.asarray(state.ktab), jnp.asarray(state.cnt, jnp.int32),
        bucket=bucket, expand=expand, scope=scope)
    return seg, new_owner, overflowed, SlotState(tbl, ktab, cnt,
                                                 bucket, expand)


@partial(jax.jit, static_argnames=("bucket", "expand", "scope"))
def _extend_probe(words, valid, state_tbl, state_ktab, state_cnt, *,
                  bucket: int, expand: int, scope: str):
    # jitted per (batch shape, bucket, expand): the probe while_loop is
    # traced once per shape instead of on every eager call — sustained
    # ingest folds hit this thousands of times.  The scope is named
    # inside the jit: a scope around an eager call does not reach it.
    with jax.named_scope(scope):
        m = bucket * expand
        n, k = words.shape
        h = _hash_words(words)
        idx = jnp.arange(n, dtype=jnp.int32)
        mask = jnp.uint32(m - 1)
        scratch_rows = bucket + n          # overflow claims park past bucket
        ktab_s = jnp.concatenate(
            [state_ktab, jnp.zeros((n, k), jnp.uint32)], axis=0)

        def cond(st):
            _t, _k, _o, _c, _s, active, rnd = st
            return (rnd < m) & jnp.any(active)

        def body(st):
            tbl, ktab, own_arr, cnt, slot, active, rnd = st
            p = rnd.astype(jnp.uint32)
            cand = ((h + (p * (p + 1)) // 2) & mask).astype(jnp.int32)
            empty = jnp.take(tbl, cand, mode="clip") < 0
            claim = jnp.full((m,), n, jnp.int32).at[cand].min(
                jnp.where(active & empty, idx, n), mode="promise_in_bounds")
            winner = active & empty & (jnp.take(claim, cand,
                                                mode="clip") == idx)
            rank = jnp.cumsum(winner.astype(jnp.int32)) - 1
            newid = cnt + rank
            tbl = tbl.at[jnp.where(winner, cand, m)].set(newid, mode="drop")
            ktab = ktab.at[jnp.where(winner, newid, scratch_rows)].set(
                words, mode="drop")
            own_arr = own_arr.at[jnp.where(winner, newid, bucket)].set(
                idx, mode="drop")
            cnt = cnt + jnp.sum(winner.astype(jnp.int32))
            own = jnp.take(tbl, cand, mode="clip")
            ow = jnp.take(ktab, jnp.clip(own, 0, scratch_rows - 1), axis=0,
                          mode="clip")
            eq = (own >= 0) & jnp.all(ow == words, axis=1)
            slot = jnp.where(active & eq, own, slot)
            active = active & ~eq
            return tbl, ktab, own_arr, cnt, slot, active, rnd + 1

        st0 = (state_tbl, ktab_s,
               jnp.full((bucket,), n, jnp.int32),
               state_cnt,
               jnp.full((n,), scratch_rows, jnp.int32), valid, jnp.int32(0))
        tbl, ktab_s, new_owner, cnt, slot, active, _rnd = lax.while_loop(
            cond, body, st0)

        placed = ~active & valid & (slot < bucket)
        seg = jnp.where(placed, slot, bucket).astype(jnp.int32)
        overflowed = jnp.sum((valid & (seg == bucket)).astype(jnp.int32))
        # overflow keys claimed scratch ids ≥ bucket; scrub those probe slots
        # (holes — hence the no-extend-after-overflow contract above)
        tbl = jnp.where(tbl >= bucket, jnp.int32(-1), tbl)
        return (seg, new_owner, overflowed, tbl, ktab_s[:bucket],
                jnp.minimum(cnt, bucket))


def slot_state_build(table, keys: Iterable[str], bucket: int,
                     expand: Optional[int] = None):
    """Full stateful build: slot every row of ``table`` from a fresh
    state — the seeding counterpart of ``slot_segment_ids`` for callers
    that will keep extending (the serving layer's append path).  Counts
    as a slot *build* (bumps the build counter, sized adaptively from
    the distinct sketch like the one-shot path); the serving layer
    counts the extensions that follow (``ServeStats.slot_extends``).
    Returns ``(seg, owner, overflowed, state)`` with ``owner`` already
    table-global (a fresh build's batch IS the table)."""
    keys = tuple(keys)
    global _SLOT_BUILDS
    _SLOT_BUILDS += 1
    with jax.named_scope("keyslot.build"):
        words = key_words_for(table.columns[k] for k in keys)
        mask = table.mask()
        if expand is None:
            expand = EXPAND
            if (adaptive_enabled()
                    and not isinstance(words, jax.core.Tracer)
                    and not isinstance(mask, jax.core.Tracer)):
                expand = adaptive_expand(
                    distinct_count_sketch(table, keys), bucket)
        state = fresh_slot_state(words.shape[1], bucket, expand)
        return _slot_extend(words, mask, state, "keyslot.build")


#: build-side probe-table expansion for ``build_probe``: the table holds
#: the next power of two ≥ 4 × build rows, bounding the load factor at
#: 1/4 — and since slots ≥ rows ≥ distinct keys, every build key is
#: guaranteed a slot (no overflow state, unlike the bucket-bounded
#: ``slot_ids_from_words``)
_JOIN_EXPAND = 4


def _probe_table_size(n_build: int) -> int:
    need = max(8, _JOIN_EXPAND * max(1, n_build))
    return 1 << (need - 1).bit_length()


def build_probe(build_words: jax.Array, build_valid: jax.Array,
                probe_words: jax.Array,
                probe_valid: Optional[jax.Array] = None,
                ) -> tuple[jax.Array, jax.Array]:
    """Hash-join lookup on canonical key words: build an open-addressing
    table over the build-side rows, then resolve each probe row to the
    matching build row with one lockstep probe walk.  Returns
    ``(ridx, found)``:

    * ``ridx``  (Np,) int32 — build-row index whose key words equal the
      probe row's (``Nb``, the build row count, where no match exists —
      a clip-safe sentinel);
    * ``found`` (Np,) bool  — probe rows with a valid-build-row match.

    The build loop is ``slot_ids_from_words``'s claim/verify round
    (scatter-min claims, full key-word equality verification) minus the
    densifying renumber — the raw probe table IS the product here.
    Duplicate build keys probe in lockstep (equal words ⇒ equal hash), so
    the scatter-min deterministically awards their shared slot to the
    *smallest* valid build-row index — exactly the stable pick the
    sorted-route join made via ``argsort(stable=True)`` + leftmost
    ``searchsorted``.  The probe walk stops at key equality or at the
    first *empty* slot: any slot a placed build key stepped over was
    contended that round (the key's own rows were active claimants), so
    it is occupied at build end — first-empty is a sound miss proof.
    Probing terminates within ``M`` rounds unconditionally (triangular
    increments are exhaustive on a power-of-two table); the ≤ 1/4 load
    bound keeps real walks to a couple of rounds.

    Equality is bitwise on canonical words (NaN matches NaN per bit
    pattern, −0.0 matches +0.0): *join* routes that need SQL value
    equality mask NaN keys out of ``found`` at the call site.
    """
    build_words = jnp.asarray(build_words)
    probe_words = jnp.asarray(probe_words)
    nb = build_words.shape[0]
    npr = probe_words.shape[0]
    pvalid = (jnp.ones((npr,), bool) if probe_valid is None
              else jnp.asarray(probe_valid, bool))
    if nb == 0:
        return (jnp.zeros((npr,), jnp.int32),
                jnp.zeros((npr,), bool))
    m = _probe_table_size(nb)
    mask = jnp.uint32(m - 1)
    bvalid = jnp.asarray(build_valid, bool)
    hb = _hash_words(build_words)
    idx = jnp.arange(nb, dtype=jnp.int32)

    def bcond(st):
        _tbl, active, rnd = st
        return (rnd < m) & jnp.any(active)

    def bbody(st):
        tbl, active, rnd = st
        p = rnd.astype(jnp.uint32)
        cand = ((hb + (p * (p + 1)) // 2) & mask).astype(jnp.int32)
        claim = jnp.full((m,), nb, jnp.int32).at[cand].min(
            jnp.where(active, idx, nb), mode="promise_in_bounds")
        tbl = jnp.where(tbl == nb, claim, tbl)
        own = jnp.take(tbl, cand, mode="clip")
        ow = jnp.take(build_words, jnp.clip(own, 0, nb - 1), axis=0,
                      mode="clip")
        eq = (own < nb) & jnp.all(ow == build_words, axis=1)
        active = active & ~eq
        return tbl, active, rnd + 1

    tbl, _active, _rnd = lax.while_loop(
        bcond, bbody,
        (jnp.full((m,), nb, jnp.int32), bvalid, jnp.int32(0)))

    with jax.named_scope("keyslot.probe"):
        hp = _hash_words(probe_words)

        def pcond(st):
            _ridx, _found, active, rnd = st
            return (rnd < m) & jnp.any(active)

        def pbody(st):
            ridx, found, active, rnd = st
            p = rnd.astype(jnp.uint32)
            cand = ((hp + (p * (p + 1)) // 2) & mask).astype(jnp.int32)
            own = jnp.take(tbl, cand, mode="clip")
            empty = own >= nb
            ow = jnp.take(build_words, jnp.clip(own, 0, nb - 1), axis=0,
                          mode="clip")
            eq = ~empty & jnp.all(ow == probe_words, axis=1)
            hit = active & eq
            ridx = jnp.where(hit, own, ridx)
            found = found | hit
            active = active & ~eq & ~empty
            return ridx, found, active, rnd + 1

        ridx, found, _a, _r = lax.while_loop(
            pcond, pbody,
            (jnp.full((npr,), nb, jnp.int32), jnp.zeros((npr,), bool),
             pvalid, jnp.int32(0)))
        return ridx, found


# ---------------------------------------------------------------------------
# Slot-table reuse: a serving layer (or any caller that amortizes the
# probe loop across repeated calls) can compute the four slot arrays once
# per (table version, key set, bucket) and *provide* them for the scope of
# an execution — ``slot_segment_ids`` then returns the provided arrays
# instead of re-probing.  The override is thread-local (concurrent server
# executions don't see each other's tables) and keyed by
# ``(key-name tuple, bucket)``; the provider owns the harder invariant
# that the arrays were built from the table being executed (the serving
# layer keys its cache by ``Table.version`` for exactly this).  Builds
# that actually run the probe loop bump a module counter — the spy tests
# and the serving bench use it to assert slotting amortized to zero.
# ---------------------------------------------------------------------------

_SLOT_BUILDS = 0
_LAST_ROUNDS = None
_PROVIDED = threading.local()


def slot_build_count() -> int:
    """Number of times the probe loop was actually built (eager call or
    jit trace) since import — provided slots don't count.  Monotonic;
    callers diff it around a region to assert slotting was cached."""
    return _SLOT_BUILDS


def probe_rounds():
    """Probe rounds the most recent *eager* ``slot_ids_from_words`` ran
    (None before any eager build; traced builds don't record — the count
    is a tracer there).  The adaptive-sizing regression test pins this:
    shrinking the probe table must not send the round count past a
    handful even at the sketch's target load factor."""
    return _LAST_ROUNDS


def provided_slots(keys, bucket: int):
    """The slot arrays provided for ``(keys, bucket)`` by an enclosing
    ``provide_slots`` scope, or None."""
    stack = getattr(_PROVIDED, "stack", None)
    if not stack:
        return None
    k = (tuple(keys), int(bucket))
    for mapping in reversed(stack):
        got = mapping.get(k)
        if got is not None:
            return got
    return None


@contextmanager
def provide_slots(mapping: Mapping):
    """Provide precomputed slot arrays for the dynamic extent of the
    context: ``mapping`` maps ``(key-name tuple, bucket)`` to the
    ``(seg, owner, occupied, overflowed)`` tuple ``slot_ids_from_words``
    returned for the table about to be executed.  Nested scopes stack;
    inner providers win."""
    norm = {(tuple(k), int(b)): tuple(v) for (k, b), v in mapping.items()}
    stack = getattr(_PROVIDED, "stack", None)
    if stack is None:
        stack = _PROVIDED.stack = []
    stack.append(norm)
    try:
        yield
    finally:
        stack.pop()


def slot_segment_ids(table, keys: Iterable[str], bucket: int):
    """``slot_ids_from_words`` over a Table's group-key columns and row
    mask — the sort-free counterpart of ``engine.segment_ids_for`` (same
    overflow-parking convention; representative rows come from ``owner``
    instead of segment starts, validity from ``occupied`` instead of a
    dense prefix).  An enclosing ``provide_slots`` scope short-circuits
    the probe loop with its cached arrays."""
    keys = tuple(keys)
    pre = provided_slots(keys, bucket)
    if pre is not None:
        return pre
    global _SLOT_BUILDS
    _SLOT_BUILDS += 1
    words = key_words_for(table.columns[k] for k in keys)
    mask = table.mask()
    expand = EXPAND
    if (adaptive_enabled()
            and not isinstance(words, jax.core.Tracer)
            and not isinstance(mask, jax.core.Tracer)):
        # eager build: size the probe table by the keys actually present
        # (sketch ~ one O(N) pass) instead of the worst-case ceiling.
        # Correctness never rides on the estimate — any key set within
        # the bucket fits (the table keeps ≥ _MIN_EXPAND × bucket slots)
        # and the dense renumbering still validates the bucket itself.
        expand = adaptive_expand(distinct_count_sketch(table, keys),
                                 bucket)
    return slot_ids_from_words(words, mask, bucket, expand)


def distinct_count_sketch(table, keys: Iterable[str],
                          m: int = 4096) -> int:
    """Linear-counting estimate of the table's distinct group-key tuples —
    the sketch the serving layer uses to infer ``max_groups`` when no
    dense bound was declared (ROADMAP carried item).  One O(N) pass: the
    canonical key words hash (the same murmur-mix slotting probes with)
    into an ``m``-bucket occupancy bitmap; ``d̂ = -m·ln(1 - b/m)`` for
    ``b`` occupied buckets.  Concrete (blocks on the device value);
    clamped to ``[1, #valid rows]``, and a saturated bitmap degrades to
    the valid-row count — an over-, never under-, estimate there.  The
    estimate itself can undershoot by its sampling error, so callers pad
    it and *validate* the resulting bound (the slot build raises on
    overflow) rather than trusting it."""
    words = key_words_for(table.columns[k] for k in keys)
    valid = jnp.asarray(table.mask(), bool)
    nvalid = int(jnp.sum(valid.astype(jnp.int32)))
    if nvalid == 0:
        return 1
    h = (_hash_words(words) & jnp.uint32(m - 1)).astype(jnp.int32)
    occ = jnp.zeros((m,), jnp.int32).at[
        jnp.where(valid, h, m)].max(1, mode="drop")
    b = int(jnp.sum(occ))
    if b >= m:
        est = nvalid
    else:
        est = max(1, min(nvalid, int(math.ceil(-m * math.log(1.0 - b / m)))))
    if faults.fire("sketch_undershoot"):
        est = max(1, est // 8)
    return est


def overflow_extended(owner: jax.Array, occupied: jax.Array,
                      capacity: int) -> tuple[jax.Array, jax.Array]:
    """Extend the (bucket,)-sized ``owner``/``occupied`` tables with the
    overflow slot, giving the ``num_segments``-sized representative-row
    and output-validity arrays the grouped executors build their result
    Table from: the overflow slot is never a real group (valid False)
    and its representative parks at ``capacity`` (callers clip before
    gathering key values).  One place owns this convention so the
    engine's GroupAgg and the executors' grouped AggCall cannot
    diverge."""
    rep = jnp.concatenate([owner, jnp.full((1,), capacity, jnp.int32)])
    out_valid = jnp.concatenate([occupied, jnp.zeros((1,), bool)])
    return rep, out_valid


def sortfree_result(table, keys: Iterable[str], rep: jax.Array,
                    out_valid: jax.Array, unplaced, bucket: int,
                    agg_cols: dict):
    """Assemble the sort-free grouped result Table — the ONE epilogue
    both grouped executors (engine ``GroupAgg`` and the executors'
    grouped ``AggCall``) share, so the overflow/representative
    convention cannot diverge between them: validate the overflow count
    (concrete raise / traced poison guard), gather one representative
    row of key values per slot (``rep`` already carries the overflow
    sentinel; clipped before the take), and stamp the claim-order
    validity mask."""
    from .group_bound import poison_overflow
    from .table import Table
    overflow_ok = check_slot_overflow(unplaced, bucket)
    cap = table.capacity
    safe_rep = jnp.clip(rep, 0, cap - 1)
    cols = {k: jnp.take(table.columns[k], safe_rep) for k in keys}
    cols.update(agg_cols)
    return Table(poison_overflow(cols, overflow_ok), out_valid)


def check_slot_overflow(unplaced, bucket: int):
    """Validate that every valid row found a real slot — the sort-free
    face of the dense-bound validation
    (``group_bound.check_group_overflow``): valid rows land in the
    overflow slot exactly when the input carries more distinct keys than
    the declared bucket.  Concrete counts raise eagerly; traced counts
    return the ``ok`` guard the caller feeds to ``poison_overflow``;
    ``None`` means the bound held."""
    if isinstance(unplaced, jax.core.Tracer):
        return unplaced == 0
    if int(unplaced) > 0:
        raise GroupBoundOverflow(
            f"sort-free grouped aggregation: {int(unplaced)} rows carry "
            f"group keys beyond the declared dense bound ({bucket} slots; "
            f"max_groups bucketed to the next power-of-two lane multiple) "
            f"— raise max_groups or drop the declaration")
    return None
