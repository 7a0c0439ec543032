"""Durable resident-state checkpoints: crash-consistent persistence of
the serving layer's incremental aggregates.

A resident epoch (serve/incremental.py) is expensive state — one full
O(table) seed pass plus every fold since — living only in process
memory.  This module makes it durable with three properties the chaos
battery (tests/test_checkpoint.py) enforces:

* **Atomic visibility** — payload and manifest are written to temp
  files and ``os.replace``d into place, manifest LAST: a crash at any
  byte leaves either the previous complete checkpoint or none, never a
  half-written file a restore could mistake for complete.
* **Verified or refused** — the manifest records a sha256 over the
  payload bytes; restore recomputes it before deserializing anything.
  A torn write (``checkpoint_write`` fault), bit rot
  (``restore_corrupt`` fault), or truncation surfaces as typed
  ``CheckpointCorrupt`` and installs NOTHING — the server falls back to
  recompute, never serves partially-read durable state.
* **Replay past the watermark** — live ``Table.version`` tokens do not
  survive restarts, so the checkpoint captures each epoch's *logical*
  watermark instead: the valid-row mask plus per-column content digests
  of the rows the epoch folded.  ``rehydrate`` proves the live catalog
  table is an append-descendant of that watermark (every checkpointed
  row still present, bit-identical), publishes the recovered epoch
  under a synthetic negative version, and registers the leftover rows
  as one synthetic append step — the server's EXISTING version-chain
  catch-up then folds the suffix through the normal guarded fold path.
  Any mismatch (the table was replaced, a column diverged) quietly
  declines: the residency re-seeds from live data, which is always
  correct, just slower.

Kill switch: ``REPRO_SERVE_CKPT=off`` (checked by the ``AggServer``
entry points) makes ``checkpoint()`` a no-op and ``restore()`` return
0 — snapshots recompute/re-seed exactly as if no checkpoint existed.
"""
from __future__ import annotations

import glob
import hashlib
import io
import json
import os
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.relational import keyslot
from repro.relational.table import Table
from repro.reliability import faults

from . import incremental
from .guard import CheckpointCorrupt

__all__ = ["CheckpointCorrupt", "plan_fingerprint", "write_checkpoint",
           "read_checkpoint", "rehydrate"]

#: manifest format version — bump on any incompatible layout change;
#: restore refuses unknown formats (typed, never a misparse)
FORMAT = 1

_PREFIX = "ckpt-"


def plan_fingerprint(plan, name, keys) -> str:
    """Identity of a resident plan across processes.  ``id(plan)`` dies
    with the process, so checkpoints key on the plan's deterministic
    dataclass ``repr`` (plans are trees of dataclasses over strings,
    ints, and tuples — no memory addresses) plus the catalog table and
    key columns it serves."""
    blob = f"{name}|{tuple(keys)}|{plan!r}".encode()
    return hashlib.sha256(blob).hexdigest()


def _column_digest(table: Table, col: str, mask: np.ndarray) -> str:
    """Content digest of one column's VALID rows at a watermark (dtype
    included — a value-preserving dtype change is still a different
    table)."""
    a = np.asarray(table.columns[col])[: mask.shape[0]][mask]
    return hashlib.sha256(
        str(a.dtype).encode() + b"|" + a.tobytes()).hexdigest()


def _seq_of(path: str) -> int:
    base = os.path.basename(path)
    try:
        return int(base[len(_PREFIX):].split(".")[0])
    except ValueError:
        return -1


# ---------------------------------------------------------------------------
# Write
# ---------------------------------------------------------------------------


def write_checkpoint(server, directory: str) -> Optional[str]:
    """Serialize every published resident epoch of ``server`` (called
    under the server lock) into ``directory``; returns the manifest
    path, or None when nothing is resident.  Files are
    ``ckpt-<seq>.npz`` (one npz payload for all epochs) and
    ``ckpt-<seq>.json`` (the checksummed manifest), ``seq``
    monotonically above any checkpoint already in the directory."""
    picked = []
    for pid, res in server._residents.items():
        ep = res.current_epoch()
        ent = server._plans.get(pid)
        if ep is None or ent is None:
            continue
        picked.append((ent, res, ep))
    if not picked:
        return None
    os.makedirs(directory, exist_ok=True)
    seq = 1 + max(
        [_seq_of(p) for p in glob.glob(
            os.path.join(directory, _PREFIX + "*.json"))] or [0])
    arrays = {}
    recs = []
    catalog = {}
    for i, (ent, res, ep) in enumerate(picked):
        mask = np.asarray(ep.table.mask())
        arrays[f"r{i}__moments"] = np.asarray(ep.moments)
        arrays[f"r{i}__owner"] = np.asarray(ep.owner)
        arrays[f"r{i}__tbl"] = np.asarray(ep.state.tbl)
        arrays[f"r{i}__ktab"] = np.asarray(ep.state.ktab)
        arrays[f"r{i}__cnt"] = np.asarray(ep.state.cnt, np.int32)
        arrays[f"r{i}__mask"] = mask
        pay_names = list(ep.payloads)
        for j, n in enumerate(pay_names):
            arrays[f"r{i}__pay{j}"] = np.asarray(ep.payloads[n])
        recs.append({
            "fingerprint": plan_fingerprint(ent.submitted, res.name,
                                            res.keys),
            "table": res.name,
            "keys": list(res.keys),
            "bound": int(ep.bound),
            "bucket": int(ep.state.bucket),
            "expand": int(ep.state.expand),
            "folds": int(ep.folds),
            "inferred": bool(res.inferred),
            "payload_names": pay_names,
            "capacity": int(mask.shape[0]),
            "valid_rows": int(mask.sum()),
            "columns": {c: _column_digest(ep.table, c, mask)
                        for c in res._needed_cols()},
        })
        catalog.setdefault(res.name, {
            "capacity": int(mask.shape[0]),
            "valid_rows": int(mask.sum()),
            "mask_sha256": hashlib.sha256(mask.tobytes()).hexdigest(),
        })
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    payload = buf.getvalue()
    sha = hashlib.sha256(payload).hexdigest()

    pname = f"{_PREFIX}{seq:06d}.npz"
    ppath = os.path.join(directory, pname)
    tmp = ppath + ".tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        if faults.fire("checkpoint_write"):
            # torn write: the process "died" mid-flush — the bytes on
            # disk are a prefix of the intended payload, but the
            # manifest checksum still names the full content, so a
            # later restore MUST detect the tear
            f.truncate(max(1, len(payload) // 2))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, ppath)

    manifest = {"format": FORMAT, "seq": seq, "payload": pname,
                "payload_sha256": sha, "catalog": catalog,
                "residents": recs}
    mpath = os.path.join(directory, f"{_PREFIX}{seq:06d}.json")
    mtmp = mpath + ".tmp"
    with open(mtmp, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(mtmp, mpath)     # manifest last: its presence IS commit
    return mpath


# ---------------------------------------------------------------------------
# Read
# ---------------------------------------------------------------------------


def read_checkpoint(server, directory: str) -> int:
    """Stage the newest checkpoint of ``directory`` into
    ``server._restored`` (called under the server lock); returns the
    number of resident payloads staged, 0 when the directory holds no
    manifest.  Raises ``CheckpointCorrupt`` — installing nothing — on
    any checksum, format, or deserialization failure."""
    manifests = sorted(glob.glob(os.path.join(directory,
                                              _PREFIX + "*.json")),
                       key=_seq_of)
    if not manifests:
        return 0
    mpath = manifests[-1]
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointCorrupt(
            f"checkpoint manifest unreadable: {e}", path=mpath) from e
    if manifest.get("format") != FORMAT:
        raise CheckpointCorrupt(
            f"checkpoint manifest format {manifest.get('format')!r} is "
            f"not the supported format {FORMAT}", path=mpath)
    ppath = os.path.join(directory, manifest.get("payload", ""))
    try:
        with open(ppath, "rb") as f:
            data = bytearray(f.read())
    except OSError as e:
        raise CheckpointCorrupt(
            f"checkpoint payload unreadable: {e}", path=ppath) from e
    if faults.fire("restore_corrupt") and data:
        data[len(data) // 2] ^= 0xFF       # bit rot on the read path
    sha = hashlib.sha256(bytes(data)).hexdigest()
    if sha != manifest.get("payload_sha256"):
        raise CheckpointCorrupt(
            "checkpoint payload failed its checksum (torn write or bit "
            "rot) — refusing the restore; snapshots will recompute",
            path=ppath)
    try:
        npz = np.load(io.BytesIO(bytes(data)), allow_pickle=False)
    except Exception as e:                   # noqa: BLE001 — typed out
        raise CheckpointCorrupt(
            f"checkpoint payload failed to deserialize: {e}",
            path=ppath) from e
    staged = 0
    try:
        for i, rec in enumerate(manifest.get("residents", ())):
            entry = {
                "rec": rec,
                "moments": npz[f"r{i}__moments"],
                "owner": npz[f"r{i}__owner"],
                "tbl": npz[f"r{i}__tbl"],
                "ktab": npz[f"r{i}__ktab"],
                "cnt": npz[f"r{i}__cnt"],
                "mask": npz[f"r{i}__mask"].astype(bool),
                "pays": [npz[f"r{i}__pay{j}"]
                         for j in range(len(rec["payload_names"]))],
            }
            server._restored[rec["fingerprint"]] = entry
            staged += 1
    except KeyError as e:
        # roll back this read's stagings: all-or-nothing
        for rec in manifest.get("residents", ()):
            server._restored.pop(rec.get("fingerprint"), None)
        raise CheckpointCorrupt(
            f"checkpoint payload is missing array {e} named by the "
            f"manifest", path=ppath) from e
    return staged


# ---------------------------------------------------------------------------
# Rehydrate
# ---------------------------------------------------------------------------


def rehydrate(server, ent):
    """Rebuild a ``ResidentAgg`` for plan entry ``ent`` from a staged
    checkpoint payload (called under the server lock from
    ``AggServer._rehydrate_resident``), or None when no staged payload
    matches or the live table diverged from the watermark.

    Matching is strict — the live table must be an append-descendant of
    the checkpointed watermark (every watermark row still valid, every
    needed column bit-identical over those rows).  On success the epoch
    publishes under a fresh synthetic negative version and the rows the
    live table holds beyond the watermark register as one synthetic
    append step at the bottom of the version chain, so the caller's
    normal catch-up folds them through the existing guarded fold path
    (never a special replay code path)."""
    if ent.slot_scan is None:
        return None
    fp = plan_fingerprint(ent.submitted, ent.slot_scan, ent.keys)
    got = server._restored.get(fp)
    if got is None:
        return None
    rec = got["rec"]
    live = server._catalog.get(rec["table"])
    if live is None:
        return None
    live_mask = np.asarray(live.mask())
    cmask = got["mask"]
    cap = int(cmask.shape[0])
    if cap > live.capacity:
        return None
    padded = np.zeros(live.capacity, bool)
    padded[:cap] = cmask
    if (padded & ~live_mask).any():          # a watermark row vanished
        return None
    for col, digest in rec["columns"].items():
        if col not in live.columns:
            return None
        if _column_digest(live, col, padded) != digest:
            return None
    res = incremental.ResidentAgg.admit(
        ent.plan, rec["table"], tuple(rec["keys"]), live,
        int(rec["bound"]))
    if res is None:
        return None
    res.inferred = bool(rec["inferred"])
    state = keyslot.SlotState(
        jnp.asarray(got["tbl"]), jnp.asarray(got["ktab"]),
        jnp.asarray(got["cnt"]), int(rec["bucket"]), int(rec["expand"]))
    payloads = {n: jnp.asarray(got["pays"][j])
                for j, n in enumerate(rec["payload_names"])}
    wtable = Table(live.columns, jnp.asarray(padded), live.group_bound,
                   row_split=live.row_split)
    server._synth_version -= 1
    synth = server._synth_version
    ep = incremental.Epoch(
        state=state, moments=jnp.asarray(got["moments"]),
        owner=jnp.asarray(got["owner"]), payloads=payloads,
        bound=int(rec["bound"]), version=synth, epoch_id=1,
        folds=int(rec["folds"]), table=wtable)
    res._epoch = ep     # pre-publication: res is not yet visible
    # register the suffix past the watermark as the BOTTOM step of the
    # version chain: rows valid live but not at the watermark, minus any
    # already covered by recorded append steps
    name = rec["table"]
    v = live.version
    chain = []
    while True:
        step = server._appends.get((name, v))
        if step is None:
            break
        v, pos = step
        chain.append(np.asarray(pos))
    extra = np.flatnonzero(live_mask & ~padded)
    if chain:
        extra = np.setdiff1d(extra, np.concatenate(chain))
    server._appends[(name, v)] = (synth, extra.astype(np.int64))
    del server._restored[fp]                 # consumed
    return res
