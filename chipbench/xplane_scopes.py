"""The named scope of each device op in a JAX profiler trace.

On a TPU the profiler records, with each XLA op's event metadata, the
op's HLO ``op_name`` (stat ``tf_op``, written ``<op_name>:<op_type>``),
the path of ``jax.named_scope``s and transforms it was traced under:
``jit(run)/vmap(sort_gather)/jit(_take)/gather``.  ``ProfileData`` gives
the events but not the stats of their metadata, so this module reads
those from the ``.xplane.pb`` itself, an ``XSpace`` protobuf decoded from
its wire format with the field numbers of the profiler's
``xplane.proto``.  Only the device planes are decoded; the others are
skipped whole.
"""
from __future__ import annotations

import re
from typing import Iterator, Optional

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_META_ID = 1
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_META_NAME = 2
_MAP_KEY, _MAP_VALUE = 1, 2

#: the stat that holds an op's HLO ``op_name``
OP_NAME_STAT = "tf_op"

#: path components JAX itself writes into an ``op_name`` for control
#: flow: not scopes a program named
_STRUCTURE = re.compile(r"(while|body|cond|branch_\d+_fun|closed_call"
                        r"|shard_map)$")
#: transforms whose parentheses hold a function's name, not scopes
_CALLS = ("jit", "pjit")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf) -> Iterator[tuple]:
    """(field number, value) of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited field, raw bytes for
    a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val


def _map_entry(buf) -> tuple:
    key, value = 0, b""
    for num, val in fields(buf):
        if num == _MAP_KEY:
            key = val
        elif num == _MAP_VALUE:
            value = val
    return key, value


def _split(path: str) -> list:
    """``path`` split at the slashes outside parentheses."""
    out, cur, depth = [], [], 0
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def _scopes(components) -> Iterator[str]:
    for c in components:
        head, paren, inner = c.partition("(")
        if paren:
            if head not in _CALLS and inner.endswith(")"):
                yield from _scopes(_split(inner[:-1]))
        elif c and not _STRUCTURE.match(c):
            yield c


def scope_of(op_name: str) -> Optional[str]:
    """The innermost named scope in an HLO ``op_name`` (a ``tf_op`` stat's
    ``:<op_type>`` suffix is dropped; of the ``;``-joined names of a
    fused op, the first), or None: the last path component is the
    primitive, ``jit(f)`` names a function, a transform such as
    ``vmap(...)`` holds the scopes traced under it, and ``while``,
    ``body`` and the like are JAX's own."""
    if ":" in op_name:
        op_name = op_name.rpartition(":")[0]
    op_name = op_name.partition(";")[0]
    found = list(_scopes(_split(op_name)[:-1]))
    return found[-1] if found else None


def _plane(buf, line_name: str):
    """(plane name, [(event name, scope)] of the line ``line_name``)."""
    name, lines, metas, stat_names = "", [], [], {}
    for num, val in fields(buf):
        if num == _PLANE_NAME:
            name = bytes(val).decode()
            if not name.startswith("/device:"):
                return name, None
        elif num == _PLANE_LINES:
            lines.append(val)
        elif num == _PLANE_EVENT_META:
            metas.append(val)
        elif num == _PLANE_STAT_META:
            sid, meta = _map_entry(val)
            for n2, v2 in fields(meta):
                if n2 == _STAT_META_NAME:
                    stat_names[sid] = bytes(v2).decode()
    events = None
    for line in lines:
        lname, evs = "", []
        for num, val in fields(line):
            if num == _LINE_NAME:
                lname = bytes(val).decode()
            elif num == _LINE_EVENTS:
                evs.append(val)
        if lname == line_name:
            events = evs
            break
    if events is None:
        return name, None
    op_stat = {sid for sid, n in stat_names.items() if n == OP_NAME_STAT}
    named = {}
    for entry in metas:
        mid, meta = _map_entry(entry)
        mname, op_name = "", None
        for num, val in fields(meta):
            if num == _META_NAME:
                mname = bytes(val).decode()
            elif num == _META_STATS:
                stat = dict(fields(val))
                if stat.get(_STAT_META_ID) not in op_stat:
                    continue
                if _STAT_STR in stat:
                    op_name = bytes(stat[_STAT_STR]).decode(errors="replace")
                elif _STAT_REF in stat:
                    op_name = stat_names.get(stat[_STAT_REF])
        named[mid] = (mname, scope_of(op_name) if op_name else None)
    out = []
    for ev in events:
        mid = next((v for num, v in fields(ev) if num == _EVENT_META_ID), 0)
        out.append(named.get(mid, ("", None)))
    return name, out


def line_scopes(path: str, line_name: str) -> dict:
    """device plane name → [(event name, scope)], one pair per event of
    the plane's line ``line_name``, in the order the trace lists them."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in fields(buf):
        if num != _SPACE_PLANES:
            continue
        name, got = _plane(plane, line_name)
        if got is not None:
            out[name] = got
    return out
