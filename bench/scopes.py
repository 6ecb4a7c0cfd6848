"""Device time by the program's named scopes, from a ``jax.profiler`` trace.

Each device operation's metadata in an ``.xplane.pb`` file carries a
``tf_op`` stat: the operation's name-stack path (``jit(program)/while/body/
closed_call/vmap(psrs.merge)/kway_merge.splitters/...``), where the
program's ``jax.named_scope`` scopes are components, bare or wrapped in a
transform such as ``vmap(...)``.  ``ProfileData`` does not expose metadata
stats, so a small reader of the XSpace protobuf takes them here, one path
per event of each device plane's ops line, in the order
:func:`bench.devtrace.load` gives the events.

    python3 -m bench.scopes <profile dir or .xplane.pb[.gz]>

prints, for the traced job, each scope's device seconds and the share of
the device's busy time that lies under a top-level ``psrs.*`` or
``pems.*`` scope.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys

from bench import devtrace

SCOPES = ("psrs.sort_sample", "psrs.local_sort", "psrs.pick_splitters",
          "psrs.partition", "psrs.merge", "pems.gather", "pems.bcast",
          "pems.alltoallv", "kway_merge.splitters", "kway_merge.gather",
          "kway_merge.tiles")
TOP = ("psrs.sort_sample", "psrs.pick_splitters", "psrs.partition",
       "psrs.merge", "pems.gather", "pems.bcast", "pems.alltoallv")


# A stdlib reader of the XSpace protobuf; field numbers from tsl's
# xplane.proto.

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, value)`` of each field of a message: an int for a
    varint or fixed field, bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield num, val


def _map_entry(buf: bytes):
    """Key and value of a ``map<int64, Message>`` entry."""
    key, val = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _plane_op_scopes(plane: bytes):
    """Name of an XPlane and the ``tf_op`` of each event of its ops line,
    in order (None where the plane has no ops line)."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, v in _fields(plane):
        if num == 2:                          # XPlane.name
            name = v.decode()
        elif num == 3:                        # XPlane.lines
            lines.append(v)
        elif num == 4:                        # XPlane.event_metadata
            key, meta = _map_entry(v)
            event_meta[key] = meta
        elif num == 5:                        # XPlane.stat_metadata
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (x.decode() for n, x in _fields(meta) if n == 2), "")
    tf_op = {k for k, nm in stat_names.items() if nm == "tf_op"}

    def op_path(meta: bytes):
        for num, stat in _fields(meta):
            if num != 5:                      # XEventMetadata.stats
                continue
            fields = dict(_fields(stat))
            if fields.get(1) not in tf_op:    # XStat.metadata_id
                continue
            if 5 in fields:                   # XStat.str_value
                return fields[5].decode()
            if 7 in fields:                   # XStat.ref_value
                return stat_names.get(fields[7])
        return None

    paths = {}
    for line in lines:
        fields = list(_fields(line))
        if not any(n == 2 and v.decode() == devtrace.OPS_LINE
                   for n, v in fields):
            continue
        out = []
        for num, ev in fields:
            if num != 4:                      # XLine.events
                continue
            mid = next((v for n, v in _fields(ev) if n == 1), 0)
            if mid not in paths:
                paths[mid] = op_path(event_meta.get(mid, b""))
            out.append(paths[mid])
        return name, out
    return name, None


def read_op_scopes(raw: bytes) -> dict:
    """Device plane name -> the ``tf_op`` path (or None) of each event of
    its ops line, in the order ``ProfileData`` gives the events."""
    out = {}
    for num, plane in _fields(raw):
        if num != 1:                          # XSpace.planes
            continue
        name, paths = _plane_op_scopes(plane)
        if paths is not None and _is_device_plane(name):
            out[name] = paths
    return out


def load_op_scopes(path: str) -> dict:
    """:func:`read_op_scopes` of an ``.xplane.pb`` file, gzipped or not, or
    of the one under a profile directory."""
    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return read_op_scopes(f.read())


_TRANSFORM = re.compile(r"\w+\((.*)\)")


def in_scope(path, scope: str) -> bool:
    """Whether the name-stack ``path`` has ``scope`` as a component, bare
    (``psrs.merge``) or inside transforms (``vmap(psrs.merge)``).  A fused
    operation's path joins its sources' paths with ``;``."""
    if not path:
        return False
    for comp in re.split(r"[/;]", path):
        comp = comp.split(":", 1)[0]          # "<op>:<type>" at the leaf
        while comp != scope:
            m = _TRANSFORM.fullmatch(comp)
            if m is None:
                break
            comp = m.group(1)
        if comp == scope:
            return True
    return False


def scope_s(prof, op_scopes: dict, scope, lo, hi) -> float:
    """Device seconds in ``[lo, hi)`` under the named scope ``scope`` (or
    under any of a tuple of scopes): the union of the intervals of the
    operations of ``prof`` (a :class:`bench.devtrace.Profile`) whose
    ``tf_op`` path in ``op_scopes`` holds it, averaged over the device
    planes.  An operation with no path counts for no scope."""
    if not prof.ops:
        return 0.0
    names = (scope,) if isinstance(scope, str) else tuple(scope)
    tot = 0.0
    for plane, events in prof.ops.items():
        paths = op_scopes.get(plane, ())
        if len(paths) != len(events):
            raise ValueError(f"{plane}: {len(paths)} tf_op paths for "
                             f"{len(events)} operations")
        hit = [(s, e) for (_, s, e), p in zip(events, paths)
               if any(in_scope(p, nm) for nm in names)]
        tot += sum(e - s for s, e in
                   devtrace.union(devtrace._clip(hit, lo, hi)))
    return tot / len(prof.ops) / 1e9


def summary(path: str) -> dict:
    """The traced job's window, busy time, each of :data:`SCOPES`' device
    seconds, and the busy time under some scope of :data:`TOP`."""
    prof = devtrace.load(path)
    op_scopes = load_op_scopes(path)
    lo, hi = prof.window()
    busy = devtrace.busy_s(prof, lo, hi)
    covered = scope_s(prof, op_scopes, TOP, lo, hi)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "scopes_s": {s: scope_s(prof, op_scopes, s, lo, hi)
                         for s in SCOPES},
            "covered_s": covered,
            "covered_share": covered / busy if busy else None}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
