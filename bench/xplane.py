"""Read each device operation's ``tf_op`` from a profiler trace
(``.xplane.pb``), and charge the operations' device time to the
program's ``pregel.*`` scopes.

``tf_op`` is JAX's name stack for the operation, such as
``jit(body)/while/body/pregel.combine/scatter-min``: ``jax.named_scope``
adds a component to it.  ``jax.profiler.ProfileData`` gives events their
names and times but not the stats of their metadata, where ``tf_op``
lives, and JAX ships no ``xplane`` protobuf module; so ``tf_ops`` reads
the file's protobuf wire format for the few fields it needs.

    names = tf_ops(path)                         # {plane: {op: tf_op}}
    scopes = scope_seconds(trace.summarize(path), names)
"""
from __future__ import annotations

from typing import Optional

SCOPE_PREFIX = "pregel."
TF_OP = "tf_op"

# Field numbers of tsl/profiler/protobuf/xplane.proto.
XSPACE_PLANES = 1
XPLANE_NAME = 2
XPLANE_EVENT_METADATA = 4       # map<int64, XEventMetadata>
XPLANE_STAT_METADATA = 5        # map<int64, XStatMetadata>
MAP_VALUE = 2
METADATA_ID = 1                 # XEventMetadata.id, XStatMetadata.id
METADATA_NAME = 2               # XEventMetadata.name, XStatMetadata.name
EVENT_METADATA_STATS = 5
STAT_METADATA_ID = 1
STAT_STR_VALUE = 5
STAT_REF_VALUE = 7              # the id of a stat metadata holding the string


def _varint(buf: memoryview, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: memoryview):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-size field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode(errors="replace")


def _plane_tf_ops(plane: memoryview) -> tuple:
    """``(plane name, {event name: tf_op})`` of one XPlane.  Of events
    that share a name, the first that has a ``tf_op`` gives it."""
    name, events, stat_names = "", [], {}
    for field, value in _fields(plane):
        if field == XPLANE_NAME:
            name = _text(value)
        elif field in (XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA):
            entry = dict(_fields(value)).get(MAP_VALUE)
            if entry is None:
                continue
            if field == XPLANE_EVENT_METADATA:
                events.append(entry)
            else:
                meta = dict(_fields(entry))
                stat_names[meta.get(METADATA_ID, 0)] = _text(
                    meta.get(METADATA_NAME, b""))
    tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
    out: dict = {}
    for entry in events:
        event_name, tf_op = None, None
        for field, value in _fields(entry):
            if field == METADATA_NAME:
                event_name = _text(value)
            elif field == EVENT_METADATA_STATS:
                stat = dict(_fields(value))
                if stat.get(STAT_METADATA_ID) not in tf_op_ids:
                    continue
                if STAT_STR_VALUE in stat:
                    tf_op = _text(stat[STAT_STR_VALUE])
                elif STAT_REF_VALUE in stat:
                    tf_op = stat_names.get(stat[STAT_REF_VALUE])
        if event_name is not None and tf_op and event_name not in out:
            out[event_name] = strip_type(tf_op)
    return name, out


def strip_type(tf_op: str) -> str:
    """``tf_op`` is ``<name>:<type>``; the name alone."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def tf_ops(path: str) -> dict:
    """``{plane name: {event name: tf_op}}`` of the trace at ``path``:
    every event whose metadata carries a ``tf_op`` (on a TPU trace, the
    operations of the device planes)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return dict(_plane_tf_ops(plane) for field, plane in _fields(space)
                if field == XSPACE_PLANES)


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost ``pregel.*`` component of a name stack, or None."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def scope_seconds(summary, names: dict) -> dict:
    """``{scope: device seconds}``, averaged over the chips: each
    operation's own time in the window (``summary.ops``, summed over
    the chips) charged to the innermost ``pregel.*`` scope of its
    ``tf_op`` in ``names`` (``tf_ops``'s result)."""
    op_scope = {}
    for plane in names.values():
        for op, tf_op in plane.items():
            op_scope.setdefault(op, scope_of(tf_op))
    out: dict = {}
    for op, seconds in summary.ops.items():
        scope = op_scope.get(op)
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + seconds / summary.n_devices
    return out
