"""Read the op-name path of each device op from a profiler trace.

XLA keeps, in the metadata of each op event of a device plane, a ``tf_op``
stat: JAX's op-name path of the HLO instruction, with the program's named
scopes in it, e.g.
``jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_bwd/exp:``.
``jax.profiler.ProfileData`` gives the stats of events, not those of their
metadata, so this decodes the few fields of the ``.xplane.pb`` protobuf that
hold them, with the standard library alone (``tensorflow``, whose
``xplane_pb2`` defines them, need not be installed).  It skips each plane's
``lines``, which hold the events, by their length.
"""

from __future__ import annotations

# field numbers, from tensorflow/tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5  # ``lines`` is 3
MAP_VALUE = 2  # a map entry's value; its key is field 1
EVENT_MD_NAME, EVENT_MD_STATS = 2, 5
STAT_MD_ID, STAT_MD_NAME = 1, 2
STAT_METADATA_ID, STAT_STR_VALUE, STAT_REF_VALUE = 1, 5, 7

TF_OP = "tf_op"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span: tuple[int, int]):
    for field, value in _fields(buf, *span):
        if field == MAP_VALUE:
            yield value


def _paths(buf: bytes, span: tuple[int, int]) -> dict:
    """``{event name: tf_op path or ""}`` of the plane in ``buf[span]``."""
    events, stat_names = [], {}
    for field, value in _fields(buf, *span):
        if field == PLANE_EVENT_METADATA:
            events += _map_values(buf, value)
        elif field == PLANE_STAT_METADATA:
            for md in _map_values(buf, value):
                sid, sname = None, ""
                for f, v in _fields(buf, *md):
                    if f == STAT_MD_ID:
                        sid = v
                    elif f == STAT_MD_NAME:
                        sname = _text(buf, v)
                stat_names[sid] = sname
    paths = {}
    for md in events:
        ev_name, path = "", None
        for f, v in _fields(buf, *md):
            if f == EVENT_MD_NAME:
                ev_name = _text(buf, v)
            elif f == EVENT_MD_STATS:
                stat = dict(_fields(buf, *v))
                if stat_names.get(stat.get(STAT_METADATA_ID)) != TF_OP:
                    continue
                if STAT_STR_VALUE in stat:
                    path = _text(buf, stat[STAT_STR_VALUE])
                elif STAT_REF_VALUE in stat:
                    path = stat_names.get(stat[STAT_REF_VALUE], "")
        paths[ev_name] = "" if path is None else strip_type(path)
    return paths


def strip_type(path: str) -> str:
    """``a/b/exp:Exp`` -> ``a/b/exp``: the op's path without its type."""
    head, sep, _ = path.rpartition(":")
    return head if sep else path


def tf_ops(path: str, keep=lambda plane_name: True) -> dict:
    """``{plane name: {event name: tf_op path}}`` for the planes of the
    ``.xplane.pb`` at ``path`` whose name ``keep`` accepts.  An op event
    whose metadata carries no ``tf_op`` (XLA's own copies and async waits)
    has the path ``""``."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, span in _fields(buf, 0, len(buf)):
        if field != SPACE_PLANES:
            continue
        name = ""
        for f, v in _fields(buf, *span):
            if f == PLANE_NAME:
                name = _text(buf, v)
                break
        if keep(name):
            out[name] = _paths(buf, span)
    return out
