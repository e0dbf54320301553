"""The scope path of every device op in a ``jax.profiler`` trace, read
straight from the ``.xplane.pb`` protobuf wire format, with no dependency.

``ProfileData`` gives each device op's name (its HLO instruction text) and
times, but not the op's event metadata, where the profiler keeps the
``tf_op`` stat: the JAX name stack the op was staged under, e.g.
``jit(decode_chunk)/while/body/while/body/closed_call/attn_core/...``.
``scopes(path)`` returns, per device plane, every ``tf_op`` path recorded
for each op name.

Fields read (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes = 1;
XPlane.name = 2, .event_metadata = 4 and .stat_metadata = 5 (maps: key 1,
value 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of a stat
metadata whose name holds the string).  Everything else is skipped by its
length, the event lines included.
"""

from __future__ import annotations

DEVICE_PREFIX = "/device:"
TF_OP = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield num, val


def _map_entry(buf) -> tuple[int, memoryview]:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane_scopes(plane) -> tuple[str, dict[str, list[str]]]:
    name, metas, stat_names = "", [], {}
    for num, val in _fields(plane):
        if num == 2:
            name = bytes(val).decode()
        elif num == 4:
            metas.append(_map_entry(val)[1])
        elif num == 5:
            sid, meta = _map_entry(val)
            stat_names[sid] = bytes(dict(_fields(meta)).get(2, b"")).decode()
    if not name.startswith(DEVICE_PREFIX):
        return name, {}
    tf_op = {sid for sid, n in stat_names.items() if n == TF_OP}
    out: dict[str, list[str]] = {}
    for meta in metas:
        op, path = "", None
        for num, val in _fields(meta):
            if num == 2:
                op = bytes(val).decode()
            elif num == 5:
                stat = dict(_fields(val))
                if stat.get(1) in tf_op:
                    path = (bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        if op and path:
            out.setdefault(op, []).append(path)
    return name, out


def scopes(path: str) -> dict[str, dict[str, list[str]]]:
    """{device plane name: {op name: [tf_op path, ...]}}.  One HLO text can
    run in two programs, hence a list; ``scope_for`` picks one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num == 1:
            name, ops = _plane_scopes(plane)
            if ops:
                out[name] = ops
    return out


def scope_for(paths: list[str], module: str) -> str:
    """The path of an op that ran in the program ``module`` (the XLA
    module's name, "jit_decode_chunk"): where the op's name has paths in
    several programs, the one under ``jit(decode_chunk)``."""
    if len(paths) > 1 and module.startswith("jit_"):
        want = f"jit({module[4:]})"
        for p in paths:
            if p.startswith(want):
                return p
    return paths[0] if paths else ""
