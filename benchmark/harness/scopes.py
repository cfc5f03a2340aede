"""Device operations with the scope each belongs to.

The program names the regions of its decode and prefill programs with
``jax.named_scope`` (``embed``, ``norm``, ``attn/qkv``, ``attn/out``,
``mlp``, ``head``, ``sample``, ``kv_pool/write``).  A scope is metadata
of the compiled operation (the HLO ``op_name``, a ``/``-separated
path), and the profiler hands it on as a stat of each operation's
event.  ``harness/trace.py`` keeps names and times only, so this file
reads the capture itself.

``load_xplane`` reads a profiler capture; ``load_json`` the hand-built
trace the tests keep: the format of ``harness/trace.py`` with one more
key, ``"op_names"``, from an operation's name to its ``op_name``.
Both give the first device's operations and its programs' executions.
"""

from __future__ import annotations

import collections
import json

from benchmark.harness import trace as trace_lib

Op = collections.namedtuple("Op", "name start dur op_name")

#: The stat of an operation's metadata that carries the HLO ``op_name``
#: (XProf's "framework op"), as the v5e's captures name it.
OP_NAME_STAT = "tf_op"

SCOPES = ("embed", "norm", "attn/qkv", "attn/out", "mlp", "head",
          "sample", "kv_pool/write")
#: A program that names its regions names this one; none else does.
MARKER = "kv_pool/write"
KERNEL = "attention kernel"
PLUMBING = "plumbing"


def scope_of(op_name: str):
    """The innermost of ``SCOPES`` on the path ``op_name``, or None."""
    path = "/" + (op_name or "") + "/"
    best = None
    for scope in SCOPES:
        at = path.rfind("/" + scope + "/")
        if at >= 0 and (best is None or at > best[0]):
            best = (at, scope)
    return best and best[1]


def load(where: str) -> tuple:
    """``(ops, programs)`` of a capture's first device: ``Op``s of its
    operations and ``trace.Event``s of its programs' executions.
    ``where`` is a directory a profiler wrote into, or a hand-built
    ``.json``."""
    if where.endswith(".json"):
        return load_json(where)
    return load_xplane(trace_lib.find_xplane(where))


def load_json(path: str) -> tuple:
    with open(path) as f:
        raw = json.load(f)
    names = raw.get("op_names", {})
    device = raw["devices"][0]
    return ([Op(name, start, dur, names.get(name, ""))
             for name, start, dur in device.get("ops", [])],
            [trace_lib.Event(*e) for e in device.get("modules", [])])


# -- the capture's own format -------------------------------------------------
#
# ``jax.profiler.ProfileData`` hands out an event's own stats (its
# offset and duration) and not those of its metadata, where the
# profiler keeps what is the same for every execution of an operation:
# its category, its bytes and ``tf_op``, the HLO ``op_name``.  So the
# file is read here as what it is, a serialized ``XSpace`` protocol
# buffer (tsl/profiler/protobuf/xplane.proto), with the few field
# numbers that are needed.


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    view of the bytes for a length-delimited or fixed-width field."""
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
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane, number: int) -> dict:
    """A ``map<int64, Message>`` field of a plane: {key: message}."""
    return {_first(entry, 1, 0): _first(entry, 2, b"")
            for n, entry in _fields(plane) if n == number}


def load_xplane(path: str) -> tuple:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for n, plane in _fields(space):            # XSpace.planes = 1
        if n == 1:
            planes[_text(_first(plane, 2, b""))] = plane   # XPlane.name
    for name in sorted(planes):
        if name.startswith("/device:"):
            ops, programs = _device_lines(planes[name])
            if ops or programs:
                return ops, programs
    return [], []


def _device_lines(plane) -> tuple:
    # XPlane: lines = 3, event_metadata = 4, stat_metadata = 5.
    stat_names = {key: _text(_first(meta, 2, b""))       # XStatMetadata.name
                  for key, meta in _map_entries(plane, 5).items()}
    metadata = {}
    for key, meta in _map_entries(plane, 4).items():
        name, op_name = "", ""
        for n, value in _fields(meta):         # XEventMetadata
            if n == 2:                         # .name
                name = _text(value)
            elif n == 5:                       # .stats (XStat)
                if stat_names.get(_first(value, 1)) == OP_NAME_STAT:
                    ref = _first(value, 7)     # .ref_value, else .str_value
                    op_name = (stat_names.get(ref, "") if ref is not None
                               else _text(_first(value, 5, b"")))
        # "<op_name>:<op_type>", the type often empty
        metadata[key] = (name, op_name.rsplit(":", 1)[0])
    lines = {}
    for n, line in _fields(plane):
        if n != 3:
            continue
        events, line_name, t0_ns = [], "", 0
        for m, value in _fields(line):         # XLine
            if m == 2:                         # .name
                line_name = _text(value)
            elif m == 3:                       # .timestamp_ns
                t0_ns = value
            elif m == 4:                       # .events (XEvent)
                events.append(value)
        if line_name in ("XLA Ops", "XLA Modules"):
            out = []
            for event in events:
                meta_id = offset_ps = duration_ps = 0
                for m, value in _fields(event):
                    if m == 1:
                        meta_id = value
                    elif m == 2:
                        offset_ps = value
                    elif m == 3:
                        duration_ps = value
                name, op_name = metadata.get(meta_id, ("", ""))
                out.append(Op(name, t0_ns * 1e-9 + offset_ps * 1e-12,
                              duration_ps * 1e-12, op_name))
            lines[line_name] = out
    return (lines.get("XLA Ops", []),
            [trace_lib.Event(op.name, op.start, op.dur)
             for op in lines.get("XLA Modules", [])])


def by_scope(ops, executions) -> dict:
    """Device seconds by scope over the operations that began inside
    one of ``executions`` (``trace.Event``s of a program).  The
    attention kernel is a class of its own (found as the roofline
    reader finds it), loops and conditionals are left out (their events
    span the operations inside them), and what is under no scope is
    ``PLUMBING``.  ``None`` when no operation carries ``MARKER``: the
    capture has no scopes, or the program names none."""
    spans = sorted((ev.start, ev.start + ev.dur) for ev in executions)
    agg = collections.Counter()
    marked = False
    i = 0
    for op in sorted(ops, key=lambda op: op.start):
        while i < len(spans) and spans[i][1] <= op.start:
            i += 1
        if i == len(spans) or op.start < spans[i][0]:
            continue
        if trace_lib.CONTAINER_RE.match(op.name):
            continue
        scope = scope_of(op.op_name)
        marked = marked or scope == MARKER
        if "_paged_decode_step" in op.name and "tpu_custom_call" in op.name:
            scope = KERNEL
        agg[scope or PLUMBING] += op.dur
    return dict(agg) if marked else None
