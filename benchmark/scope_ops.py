"""Which operations of a traced program ran under a named scope.

The reduction in `trace_reduce.py` names an operation by its HLO instruction
and keeps the start of that text; neither says which `jax.named_scope` the
operation was traced under. The profile does: every operation's entry in a
device plane's table of event metadata carries, as a string statistic, the
instruction's `op_name` (`jit(step)/.../block0/mixer/short_conv/mul:`), which
`jax.profiler.ProfileData` does not hand out. This module reads that table
straight from the `.xplane.pb` (protocol-buffer wire format, the few fields
it needs, standard library only) and returns the names of the instructions
whose `op_name` lies under a scope, in the form `trace_reduce.summarise`
keys its `op_s` by. A fusion is under the scope its root instruction was
traced under; an operation the compiler fused into a neighbour outside the
scope (a gate folded into the product that follows it) is that neighbour's.

    python3 -m benchmark.scope_ops <file.xplane.pb> <scope>

The fields read (tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1;
XPlane.name = 2, .event_metadata = 4 and .stat_metadata = 5 (maps: key = 1,
value = 2); XEventMetadata.name = 2, .stats = 5; XStat.str_value = 5,
.ref_value = 7 (the id of an XStatMetadata whose name = 2 is the string).
"""

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(rb"^/device:TPU:\d+$")
#: where `benchmark/run.py` has the profiler write a traced run's file, and
#: leaves it until the per-layer readers have run
TRACE_FILES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_trace", "plugins", "profile", "*", "*.xplane.pb")


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited field; fixed-width fields are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif kind == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif kind in (1, 5):
            at += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _first(message, number, default=None):
    return next((v for n, v in fields(message) if n == number), default)


def _map_values(plane, number):
    """The values of one of a plane's id -> message maps."""
    for n, entry in fields(plane):
        if n == number:
            value = _first(entry, 2)
            if value is not None:
                yield _first(entry, 1, 0), value


def instruction_name(text):
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`, as
    `trace_reduce.split_name` has it."""
    return text.partition(" = ")[0].lstrip("%")


def op_names(path):
    """{instruction name: its op_name strings} over the device planes."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for n, plane in fields(space):
        if n != 1 or not DEVICE_PLANE.match(bytes(_first(plane, 2, b""))):
            continue
        interned = {key: bytes(_first(meta, 2, b""))
                    for key, meta in _map_values(plane, 5)}
        for _, meta in _map_values(plane, 4):
            name = instruction_name(
                bytes(_first(meta, 2, b"")).decode("utf-8", "replace"))
            for m, stat in fields(meta):
                if m != 5:
                    continue
                for s, value in fields(stat):
                    text = (bytes(value) if s == 5 else
                            interned.get(value, b"") if s == 7 else b"")
                    # an op_name is a path of scopes; the stack frames
                    # beside it are paths of source files
                    if b"/" in text and b".py:" not in text:
                        out.setdefault(name, set()).add(
                            text.decode("utf-8", "replace"))
    return out


def under_scope(path, scope):
    """The instruction names whose op_name has `scope` as one of its parts."""
    part = re.compile(rf"/{re.escape(scope)}[/:]")
    return {name for name, texts in op_names(path).items()
            if any(part.search(t) for t in texts)}


def traced_run_file():
    """The file of the traced run in progress, or None."""
    files = glob.glob(TRACE_FILES)
    return max(files, key=os.path.getmtime) if files else None


if __name__ == "__main__":
    for found in sorted(under_scope(sys.argv[1], sys.argv[2])):
        print(found)
