"""Device time by the program's named scopes, from a JAX profiler trace.

    python3 perf/scopes.py TRACE.xplane.pb[.gz] --steps 75 \\
        --scopes halo_exchange,sw_step,sw_pad_rows,sw_unpad_rows,sw_fused_step

``perf/tracefile.py`` classes each instruction of a trace by its HLO
opcode.  This adds the instruction's scope: the innermost of the given
names in its JAX op_name, or ``unscoped`` where it has none (the copies
XLA inserts for a loop's carry and for layouts, the loop counter, every
instruction of a program that names no scope).  A program names its parts
with ``jax.named_scope`` and a ``pallas_call``'s ``name``; the names land
in the op_name, which the trace keeps as the ``tf_op`` stat of each
event's metadata.  ``ProfileData`` does not expose that stat, so
``op_names`` reads it from the file's protobuf wire format, with the
stdlib alone.  A fusion carries its root instruction's op_name, as XLA
records it.

Only the leaf instructions inside the harness's ``perf.window`` span
count, clipped to it, as in ``tracefile.reduce``.  The command prints one
JSON line: for each ``<scope> <class>`` the device milliseconds per step
on the chip that spent most, and the largest ``<scope> <opcode>`` lines
in seconds averaged over chips.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from collections import Counter

if __package__ in (None, ""):
    # run as a script: imports start at the checkout's root
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from perf import tracefile  # noqa: E402

UNSCOPED = "unscoped"
DEVICE = "/device:TPU:"


def load(path):
    """The serialized XSpace of a ``.xplane.pb``, gzipped or not."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, lo, hi):
    """``(field number, value)`` of each field of the protobuf message in
    ``buf[lo:hi]``: an int for a varint, a ``(lo, hi)`` span for bytes,
    a message or a string; fixed-width values are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf, span):
    return buf[span[0]:span[1]].decode()


def op_names(buf, planes):
    """``{plane: {event name: {op_name}}}`` for the named planes of the
    serialized XSpace ``buf``, from the ``tf_op`` stat of each event's
    metadata (``op_name:op_type``; JAX leaves the type empty), or ``""``
    where it has none; a name may carry several.  Read:
    ``XSpace.planes`` (1) -> ``XPlane.name`` (2), ``event_metadata`` (4)
    and ``stat_metadata`` (5), maps by id -> ``XEventMetadata.name`` (2)
    and ``stats`` (5) -> ``XStat.metadata_id`` (1), ``str_value`` (5) or
    ``ref_value`` (7, the id of a stat metadata whose name is the
    string).  An XPlane's lines, the bulk of the file, are skipped
    unread."""
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        parts = {4: [], 5: []}  # map entries: (key, value span)
        name = None
        for pf, value in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, value)
            elif pf in parts:
                entry = dict(_fields(buf, *value))
                parts[pf].append((entry.get(1, 0), entry.get(2, (0, 0))))
        if name not in planes:
            continue
        stat_names = {key: _text(buf, v) for key, span in parts[5]
                      for sf, v in _fields(buf, *span) if sf == 2}
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        names = {}
        for _, span in parts[4]:
            ev_name = op_name = None
            for mf, value in _fields(buf, *span):
                if mf == 2:
                    ev_name = _text(buf, value)
                elif mf == 5:
                    stat = dict(_fields(buf, *value))
                    if stat.get(1) not in tf_op:
                        continue
                    if 5 in stat:
                        op_name = _text(buf, stat[5])
                    elif 7 in stat:
                        op_name = stat_names[stat[7]]
            if op_name is not None and ":" in op_name:
                op_name = op_name.rpartition(":")[0]
            names.setdefault(ev_name, set()).add(op_name or "")
        out[name] = names
    return out


def scope(op_name, scopes):
    """The innermost of ``scopes`` in an op_name path, or ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def event_scopes(names, scopes):
    """``{event name: scope}`` of one plane's ``op_names``; raises where
    one event name carries op_names of two scopes, which no reading could
    tell apart.  The same instruction text in two programs (a bootstrap
    and an advance call) gives op_names that differ only in the program's
    name, and one scope."""
    out = {}
    for ev_name, ops in names.items():
        found = {scope(op, scopes) for op in ops}
        if len(found) > 1:
            raise RuntimeError(f"event {ev_name[:120]!r} has op_names of "
                               f"scopes {sorted(found)}: {sorted(ops)}")
        out[ev_name] = found.pop()
    return out


def split(buf, scopes):
    """``{chip id: Counter{(scope, opcode): seconds}}`` of every TPU plane
    in the serialized XSpace ``buf``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(buf)
    windows = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
               for plane in data.planes if plane.name == "/host:CPU"
               for line in plane.lines for ev in line.events
               if ev.name == "perf.window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one perf.window span, found "
                           f"{len(windows)}")
    lo, hi = windows[0]
    chips = [p for p in data.planes if p.name.startswith(DEVICE)]
    names = op_names(buf, {p.name for p in chips})
    out = {}
    for plane in chips:
        of = event_scopes(names.get(plane.name, {}), scopes)
        line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
        seconds = Counter()
        for ev in line.events:
            s, e = max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi)
            op = tracefile.opcode(ev.name)
            if e > s and tracefile.op_class(op) != "control":
                seconds[of.get(ev.name, UNSCOPED), op] += e - s
        out[int(plane.name[len(DEVICE):])] = seconds
    return out


def summary(chips, steps, top=10):
    """What the command prints, from :func:`split`."""
    per_class = {}
    for seconds in chips.values():
        by = Counter()
        for (sc, op), t in seconds.items():
            by[f"{sc} {tracefile.op_class(op)}"] += t
        for key, t in by.items():
            per_class[key] = max(per_class.get(key, 0.0), t)
    ops = Counter()
    for seconds in chips.values():
        for (sc, op), t in seconds.items():
            ops[f"{sc} {op}"] += t / len(chips)
    return {
        "chips": len(chips),
        "ms_per_step": {k: per_class[k] / steps * 1e3
                        for k in sorted(per_class)},
        "device_ops": [[k, t] for k, t in ops.most_common(top)],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb, or one gzipped")
    ap.add_argument("--steps", type=int, required=True,
                    help="model steps in the traced window")
    ap.add_argument("--scopes", required=True,
                    help="the program's scope names, comma-separated")
    args = ap.parse_args(argv)
    chips = split(load(args.trace), tuple(args.scopes.split(",")))
    print(json.dumps(summary(chips, args.steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
