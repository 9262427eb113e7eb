"""Reduction of a JAX profiler trace to what the metric readers need.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU chip is a plane ``/device:TPU:<id>``; its line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
text (``%fusion.3 = f32[...] fusion(...), ...``), and its line ``Async
XLA Ops`` the in-flight interval of each asynchronous instruction, from
its ``-start`` to its ``-done``.  The host plane ``/host:CPU`` holds the
harness's own spans (``perf.window``, ``perf.prepare``,
``perf.dispatch``, ``perf.sync``) on the same clock.

Given the program's scope names, each instruction also carries its
scope: the innermost of those names in its JAX op_name, or ``unscoped``
(``perf/scopes.py``, which reads the op_names from the same bytes).
Without them every instruction is ``unscoped`` and the op_names are not
read.

An instruction's class comes from its HLO opcode, read from that text:

- ``kernel``: a Mosaic kernel (``custom-call`` to ``tpu_custom_call``,
  what ``pallas_call`` compiles to);
- ``collective``: the cross-chip opcodes in ``COLLECTIVE_OPCODES``;
- ``control``: ``while``, ``conditional`` and ``call``, whose events
  enclose the instructions of their bodies and are left out of every sum;
- ``xla``: every other instruction XLA emits.
"""

from __future__ import annotations

import glob
import os
import re
from collections import Counter

COLLECTIVE_OPCODES = frozenset(
    f"{op}{suffix}"
    for op in ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast", "send", "recv")
    for suffix in ("", "-start", "-done", "-update"))
CONTROL_OPCODES = frozenset(("while", "conditional", "call"))
HOST_SPANS = ("perf.prepare", "perf.dispatch", "perf.sync")

# the opcode is the first lower-case word that opens an argument list;
# shapes and layouts before it use only upper case and brackets
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def opcode(event_name):
    """``fusion``, ``custom-call:tpu_custom_call``, ... of an op event."""
    m = _OPCODE.search(event_name)
    if m is None:
        return event_name.split(" ")[0]
    op = m.group(1)
    if op == "custom-call":
        t = _TARGET.search(event_name)
        op += ":" + (t.group(1) if t else "?")
    return op


def op_class(op):
    if op == "custom-call:tpu_custom_call":
        return "kernel"
    if op in COLLECTIVE_OPCODES:
        return "collective"
    if op in CONTROL_OPCODES:
        return "control"
    return "xla"


def union(intervals):
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of disjoint sorted intervals ``a`` that no interval of
    disjoint sorted ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Chip:
    """One chip's instructions inside the window, times in seconds."""

    def __init__(self, ops, in_flight):
        self.ops = ops              # [(start, end, opcode, scope)], leaf ops
        self.in_flight = in_flight  # [(start, end, opcode, scope)], async

    def seconds(self, cls=None, scope=None):
        """Instruction seconds of class ``cls`` in ``scope``; None is
        any."""
        return sum(e - s for s, e, op, sc in self.ops
                   if (cls is None or op_class(op) == cls)
                   and (scope is None or sc == scope))

    def busy(self):
        return union((s, e) for s, e, _, _ in self.ops)

    def collective(self):
        """When a collective runs or is in flight on this chip."""
        return union([(s, e) for s, e, op, _ in self.ops + self.in_flight
                      if op_class(op) == "collective"])

    def exposed_collective(self):
        """Collective time during which no other instruction runs."""
        others = union((s, e) for s, e, op, _ in self.ops
                       if op_class(op) != "collective")
        return subtract(self.collective(), others)


class Trace:
    """The reduced trace of one traced window."""

    def __init__(self, chips, window, host_spans, scoped=False):
        self.chips = chips            # {device id: Chip}
        self.window = window          # (start, end) of perf.window
        self.host_spans = host_spans  # [(start, end, name)]
        self.scoped = scoped          # reduced with the program's scopes

    def window_s(self):
        return self.window[1] - self.window[0]

    def busy_s(self):
        """Seconds in which an instruction ran, averaged over chips."""
        return sum(length(c.busy()) for c in self.chips.values()) / len(
            self.chips)

    def idle_gaps(self):
        """``(seconds, chip, start)`` of every gap in the window in which
        no instruction ran on a chip."""
        lo, hi = self.window
        gaps = []
        for dev, chip in self.chips.items():
            gaps += [(e - s, dev, s) for s, e in
                     subtract([(lo, hi)], chip.busy())]
        return gaps

    def host_activity(self, t):
        """The innermost harness span open at host time ``t``."""
        best = None
        for s, e, name in self.host_spans:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "none"

    def breakdown(self, top=10):
        """The instructions that took most time, averaged over chips and
        named ``<scope> <opcode>`` where the trace is scoped, and the
        longest idle gaps by the harness span open in each."""
        per_op = Counter()
        for chip in self.chips.values():
            for s, e, op, sc in chip.ops:
                per_op[f"{sc} {op}" if self.scoped else op] += (
                    (e - s) / len(self.chips))
        gaps = sorted(self.idle_gaps(), reverse=True)[:top]
        return {
            "device_ops": [[op, t] for op, t in per_op.most_common(top)],
            "idle_gaps": [[f"{self.host_activity(s + d / 2)} (chip {dev})",
                           d] for d, dev, s in gaps],
        }


def _events(line):
    for ev in line.events:
        yield ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name


def _ops(line, scope_of, unscoped):
    return [(s, e, opcode(n), scope_of.get(n, unscoped))
            for s, e, n in _events(line)]


def reduce(path, devices, scopes=()):
    """The :class:`Trace` of ``devices`` (jax devices) in ``path`` (an
    ``.xplane.pb``, gzipped or not), each instruction in the innermost of
    ``scopes`` that its op_name names."""
    from jax.profiler import ProfileData

    from perf.scopes import UNSCOPED, event_scopes, load, op_names

    buf = load(path)
    data = ProfileData.from_serialized_xspace(buf)
    wanted = {f"/device:TPU:{d.id}": d.id for d in devices}
    names = op_names(buf, set(wanted)) if scopes else {}
    raw = {}
    host = []
    for plane in data.planes:
        if plane.name in wanted:
            of = event_scopes(names.get(plane.name, {}), scopes)
            lines = {ln.name: ln for ln in plane.lines}
            raw[wanted[plane.name]] = (
                _ops(lines["XLA Ops"], of, UNSCOPED),
                _ops(lines["Async XLA Ops"], of, UNSCOPED)
                if "Async XLA Ops" in lines else [])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(s, e, n) for s, e, n in _events(line)
                         if n == "perf.window" or n in HOST_SPANS]
    windows = [(s, e) for s, e, n in host if n == "perf.window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one perf.window span, found "
                           f"{len(windows)}")
    missing = set(wanted.values()) - set(raw)
    if missing:
        raise RuntimeError(f"no trace of devices {sorted(missing)}")
    lo, hi = windows[0]
    chips = {}
    for dev, (ops, in_flight) in raw.items():
        leaf = [(max(s, lo), min(e, hi), op, sc) for s, e, op, sc in ops
                if op_class(op) != "control" and e > lo and s < hi]
        flight = [(max(s, lo), min(e, hi), op, sc)
                  for s, e, op, sc in in_flight if e > lo and s < hi]
        chips[dev] = Chip(leaf, flight)
    spans = [(s, e, n) for s, e, n in host if n in HOST_SPANS]
    return Trace(chips, (lo, hi), spans, scoped=bool(scopes))
