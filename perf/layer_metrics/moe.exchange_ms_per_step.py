"""Time in which a collective of the MoE exchange runs or is in flight,
per prefill step, on the chip that spent most: instructions under the
program's ``moe_dispatch`` and ``moe_combine`` scopes whose opcode is a
cross-chip one (``ragged-all-to-all``, which ``perf/tracefile.py`` does
not class as a collective, and the small ``all-gather`` of the counts
and ``all-to-all`` of the offsets); None where none ran."""

from perf.tracefile import COLLECTIVE_OPCODES, length, union

SCOPES = ("moe_dispatch", "moe_combine")


def _exchange(op, scope):
    return scope in SCOPES and (op in COLLECTIVE_OPCODES
                                or op.startswith("ragged-all-to-all"))


def read(run):
    seconds = max(length(union([(s, e) for s, e, op, sc
                                in chip.ops + chip.in_flight
                                if _exchange(op, sc)]))
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
