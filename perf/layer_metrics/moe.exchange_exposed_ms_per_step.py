"""The part of the MoE exchange's collective time (the instructions that
``moe.exchange_ms_per_step`` counts) during which no other instruction
runs on that chip, per prefill step, on the chip where it is largest;
None where no exchange collective ran."""

from perf.tracefile import COLLECTIVE_OPCODES, length, subtract, union

SCOPES = ("moe_dispatch", "moe_combine")


def _exchange(op, scope):
    return scope in SCOPES and (op in COLLECTIVE_OPCODES
                                or op.startswith("ragged-all-to-all"))


def read(run):
    worst = None
    for chip in run.trace.chips.values():
        mine = union([(s, e) for s, e, op, sc in chip.ops + chip.in_flight
                      if _exchange(op, sc)])
        if not mine:
            continue
        others = union([(s, e) for s, e, op, sc in chip.ops
                        if not _exchange(op, sc)])
        exposed = length(subtract(mine, others))
        worst = exposed if worst is None else max(worst, exposed)
    return None if worst is None else worst / run.window.steps * 1e3
