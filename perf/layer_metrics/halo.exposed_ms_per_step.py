"""The part of the collective time during which no other instruction
runs on that chip, per model step, on the chip where it is largest;
None where no collective ran."""

from perf.tracefile import length


def read(run):
    chips = run.trace.chips.values()
    if not any(chip.collective() for chip in chips):
        return None
    seconds = max(length(chip.exposed_collective()) for chip in chips)
    return seconds / run.window.steps * 1e3
