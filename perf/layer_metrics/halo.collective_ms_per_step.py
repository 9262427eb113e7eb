"""Time in which a collective runs or is in flight per model step (the
halo exchange's collective-permutes), on the chip that spent most; None
where no collective ran."""

from perf.tracefile import length


def read(run):
    seconds = max(length(chip.collective())
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
