"""Share of the traced window in which no instruction ran on the chip,
for the chip that idled most."""

from perf.tracefile import length


def read(run):
    trace = run.trace
    return max(1.0 - length(chip.busy()) / trace.window_s()
               for chip in trace.chips.values())
