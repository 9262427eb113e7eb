"""Device time of every class outside the program's named scopes (what
XLA adds: loop-carry and layout copies, async copy start and done, the
loop counter) per model step, on the chip that spent most; None where
the trace was not reduced with the program's scopes."""


def read(run):
    if not run.trace.scoped:
        return None
    seconds = max(chip.seconds(scope="unscoped")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3
