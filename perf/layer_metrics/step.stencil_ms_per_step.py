"""XLA device time under the program's ``sw_step`` scope and outside its
exchanges (the stencil arithmetic, the interior and wall writes, the
step's padding) per model step, on the chip that spent most; None where
no instruction carries that scope."""


def read(run):
    seconds = max(chip.seconds("xla", "sw_step")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
