"""XLA device time under the program's ``sw_pad_rows`` and
``sw_unpad_rows`` scopes (each call padding the fields to the fused
kernel's block and cutting them back) per model step, on the chip that
spent most; None where no instruction carries those scopes."""


def read(run):
    seconds = max(chip.seconds("xla", "sw_pad_rows")
                  + chip.seconds("xla", "sw_unpad_rows")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
