"""XLA device time under the program's ``halo_exchange`` scope (the
stacking of the fields, the strips, the ghost writes, the unstacking, and
every fusion rooted there) per model step, on the chip that spent most;
None where no instruction carries that scope."""


def read(run):
    seconds = max(chip.seconds("xla", "halo_exchange")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
