"""Device time of the instructions that are neither Mosaic kernels nor
collectives (the XLA step, padding, halo stacking copies) per model step,
on the chip that spent most."""


def read(run):
    seconds = max(chip.seconds("xla") for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3
