"""Device time of the Mosaic kernels (the fused shallow-water step) per
model step, on the chip that spent most; None where no kernel ran."""


def read(run):
    seconds = max(chip.seconds("kernel") for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
