"""Device time of the Mosaic kernels under the program's ``moe_gmm`` scope
(the routed experts' grouped matmuls, gate and up then down) per prefill
step, on the chip that spent most; None where no kernel carries that
scope."""


def read(run):
    seconds = max(chip.seconds("kernel", "moe_gmm")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
