"""Device time of every instruction under the program's ``mla_attention``
scope (the latent projections, RoPE, the flash kernel, the output
projection) per prefill step, on the chip that spent most; None where no
instruction carries that scope."""


def read(run):
    seconds = max(chip.seconds(scope="mla_attention")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
