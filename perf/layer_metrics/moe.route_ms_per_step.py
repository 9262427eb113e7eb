"""XLA device time under the program's ``moe_route`` scope (the router,
top-k, the sort by expert and the un-sort, the row gathers and the
weighted sum) per prefill step, on the chip that spent most; None where
no instruction carries that scope."""


def read(run):
    seconds = max(chip.seconds("xla", "moe_route")
                  for chip in run.trace.chips.values())
    return seconds / run.window.steps * 1e3 if seconds > 0 else None
