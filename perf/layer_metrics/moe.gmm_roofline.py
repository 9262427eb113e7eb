"""The grouped expert matmuls' share of their roofline, in percent, on the
chip whose ``moe_gmm`` kernels took longest: the least time the chip
could take for them, ``max(FLOPs / bf16 peak, bytes / HBM peak)``, over
their measured device time.  FLOPs and bytes come from the rows that
chip's experts received per step (the system's ``moe.rows`` counter),
through ``perf/work/moonlight.py``; the peaks from ``perf/peaks.json`` by
the chips' ``device_kind``.  None where no kernel carries the scope or
the counters are absent."""

import json
import os

from perf.work import moonlight as work

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counters = run.counters
    if "moe.rows" not in counters:
        return None
    chips = run.trace.chips
    dev = max(chips, key=lambda d: chips[d].seconds("kernel", "moe_gmm"))
    seconds = chips[dev].seconds("kernel", "moe_gmm") / run.window.steps
    if seconds <= 0:
        return None
    with open(os.path.join(PERF, "peaks.json")) as f:
        peak = json.load(f)[run.device_kind]
    rows = counters["moe.rows"][counters["device_ids"].index(dev)]
    flops, bytes_ = work.gmm(rows, counters["shape"])
    least = max(flops / peak["bf16_flops_per_s"],
                bytes_ / peak["hbm_bytes_per_s"])
    return least / seconds * 100
