"""The flash attention kernel's share of its roofline, in percent, on the
chip whose kernels under ``mla_attention`` took longest: the least time
for its work, ``max(FLOPs / bf16 peak, bytes / HBM peak)``, over the
measured device time.  FLOPs are causal attention's (``q k^T`` over the
q.k head, ``p v`` over the value head), bytes q, k and v in and the
float32 partial output out (``perf/work/moonlight.py``, from the shape
the system reports); peaks from ``perf/peaks.json``.  None where no
kernel carries the scope or the counters are absent."""

import json
import os

from perf.work import moonlight as work

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if "shape" not in run.counters:
        return None
    seconds = max(chip.seconds("kernel", "mla_attention")
                  for chip in run.trace.chips.values()) / run.window.steps
    if seconds <= 0:
        return None
    with open(os.path.join(PERF, "peaks.json")) as f:
        peak = json.load(f)[run.device_kind]
    flops, bytes_ = work.flash(run.counters["shape"])
    least = max(flops / peak["bf16_flops_per_s"],
                bytes_ / peak["hbm_bytes_per_s"])
    return least / seconds * 100
