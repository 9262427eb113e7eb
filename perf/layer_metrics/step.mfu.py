"""Model FLOPs utilization of a prefill step, in percent: the model's
FLOPs per step (``perf/work/moonlight.py``: twice the parameters each
token uses, the causal attention products and the last-token head, over
every chip) over the step's seconds times the chips times the bf16 peak
of ``perf/peaks.json``.  The step's seconds are the traced window's
host-clock seconds over its steps.  None where the counters are
absent."""

import json
import os

from perf.work import moonlight as work

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if "shape" not in run.counters or not run.window.steps:
        return None
    with open(os.path.join(PERF, "peaks.json")) as f:
        peak = json.load(f)[run.device_kind]["bf16_flops_per_s"]
    shape = run.counters["shape"]
    step_s = run.window.seconds / run.window.steps
    return work.model_flops(shape) / (step_s * shape["chips"] * peak) * 100
