"""Readings of a cell's check for the program, its control and a state
left unchanged, each judged by the harness's own comparison.

    python3 perf/calibrate.py --workload sw_ref.periodic --seeds 12 \\
        --control-seeds 3 --first-seed 1000

On the chip, in one process.  For each seed: the solve that a run of
that seed would judge, driven through the cell's own calls (the lower
readings).  For each control seed, in the program's place: the same
reference computed in bfloat16, the precision below the configuration's
float32 (the upper readings), and the initial state itself, the answer
of a step that returns its state unchanged.  Each row carries the
``correct`` that ``harness.judge`` gives it under the configuration's
limits.  Prints one JSON object per row, then a summary line.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    import argparse
    import importlib
    import json

    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)

    from perf import harness, schedule

    spec = harness.resolve(args.workload)
    devices = harness.chip_devices(spec["chips"])
    config, traffic = spec["config"], spec["traffic"]
    system = importlib.import_module(f"perf.systems.{config['system']}")
    cell = system.Cell(config, traffic, devices, args.first_seed)
    cell.generate()
    cell.warm_up()

    rows = []

    def emit(kind, seed, slot, got, t0):
        readings, _ = harness.judge(spec, cell, {slot: got})
        row = {"kind": kind, "seed": seed, "slot": slot,
               "seconds": time.perf_counter() - t0,
               "correct": harness.verdict(readings),
               **{k: r["value"] for k, r in readings.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + i
        cell.modes = schedule.initial_modes(seed, traffic)
        cell.generate()
        window = harness.drive(cell, solves=len(cell.inputs))
        (slot,) = schedule.checked_solves(seed, window.kept, dict(
            traffic, check_solves=1))
        t0 = time.perf_counter()
        emit("program", seed, slot, cell.to_host(window.kept[slot]), t0)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            emit("control", seed, slot, cell.reference(slot, jnp.bfloat16),
                 t0)
            t0 = time.perf_counter()
            emit("unchanged", seed, slot,
                 cell.to_host(cell.result(cell.initial(slot))), t0)

    summary = {"summary": args.workload}
    for kind, pick in (("program", max), ("control", min),
                       ("unchanged", min)):
        vals = [r for r in rows if r["kind"] == kind]
        if vals:
            summary[kind] = {n: pick((r[n] for r in vals if r[n] is not None),
                                  default=None)
                             for n in config["limits"]}
            summary[kind]["correct"] = sorted({r["correct"] for r in vals})
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
