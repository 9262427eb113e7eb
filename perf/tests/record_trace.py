"""Record a trace of a cell's program through the harness's traced path.

    python3 perf/tests/record_trace.py sw_ref.periodic \\
        perf/tests/data/sw_1x1_scoped.xplane.pb.gz
    python3 perf/tests/record_trace.py sw_ref.periodic /tmp/full.xplane.pb.gz \\
        --full

Run on the chips the cell asks for.  The cell is set up as the harness
sets it up, then ``harness.drive`` runs under the profiler, inside the
harness's own ``perf.window``, ``perf.prepare``, ``perf.dispatch`` and
``perf.sync`` spans.  By default: the cell's configuration at 240 x 512
cells per chip and one solve of three 25-step advance calls, the small
traces the tests pin.  With ``--full``: the cell as configured and its
traffic's ``trace_solves`` whole solves, what a ``--trace 1`` run
records.  The ``.xplane.pb`` is written gzipped to the path given, and
the number of model steps in the window is printed beside it.
"""

import argparse
import gzip
import importlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BLOCK = (240, 512)  # cells per chip
CALLS = 3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("out", help="path of the gzipped .xplane.pb")
    ap.add_argument("--full", action="store_true",
                    help="the cell as configured, its traced solves")
    args = ap.parse_args(argv)

    import jax

    from perf import harness, tracefile

    spec = harness.resolve(args.workload)
    config = spec["config"]
    if not args.full:
        gy, gx = config["grid"]
        config = dict(config, ny=BLOCK[0] * gy, nx=BLOCK[1] * gx)
    system = importlib.import_module(f"perf.systems.{config['system']}")
    cell = system.Cell(config, spec["traffic"],
                       harness.chip_devices(spec["chips"]), 0)
    cell.generate()
    cell.warm_up()
    solves = spec["traffic"]["trace_solves"]
    if not args.full:
        cell.calls = [c for c in cell.calls if c[0] == "advance"][:CALLS]
        solves = 1
    trace_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            window = harness.drive(cell, solves=solves)
        finally:
            jax.profiler.stop_trace()
        with open(tracefile.find_xplane(trace_dir), "rb") as f, \
                gzip.open(args.out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "steps": window.steps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
