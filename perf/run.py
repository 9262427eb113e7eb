"""Run one benchmark cell once and print its result line.

    python3 perf/run.py --workload sw_ref.periodic --seed 7 --seconds 10 --trace 0

Run from the root of a checkout.  ``--trace 0`` measures the cell's
end-to-end metrics over ``--seconds``; ``--trace 1`` records whole solves
under the profiler and reports its per-layer metrics.  Both check the
answers of finished solves against the plain reference.  The last line
of stdout is the result object; the numbers compared, each beside its
limit, are the last lines of stderr.  Without the TPU chips the cell
asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root, not this directory, is where imports start
sys.path[0] = ROOT
# one compile cache per checkout, at a fixed path, so that only a cell's
# first run in a checkout compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from perf import harness

    spec = harness.resolve(args.workload)
    try:
        devices = harness.chip_devices(spec["chips"])
    except harness.NoDevice as e:
        print(f"perf: {e}", file=sys.stderr)
        return 2
    result = harness.run(spec, args.seed, args.seconds, args.trace,
                         t_start=T_START, devices=devices)
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, r in result["check"].items():
        print(f"check {name} {r['value']} limit {r['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
