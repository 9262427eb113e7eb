"""Readings of the Moonlight cell's check for the program and its two
controls, each judged by the harness's own comparison, and the check that
the ragged and the padded exchange agree bit for bit.

    python3 perf/calibrate_moonlight.py --seeds 3 --control-seeds 1 \\
        --first-seed 1000 [--exchange-check]

On the chip, in one process.  For each seed: the prompt batch that a run
of that seed would judge, prefilled through the cell's own call (the
lower readings).  For each control seed, in the program's place: the
reference with every matmul input rounded to ``float8_e4m3fn``, the
precision below the configuration's bfloat16, and the embeddings passed
to the final norm and head through no layer (the upper readings).  Each
row carries the ``correct`` that ``harness.judge`` gives it under the
configuration's limits.  ``--exchange-check`` then prefills one batch
twice, with ``ops.alltoallv`` as ``ragged_all_to_all`` and as the padded
``all_to_all`` that runs off TPU, and compares every output bit.  Prints
one JSON object per row, then a summary line.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

WORKLOAD = "moonlight_ep4.prefill"


def exchange_check(cell):
    """Prefill ``cell.inputs[0]`` with the ragged exchange and with the
    padded one: whether every output is bit-identical, and the shapes of
    the exchanges in the compiled program of the timed path."""
    import functools
    import re

    import jax
    import numpy as np

    from mpi4jax_tpu.ops import _mesh_impl

    hlo = cell.prefill.lower(cell.params, cell.inputs[0]).compile().as_text()
    ops = re.findall(r"= (\S+) ((?:ragged-)?all-to-all)\(", hlo)
    ragged = cell.prefill(cell.params, cell.inputs[0])
    exchange = _mesh_impl.alltoallv
    _mesh_impl.alltoallv = functools.partial(exchange, ragged=False)
    try:
        padded = cell.model.prefill_fn()(cell.params, cell.inputs[0])
    finally:
        _mesh_impl.alltoallv = exchange
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.device_get(ragged),
                               jax.device_get(padded)))
    return {"ragged_equals_padded": same,
            "exchanges": [f"{op} {shape}" for shape, op in ops]}


def main(argv=None):
    import argparse
    import json

    import jax
    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--exchange-check", action="store_true")
    args = ap.parse_args(argv)

    from perf import harness, schedule
    from perf.systems import moonlight

    spec = harness.resolve(WORKLOAD)
    devices = harness.chip_devices(spec["chips"])
    config, traffic = spec["config"], spec["traffic"]
    cell = moonlight.Cell(config, traffic, devices, args.first_seed)
    cell.generate()
    cell.warm_up()

    rows = []
    # every reading of a seed compares with the same float32 reference
    reference, memo = cell.reference, {}

    def cached(slot, dtype, layers=None):
        key = (slot, jnp.dtype(dtype).name, layers)
        if key not in memo:
            memo[key] = reference(slot, dtype, layers)
        return memo[key]

    cell.reference = cached

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
        memo.clear()
        cell.tokens = moonlight.prompts(seed, traffic, config,
                                        len(cell.devices))
        cell.inputs = [jax.device_put(t, cell.inputs[0].sharding)
                       for t in cell.tokens]
        window = harness.drive(cell, solves=len(cell.inputs))
        (slot,) = schedule.checked_solves(seed, window.kept, dict(
            traffic, check_solves=1))
        t0 = time.perf_counter()
        emit("program", seed, slot, cell.to_host(window.kept[slot]), t0)
        if i < args.control_seeds:
            for kind, kw in (("float8", dict(dtype=jnp.float8_e4m3fn)),
                             ("no_layers", dict(dtype=jnp.float32,
                                                layers=0))):
                t0 = time.perf_counter()
                emit(kind, seed, slot, cell.reference(slot, **kw), t0)

    summary = {"summary": WORKLOAD}
    for kind, pick in (("program", max), ("float8", min), ("no_layers", min)):
        vals = [r for r in rows if r["kind"] == kind]
        if vals:
            summary[kind] = {n: pick((r[n] for r in vals if r[n] is not None),
                                  default=None)
                             for n in config["limits"]}
            summary[kind]["correct"] = sorted({r["correct"] for r in vals})
    if args.exchange_check:
        summary.update(exchange_check(cell))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
