"""Sweep the fused shallow-water kernel's (tile_rows, fuse) on the real
chip, plus the XLA step as control.  One jitted multi-step call per
config, so dispatch stays out of the timing; prints one JSON line per
config with ms/step.

    python benchmarks/sw_tile_sweep.py [--steps 64] [--size 1800 3600]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--size", type=int, nargs=2, default=(1800, 3600))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mpi4jax_tpu.models.shallow_water import ShallowWater, SWParams
    from mpi4jax_tpu.parallel.grid import ProcessGrid

    grid = ProcessGrid((1, 1), devices=jax.devices()[:1])
    model = ShallowWater(grid, tuple(args.size), SWParams(dx=5e3, dy=5e3))
    n = args.steps

    configs = [("xla", None, None)]
    for fuse in (1, 2):
        for tr in (16, 32, 64, 128, 256):
            configs.append(("pallas", tr, fuse))

    state0 = model.step_fn(1, first=True)(model.init())
    best = None
    for impl, tr, fuse in configs:
        kw = {} if impl == "xla" else {"tile_rows": tr, "fuse": fuse}
        try:
            run = model.step_fn(n, first=False, impl=impl, **kw)
            float(jnp.sum(run(state0).h))  # compile + warmup
            t0 = time.perf_counter()
            float(jnp.sum(run(state0).h))
            dt = time.perf_counter() - t0
        except Exception as err:
            print(json.dumps({"impl": impl, "tile_rows": tr, "fuse": fuse,
                              "error": f"{type(err).__name__}: {err}"[:160]}),
                  flush=True)
            continue
        ms = dt / n * 1e3
        rec = {"impl": impl, "tile_rows": tr, "fuse": fuse,
               "ms_per_step": round(ms, 3),
               "total_s": round(dt, 3)}
        if best is None or ms < best["ms_per_step"]:
            best = rec
        print(json.dumps(rec), flush=True)
    print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    main()
