"""Drive the main path once on the TPU and check what comes out.

    python chip_smoke.py              # one chip: the three phases below
    python chip_smoke.py --chips 4    # four chips: the mesh tier across chips
    python chip_smoke.py --rehearse   # tiny sizes, any platform (no result)

One chip:

1. world  — a 1-rank world job through the launcher
   (``python -m mpi4jax_tpu.runtime.launch -n 1 --platform tpu``) running
   ``tests/world_programs/tpu_world.py``: every op on device arrays inside
   ``jax.jit``, through the ordered host callback.  It runs first, in a
   child, before this process touches the chip: one process holds a chip
   at a time.
2. mesh   — the twelve ops through ``m4j.spmd`` on a mesh of the chip, each
   compared with numpy.  ``send``/``recv`` have no meaning inside one SPMD
   program: the mesh tier refuses them (they ran in phase 1).
3. flagship — shallow water at 1800x3600 for 0.1 model day (432 steps)
   through ``ShallowWater.step_fn`` with the default fused Pallas kernel,
   compiled by Mosaic (``tpu_custom_call`` in the program), against the
   XLA step on the same chip.

Four chips (``--chips 4``), and nothing else: the mesh-tier ops and the
Pallas ICI kernels across the chips against numpy, and shallow water on a
2x2 ``ProcessGrid`` (halo exchange over ICI) against the 1x1 run at the
same size.  Every output shard must sit on its own chip.

The last line of stdout is the result, printed only when every phase
passed on a TPU:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Tolerances, fixed before the first chip run, on max|a - b| per field
# over the field's scale: max|h| for h, the flow speed max(|u|, |v|) for
# u and v (v alone is ~1% of u here, so its own max would scale the
# same absolute error up 100x).  The fused kernel and the XLA step
# compute the same stencils in another association, and 432 steps let
# f32 rounding grow (the 12-step test, tests/models/test_sw_pallas.py,
# holds 2e-5).
SW_TOL = 1e-4
# 2x2 vs 1x1 XLA steps: halo copies are exact, fusion may reassociate
GRID_TOL = 1e-4


class PhaseError(Exception):
    pass


def say(msg):
    print(f"chip_smoke: {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise PhaseError(what)


# ---------------------------------------------------------------- world


def phase_world():
    cmd = [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "1",
           "--platform", "tpu", "--timeout", "540",
           os.path.join(REPO, "tests", "world_programs", "tpu_world.py")]
    t0 = time.perf_counter()
    res = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    ok_lines = [ln for ln in res.stdout.splitlines()
                if ln.startswith("tpu_world OK")]
    if res.returncode != 0 or not ok_lines:
        raise PhaseError(f"world job rc={res.returncode}:\n"
                         f"{res.stdout[-1500:]}\n{res.stderr[-3000:]}")
    say(f"[world] {ok_lines[0]} ({time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------- mesh


def _shards_on_own_chips(arr, devices):
    devs = [s.device for s in arr.addressable_shards]
    check(len(set(devs)) == len(devs) == len(devices)
          and set(devs) == set(devices),
          f"shards of {arr.shape} sit on {devs}, not one per chip "
          f"of {list(devices)}")


def phase_mesh(n, seed):
    """The twelve ops across an n-device mesh against numpy refs."""
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j

    devices = jax.devices()[:n]
    mesh = m4j.make_mesh(n, devices=devices)
    rng = np.random.RandomState(seed)
    X = rng.randn(n, n, 8).astype(np.float32)  # rank r holds X[r]: (n, 8)
    x = jnp.asarray(X)
    root = n - 1

    def run(name, fn, want):
        out = m4j.spmd(lambda v: fn(v[0])[None], mesh=mesh)(x)
        if n > 1:
            _shards_on_own_chips(out, devices)
        got = np.asarray(out)
        for r in range(n):
            if want[r] is None:
                continue
            np.testing.assert_allclose(got[r], want[r], rtol=1e-6,
                                       atol=1e-6, err_msg=f"{name} rank {r}")
        return name

    total = X.sum(axis=0)
    done = [
        run("allreduce", lambda v: m4j.allreduce(v, op=m4j.SUM),
            [total] * n),
        run("allgather", lambda v: m4j.allgather(v), [X] * n),
        run("alltoall", lambda v: m4j.alltoall(v),
            [X[:, r] for r in range(n)]),
        run("bcast", lambda v: m4j.bcast(v, root), [X[root]] * n),
        run("gather", lambda v: m4j.gather(v, root),
            [X if r == root else None for r in range(n)]),
        run("reduce", lambda v: m4j.reduce(v, m4j.SUM, root),
            [total if r == root else X[r] for r in range(n)]),
        run("scan", lambda v: m4j.scan(v, m4j.SUM),
            [X[:r + 1].sum(axis=0) for r in range(n)]),
        run("scatter", lambda v: m4j.scatter(v, root),
            [X[root][r] for r in range(n)]),
        run("sendrecv", lambda v: m4j.sendrecv(v, shift=1),
            [X[(r - 1) % n] for r in range(n)]),
    ]

    def barrier_then_identity(v):
        m4j.barrier()
        return v

    done.append(run("barrier", barrier_then_identity, list(X)))
    for name, op in (("send", lambda v: m4j.send(v, 0)),
                     ("recv", lambda v: m4j.recv(v, 0))):
        try:
            m4j.spmd(lambda v: op(v)[None], mesh=mesh)(x)
        except NotImplementedError as e:
            check("sendrecv" in str(e), f"{name}: {e}")
            done.append(f"{name}(refused)")
        else:
            raise PhaseError(f"{name} ran inside an SPMD program")
    say(f"[mesh] {len(done)} ops on {n} device(s) matched numpy: "
        f"{' '.join(done)}")


def phase_ici(n, seed, interpret):
    """The Pallas ICI kernels across n devices against numpy."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu import topo
    from mpi4jax_tpu.ops import pallas_collectives as pc

    devices = jax.devices()[:n]
    mesh = m4j.make_mesh(n, axis="x", devices=devices)
    rng = np.random.RandomState(seed)
    A = rng.randn(n, 64, 128).astype(np.float32)
    B = rng.randn(n, 64, 128).astype(np.float32)
    R = (rng.randn(n, 4 * 4096 + 37) * 3).astype(np.float32)

    def smap(f, n_in=1):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("x"),) * n_in,
                                     out_specs=P("x"), check_vma=False))

    shift = smap(lambda a: pc.ring_shift(a, "x", 1, interpret=interpret))
    shift2 = smap(lambda a, b: jnp.stack(
        pc.ring_shift2(a, b, "x", interpret=interpret), axis=1), 2)
    fused = smap(lambda v: pc.fused_ring_allreduce_sum(
        v.reshape(-1), "x", interpret=interpret)[None])
    args = {"ring_shift": (jnp.asarray(A),),
            "ring_shift2": (jnp.asarray(A), jnp.asarray(B)),
            "fused_ring_allreduce_sum": (jnp.asarray(R),)}
    fns = {"ring_shift": shift, "ring_shift2": shift2,
           "fused_ring_allreduce_sum": fused}
    for name, f in fns.items():
        fns[name] = f.lower(*args[name]).compile()
        check(interpret or "tpu_custom_call" in fns[name].as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
    shift, shift2, fused = fns.values()

    out = shift(*args["ring_shift"])
    _shards_on_own_chips(out, devices)
    np.testing.assert_array_equal(np.asarray(out), np.roll(A, 1, axis=0))
    out = shift2(*args["ring_shift2"])
    _shards_on_own_chips(out, devices)
    got = np.asarray(out)
    np.testing.assert_array_equal(got[:, 0], np.roll(A, 1, axis=0))
    np.testing.assert_array_equal(got[:, 1], np.roll(B, -1, axis=0))
    out = fused(*args["fused_ring_allreduce_sum"])
    _shards_on_own_chips(out, devices)
    got = np.asarray(out)
    want = topo.simulate_ring_sum([R[r] for r in range(n)])
    diff = float(np.max(np.abs(got - want[None])))
    np.testing.assert_allclose(got, np.broadcast_to(want, got.shape),
                               rtol=1e-6, atol=1e-5)
    say(f"[ici] ring_shift, ring_shift2, fused_ring_allreduce_sum on {n} "
        f"devices matched numpy (fused ring max |diff| vs "
        f"simulate_ring_sum {diff:.3g}, "
        f"{'bit-identical' if diff == 0 else 'not bit-identical'})")


# ---------------------------------------------------------------- flagship


def _sw_model(shape, grid_shape, devices):
    from mpi4jax_tpu.models.shallow_water import ShallowWater, SWParams
    from mpi4jax_tpu.parallel.grid import ProcessGrid

    grid = ProcessGrid(grid_shape, devices=devices)
    return ShallowWater(grid, shape, SWParams(dx=5e3, dy=5e3))


def _sw_steps(model):
    p = model.params
    return int(0.1 * p.day_seconds / p.dt)  # 0.1 model day


def _compare(model_a, a, model_b, b, tol, what):
    """Per field h, u, v: finite, and max|a - b| <= tol * its scale."""
    xs = {f: model_a.interior(getattr(a, f)) for f in "huv"}
    ys = {f: model_b.interior(getattr(b, f)) for f in "huv"}
    speed = max(np.max(np.abs(ys["u"])), np.max(np.abs(ys["v"])))
    scale = {"h": np.max(np.abs(ys["h"])), "u": speed, "v": speed}
    errs = {}
    for f in "huv":
        check(np.all(np.isfinite(xs[f])), f"{what}: {f} not finite")
        errs[f] = float(np.max(np.abs(xs[f] - ys[f])) / scale[f])
        check(errs[f] <= tol, f"{what}: field {f} differs by {errs[f]:.3g}"
              f" of its scale (limit {tol})")
    return ", ".join(f"{k} {v:.3g}" for k, v in errs.items())


def phase_flagship(shape, rehearse):
    """Fused Pallas step (the default path) vs the XLA step."""
    import jax

    model = _sw_model(shape, (1, 1), jax.devices()[:1])
    n_steps = _sw_steps(model)
    impl = "pallas" if rehearse else "auto"  # auto is the kernel on a TPU
    state0 = model.init()

    first = model.step_fn(1, first=True, impl=impl)
    run = model.step_fn(n_steps - 1, impl=impl)
    state1 = first(state0)
    t0 = time.perf_counter()
    compiled = run.lower(state1).compile()
    t_compile = time.perf_counter() - t0
    if not rehearse:
        check("tpu_custom_call" in compiled.as_text(),
              "the fused kernel is not in the compiled flagship program")
    jax.block_until_ready(compiled(state1))  # warm
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(state1))
    t_window = time.perf_counter() - t0
    say(f"[flagship] {shape[0]}x{shape[1]} fused kernel (impl={impl!r}): "
        f"compile {t_compile:.1f} s, {n_steps - 1}-step window "
        f"{t_window:.4f} s (a reading, not a metric)")

    ref1 = model.step_fn(1, first=True, impl="xla")(state0)
    ref = model.step_fn(n_steps - 1, impl="xla")(ref1)
    errs = _compare(model, got, model, ref, SW_TOL, "fused vs xla")
    say(f"[flagship] {n_steps} steps: fused kernel matched impl='xla', "
        f"max|diff|/scale {errs} (limit {SW_TOL}); all finite")


def phase_grid(shape, n):
    """Shallow water on a 2x2 grid (halo exchange) vs 1x1, XLA steps."""
    import jax

    devices = jax.devices()[:n]
    gy = 2
    big = _sw_model(shape, (gy, n // gy), devices)
    one = _sw_model(shape, (1, 1), devices[:1])
    n_steps = _sw_steps(one)
    out = {}
    for label, model in (("2x2", big), ("1x1", one)):
        s = model.step_fn(1, first=True, impl="xla")(model.init())
        s = jax.block_until_ready(
            model.step_fn(n_steps - 1, impl="xla")(s))
        out[label] = (model, s)
    model, s = out["2x2"]
    _shards_on_own_chips(s.h, devices)
    errs = _compare(model, s, *out["1x1"], GRID_TOL, "2x2 vs 1x1")
    say(f"[grid] shallow water {shape[0]}x{shape[1]}, {n_steps} steps on a "
        f"{gy}x{n // gy} grid matched 1x1, max|diff|/scale {errs} "
        f"(limit {GRID_TOL}); one block per chip")


# ---------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes and interpreted kernels on any "
                         "platform; prints no result line")
    args = ap.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if (not args.rehearse and platforms
            and "tpu" not in platforms.split(",")):
        say(f"FAILED: no TPU (JAX_PLATFORMS={platforms})")
        return 2

    from mpi4jax_tpu.utils.compile_cache import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    phases = []
    if args.chips == 1:
        if not args.rehearse:
            phases.append(("world", phase_world))
        phases.append(("device", None))
        phases.append(("mesh", lambda: phase_mesh(1, args.seed)))
        phases.append(("flagship", lambda: phase_flagship(
            (48, 96) if args.rehearse else (1800, 3600), args.rehearse)))
    else:
        phases.append(("device", None))
        phases.append(("mesh", lambda: phase_mesh(4, args.seed)))
        phases.append(("ici", lambda: phase_ici(4, args.seed,
                                                interpret=args.rehearse)))
        phases.append(("grid", lambda: phase_grid(
            (48, 96) if args.rehearse else (1800, 3600), 4)))

    device = None
    for name, fn in phases:
        try:
            if fn is None:  # first touch of the chip, after the world child
                import jax

                devs = jax.devices()
                device = {"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs)}
                if not args.rehearse and device["platform"] != "tpu":
                    raise PhaseError(f"no TPU found: jax reports {device}")
                if len(devs) < args.chips:
                    raise PhaseError(f"{args.chips} chips asked for, "
                                     f"{len(devs)} found")
                say(f"[device] {device}")
                continue
            fn()
        except Exception as e:  # report the phase, never print a result
            say(f"FAILED in phase {name}: {type(e).__name__}: {e}")
            return 1
    if args.rehearse:
        say(f"rehearsal passed on {device} (no result line)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
