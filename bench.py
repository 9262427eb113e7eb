"""Round benchmark battery (driver-run on the real TPU chip).

Sections (each emits one JSON line as it completes; the final line is the
headline shallow-water metric with every section's record embedded under
``"metrics"``):

1. shallow-water headline config — reference BASELINE.md: 6.28 s on one
   P100, 111.95 s on one CPU socket (docs/shallow-water.rst there).
2. flash-attention MFU — Pallas ring-flash fwd and fwd+bwd, Mosaic-
   compiled on the chip, vs the chip's 197 TFLOP/s bf16 peak (v5e).
3. pallas kernel census — every Pallas kernel in the tree compiled and
   executed on the chip (no interpret fallbacks): flash fwd/bwd, RDMA
   hop/bidir/multi, direct alltoall (size-1-ring loopback DMAs), fused
   shallow-water step.
4. world tier ON the TPU platform — 1-rank launcher job running every op
   through the ordered host callback under the accelerator runtime
   (tests/world_programs/tpu_world.py).
5. allreduce message sweep, world tier np=8 loopback (native transport).
6. DP ResNet grad-allreduce step (BASELINE config 3).
7. GPT-2-124M train step, bf16 (BASELINE config 4 scale) + tokens/s.
8. spectral 3-D Poisson solve via FFT alltoall transpose (config 5).

Every timed region ends inside jit with a scalar reduction that is
fetched with ``float(...)``, and multi-iteration loops live inside one
jit call.

Artifact contract: the battery maintains ONE summary line — the headline
metric with EVERY section embedded under ``"metrics"``, sections not yet
run appearing as explicit pending records — and (re)prints it at
startup, after every section, from the SIGTERM/SIGINT handler, and from
a budget-guard thread that ends the process 75 s before
``BENCH_TOTAL_BUDGET_S`` runs out.  A section that raises is recorded
with its error and the battery goes on; the exit code is non-zero when
any section failed or the battery did not finish.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

from mpi4jax_tpu.utils.compile_cache import use_compile_cache

BASELINE_GPU_SECONDS = 6.28  # reference: 1x P100, docs/shallow-water.rst:81-83

# bf16 peak FLOP/s per chip, keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A device that is
# not listed is an error, never a default.
BF16_PEAK = {"TPU v5 lite": 197e12}

WORLD_TIMEOUT_S = 600
REPO = os.path.dirname(os.path.abspath(__file__))


def _bf16_peak():
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in BF16_PEAK:
        raise KeyError(f"no bf16 peak recorded for device kind {kind!r}")
    return BF16_PEAK[kind]


# children launched by battery sections, killed by Battery.final_exit so
# an aborting battery never leaves a rank subprocess holding the device
# claim or a rendezvous port.  The lock covers spawn+register as one
# step so an abort snapshot cannot miss a child mid-launch.
_CHILDREN = set()
_CHILDREN_LOCK = threading.Lock()


def _run_tracked(cmd, timeout=None, **kwargs):
    """``subprocess.run`` equivalent whose child is registered in
    ``_CHILDREN`` for the battery's abort paths."""
    if kwargs.pop("capture_output", False):
        kwargs["stdout"] = subprocess.PIPE
        kwargs["stderr"] = subprocess.PIPE
    with _CHILDREN_LOCK:
        proc = subprocess.Popen(cmd, **kwargs)
        _CHILDREN.add(proc)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise
    finally:
        _CHILDREN.discard(proc)
    return subprocess.CompletedProcess(cmd, proc.returncode, out, err)


def bench_shallow_water():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4jax_tpu.models.shallow_water import ShallowWater, SWParams
    from mpi4jax_tpu.parallel.grid import ProcessGrid

    grid = ProcessGrid((1, 1), devices=jax.devices()[:1])
    params = SWParams(dx=5e3, dy=5e3)
    ny, nx = 1800, 3600
    model = ShallowWater(grid, (ny, nx), params)

    days = 0.1
    n_steps = int(days * params.day_seconds / params.dt)  # 432 (timed: 431)

    # ALL steps in ONE jitted call.  Timed region matches the
    # reference's "Solution took" exactly: the multistep loop only —
    # initial conditions, the Euler bootstrap step, and compilation all
    # happen before its timer starts
    # (/root/reference/examples/shallow_water.py:423-470).
    state1 = model.step_fn(1, first=True)(model.init())
    run = model.step_fn(n_steps - 1, first=False)

    float(jnp.sum(run(state1).h))  # compile + warmup, fetch-forced

    t0 = time.perf_counter()
    state = run(state1)
    float(jnp.sum(state.h))  # drain the queue
    elapsed = time.perf_counter() - t0

    h = model.interior(state.h)
    if not np.all(np.isfinite(np.asarray(h))):
        raise RuntimeError("diverged")
    timed = n_steps - 1
    return {
        "metric": "shallow_water_1800x3600_0.1day_1chip",
        "value": round(elapsed, 3), "unit": "s",
        "vs_baseline": round(BASELINE_GPU_SECONDS / elapsed, 3),
        "steps": timed, "ms_per_step": round(elapsed / timed * 1e3, 3),
        "timed_region": "multistep loop (= reference 'Solution took')",
        "platform": jax.devices()[0].platform,
    }


def _flash_setup(**fa_kwargs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from mpi4jax_tpu.ops.flash import ring_flash_attention

    B, T, H, D = 4, 4096, 16, 128
    ks = [jax.random.PRNGKey(i) for i in range(3)]
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16) for kk in ks)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    fa = jax.shard_map(
        partial(ring_flash_attention, axis="sp", causal=True,
                interpret=False, **fa_kwargs),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    fwd_flops = 2 * 2 * B * H * T * T * D * 0.5  # causal
    return q, k, v, fa, fwd_flops


def bench_flash_mfu():
    import jax
    import jax.numpy as jnp

    q, k, v, fa, fwd_flops = _flash_setup()
    K = 10

    @jax.jit
    def many_fwd(q, k, v):
        def step(qc, _):
            return fa(qc, k, v).astype(qc.dtype), ()
        out, _ = jax.lax.scan(step, q, None, length=K)
        return jnp.sum(out.astype(jnp.float32))

    def loss(q, k, v):
        return jnp.sum(fa(q, k, v).astype(jnp.float32))

    gfn = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def many_bwd(q, k, v):
        def step(qc, _):
            dq, _, _ = gfn(qc, k, v)
            return qc + dq.astype(qc.dtype) * 1e-4, ()
        out, _ = jax.lax.scan(step, q, None, length=K)
        return jnp.sum(out.astype(jnp.float32))

    recs = []
    for name, fn, mult in [("fwd", many_fwd, 1.0),
                           ("fwd+bwd", many_bwd, 3.5)]:
        float(fn(q, k, v))  # compile + warmup
        t0 = time.perf_counter()
        float(fn(q, k, v))
        dt = (time.perf_counter() - t0) / K
        tflops = fwd_flops * mult / dt / 1e12
        recs.append({
            "metric": f"flash_attention_{name}_B4_T4096_H16_D128_bf16",
            "value": round(tflops, 1), "unit": "TFLOP/s",
            "vs_baseline": None,  # reference ships no attention kernels
            "pct_of_bf16_peak": round(tflops * 1e12 / _bf16_peak()
                                      * 100, 1),
            "ms": round(dt * 1e3, 3),
        })
    recs.extend(bench_flash_experiments())
    return recs


def bench_flash_experiments():
    """Settle the r4 fwd-MFU questions with data (VERDICT r4 #5):
    (a) the q-prescale rewrite A/B (claimed ~5-10%, never measured);
    (b) the VPU-exp roofline probe — identical kernel with the two
    ``exp`` calls swapped for a linear stand-in.  If (b) barely moves,
    the forward is NOT exp-bound; if it jumps, the VPU transcendental
    unit is the ceiling and the measured gap bounds it."""
    import jax
    import jax.numpy as jnp

    from mpi4jax_tpu.ops import flash as flash_mod

    K = 10
    recs = []

    def timed_fwd(fa, q, k, v):
        @jax.jit
        def many(q, k, v):
            def step(qc, _):
                return fa(qc, k, v).astype(qc.dtype), ()
            out, _ = jax.lax.scan(step, q, None, length=K)
            return jnp.sum(out.astype(jnp.float32))

        float(many(q, k, v))
        t0 = time.perf_counter()
        float(many(q, k, v))
        return (time.perf_counter() - t0) / K

    for label, kwargs, patch_exp in [
            ("prescale_off", {"prescale_q": False}, False),
            ("cheap_exp", {}, True)]:
        saved = flash_mod._EXP
        if patch_exp:
            flash_mod._EXP = lambda x: x * 0.25 + 1.0  # linear stand-in
        try:
            q, k, v, fa, fwd_flops = _flash_setup(**kwargs)
            dt = timed_fwd(fa, q, k, v)
        finally:
            flash_mod._EXP = saved
        tflops = fwd_flops / dt / 1e12
        recs.append({
            "metric": f"flash_fwd_experiment_{label}",
            "value": round(tflops, 1), "unit": "TFLOP/s",
            "vs_baseline": None,
            "pct_of_bf16_peak": round(tflops * 1e12 / _bf16_peak()
                                      * 100, 1),
            "ms": round(dt * 1e3, 3),
            "note": ("kernel-internal s*scale (pre-r4 behavior)"
                     if label == "prescale_off" else
                     "exp swapped for linear op — NOT valid attention; "
                     "roofline probe only"),
        })
    return recs


def bench_pallas_census():
    """Compile + execute every Pallas kernel on the real chip."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mpi4jax_tpu.ops.pallas_collectives import (
        ring_shift, ring_shift2, ring_shift_n, _make_alltoall_kernel)

    mesh = Mesh(np.array(jax.devices()[:1]), ("r",))
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    ok, total, failures = 0, 0, []

    def shard(f, nin=1):
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("r"),) * nin, out_specs=P("r"),
            check_vma=False))

    def attempt(fn):
        # partial failures count, not abort
        nonlocal ok, total
        total += 1
        try:
            out = fn()
            float(np.sum(np.asarray(
                jax.tree_util.tree_leaves(out)[0], dtype=np.float32)))
            ok += 1
        except Exception as err:
            failures.append(f"{type(err).__name__}: {err}"[:160])

    # RDMA hop kernels as size-1-ring loopback DMAs
    attempt(lambda: shard(
        lambda v: ring_shift(v, "r", 1, interpret=False))(x))
    attempt(lambda: shard(
        lambda a: sum(ring_shift2(a, a + 1, "r", interpret=False)))(x))
    attempt(lambda: shard(
        lambda a: sum(ring_shift_n((a, a * 2, a * 3), "r", 1,
                                   interpret=False)))(x))

    def direct_a2a(v):
        meta = jnp.stack([jnp.int32(0), jnp.int32(0)])
        return pl.pallas_call(
            _make_alltoall_kernel(1),
            out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,)),
                            pltpu.SemaphoreType.DMA((1,))],
            interpret=False,
        )(meta, v)

    attempt(lambda: jax.jit(jax.shard_map(
        direct_a2a, mesh=mesh, in_specs=P(None, "r"),
        out_specs=P(None, "r"), check_vma=False))(x[None]))

    # flash fwd + bwd kernels (fwd/dq/dkv) via value_and_grad
    q, k, v, fa, _ = _flash_setup()
    attempt(lambda: jax.jit(fa)(q, k, v))
    attempt(lambda: jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(fa(a, b, c).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v))

    # fused shallow-water step kernel (fuse=1 and fuse=2 variants)
    from mpi4jax_tpu.models import _sw_pallas
    from mpi4jax_tpu.models.shallow_water import ShallowWater, SWParams
    from mpi4jax_tpu.parallel.grid import ProcessGrid

    grid = ProcessGrid((1, 1), devices=jax.devices()[:1])
    model = ShallowWater(grid, (256, 512), SWParams(dx=5e3, dy=5e3))
    s0 = model.init()
    shape = s0.h.shape
    for fuse in (1, 2):
        sp = _sw_pallas.pad_rows(s0, tile_rows=128, fuse=fuse)
        attempt(lambda: jax.jit(
            lambda st: jnp.sum(_sw_pallas.fused_step(
                st, model.params, first=False, logical_shape=shape,
                tile_rows=128, fuse=fuse).h))(sp))

    rec = {
        "metric": "pallas_kernels_compiled_on_tpu",
        "value": ok, "unit": f"of {total} kernels",
        "vs_baseline": None,  # reference has no device kernels at all
        "detail": "hop, bidir, multi, direct-alltoall, flash fwd, "
                  "flash bwd (dq+dkv), sw fused (fuse=1, fuse=2)",
    }
    if failures:
        rec["failures"] = failures
    return rec


def bench_world_on_tpu():
    """1-rank world job on the TPU (ordered host callbacks in jit).

    Runs before the parent first touches the device: the rank needs the
    chip, and one process holds it at a time."""
    res = _run_tracked(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "1",
         "--port", "46100", "--platform", "tpu",
         os.path.join(REPO, "tests", "world_programs", "tpu_world.py")],
        capture_output=True, text=True, timeout=WORLD_TIMEOUT_S,
        cwd=REPO, env=dict(os.environ),
    )
    ok = res.returncode == 0 and "tpu_world OK" in res.stdout
    rec = {
        "metric": "world_tier_on_tpu_platform",
        "value": 1 if ok else 0, "unit": "ok",
        "vs_baseline": None,
        "rc": res.returncode,
    }
    if not ok:
        rec["stderr_tail"] = res.stderr[-800:]
    return rec


def bench_host_context():
    """Record the host's single-core copy bandwidth next to the loopback
    sweep: with N ranks time-sharing this machine's cores, an N-rank
    16 MB allreduce moves ~2N payloads through one memory system, so
    the sweep's ceiling is a host property — the record makes the
    comparison against multi-socket reference numbers interpretable."""
    import numpy as np

    n = 64 * 1024 * 1024
    a = np.ones(n, np.uint8)
    b = np.empty_like(a)
    np.copyto(b, a)  # warm
    t0 = time.perf_counter()
    for _ in range(4):
        np.copyto(b, a)
    dt = (time.perf_counter() - t0) / 4
    return {
        "metric": "host_context", "value": os.cpu_count(), "unit": "cores",
        "vs_baseline": None,
        "memcpy_GBps": round(n / dt / 1e9, 2),
        "note": "reference CPU table used 2x Xeon E5-2650 v4 (24 cores)",
    }


def _run_world_sweep(n_ranks, port, sizes=None, timeout_s=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
           "-n", str(n_ranks), "--port", str(port),
           os.path.join(REPO, "benchmarks", "allreduce_sweep.py"),
           "--world", "--max-mb", "17"]
    if sizes:
        cmd += ["--sizes", ",".join(str(s) for s in sizes)]
    res = _run_tracked(cmd, capture_output=True, text=True,
                       timeout=timeout_s, cwd=REPO, env=env)
    rows = []
    for line in res.stdout.splitlines():
        try:
            rows.append(json.loads(line))
        except (json.JSONDecodeError, ValueError):
            continue
    return res, rows


def bench_allreduce_sweep():
    """World-tier loopback allreduce: full np=8 sweep + np=2/np=4
    headline points (native transport, shm arena on this single host).

    Reports both the in-jit time (ops inside a compiled step function —
    the deployment shape) and the transport-level time (native call on
    host buffers) per point, labeled as such.
    """
    res, rows = _run_world_sweep(8, 46150)
    if res.returncode != 0 or not rows:
        return {
            "metric": "allreduce_world_np8_sweep", "value": None,
            "unit": "GB/s", "vs_baseline": None, "rc": res.returncode,
            "stderr_tail": res.stderr[-500:],
        }
    small = min(rows, key=lambda r: r["bytes"])
    big = max(rows, key=lambda r: r["bytes"])
    rec = {
        "metric": "allreduce_world_np8_sweep",
        "value": big["eff_GBps_per_chip"],
        "unit": "GB/s/rank eff (16MiB, in-jit)",
        "vs_baseline": None,  # BASELINE.json published: {} — first capture
        "eff_GBps_transport_16MiB": big.get("raw_eff_GBps_per_chip"),
        "small_msg_1KB_us_injit": round(small["seconds"] * 1e6, 1),
        "small_msg_1KB_us_transport": round(
            small.get("raw_seconds", small["seconds"]) * 1e6, 1),
        "sizes": len(rows), "ranks": big["ranks"],
    }
    out = [rec]
    for n_ranks, port in ((2, 46170), (4, 46180)):
        try:
            res, rows = _run_world_sweep(
                n_ranks, port, sizes=[1024, 16 * 1024 * 1024],
                timeout_s=300)
            big = max(rows, key=lambda r: r["bytes"])
            small = min(rows, key=lambda r: r["bytes"])
            out.append({
                "metric": f"allreduce_world_np{n_ranks}_16MiB",
                "value": big["eff_GBps_per_chip"],
                "unit": "GB/s/rank eff (in-jit)",
                "vs_baseline": None,
                "eff_GBps_transport": big.get("raw_eff_GBps_per_chip"),
                "small_msg_1KB_us_injit": round(
                    small["seconds"] * 1e6, 1),
            })
        except Exception as err:
            out.append({
                "metric": f"allreduce_world_np{n_ranks}_16MiB",
                "value": None, "vs_baseline": None,
                "error": f"{type(err).__name__}: {err}"[:200],
            })
    return out


def bench_dp_resnet():
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu.models import resnet

    def run(cfg, B, K, label):
        mesh = m4j.make_mesh(1)
        params = resnet.init_params(cfg)
        step = resnet.make_dp_train_step(cfg, mesh, lr=0.05)
        x = jnp.ones((B, 224, 224, 3), jnp.float32)
        y = jnp.zeros((B,), jnp.int32)

        @jax.jit
        def many(params, x, y):
            def one(p, _):
                loss, p = step(p, x, y)
                return p, loss
            p, losses = jax.lax.scan(one, params, None, length=K)
            return losses[-1]

        float(many(params, x, y))
        t0 = time.perf_counter()
        loss = float(many(params, x, y))
        dt = (time.perf_counter() - t0) / K
        return {
            "metric": f"dp_{label}_grad_allreduce_step_bf16",
            "value": round(B / dt, 1), "unit": "img/s",
            "vs_baseline": None,  # BASELINE.json published: {}
            "ms_per_step": round(dt * 1e3, 1), "batch": B,
            "loss_finite": bool(loss == loss),
        }

    # BASELINE.md names ResNet-50: bottleneck (3,4,6,3)
    return run(resnet.resnet50_config(dtype="bfloat16"), 32, 5, "resnet50")


def bench_gpt2_step():
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu.models.transformer import GPT, GPTConfig, init_params

    import numpy as np
    from jax.sharding import Mesh

    cfg = GPTConfig(vocab=50304, d_model=768, n_heads=12, n_layers=12,
                    d_ff=3072, max_seq=1024, dtype="bfloat16")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "tp", "sp"))
    model = GPT(cfg, mesh)
    params = init_params(cfg, tp=1)
    opt_state = model.init_opt_state(params)
    step = model.train_step_fn(opt_state)
    B, T = 8, 1024
    tokens = jnp.ones((B, T), jnp.int32)
    K = 3

    @jax.jit
    def many(params, opt_state, tokens):
        def one(carry, _):
            p, o = carry
            loss, p, o = step(p, o, tokens)
            return (p, o), loss
        (p, o), losses = jax.lax.scan(
            one, (params, opt_state), None, length=K)
        return losses[-1]

    float(many(params, opt_state, tokens))
    t0 = time.perf_counter()
    loss = float(many(params, opt_state, tokens))
    dt = (time.perf_counter() - t0) / K

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    flops = 6 * n_params * B * T  # fwd+bwd dense estimate
    tflops = flops / dt / 1e12
    return {
        "metric": "gpt2_124M_train_step_bf16",
        "value": round(B * T / dt, 0), "unit": "tokens/s",
        "vs_baseline": None,  # BASELINE.json published: {} — first capture
        "ms_per_step": round(dt * 1e3, 1),
        "model_TFLOPs": round(tflops, 1),
        "pct_of_bf16_peak": round(tflops * 1e12 / _bf16_peak() * 100, 1),
        "params_M": round(n_params / 1e6, 1),
        "loss_finite": bool(loss == loss),
        # BASELINE.md names "PP GPT-2 124M via point-to-point"; pipeline
        # parallelism needs >1 device, so on this single chip the battery
        # measures the same model dense (dp=tp=sp=1) and the PP path
        # (models/pp_transformer.py, ppermute handoffs) executes in
        # dryrun_multichip section 2 on the virtual mesh every round
        "pp_note": "PP path exercised in dryrun_multichip (1 chip here)",
    }


def bench_spectral():
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu.models import spectral

    mesh = m4j.make_mesh(1, axis="x")
    n = 256
    shape = (n, n, n)
    f = jnp.ones((n, n, n), jnp.float32)
    K = 5

    solve = m4j.spmd(
        lambda v: spectral.poisson_solve(v, axis="x", shape=shape),
        mesh=mesh)

    @jax.jit
    def many(f):
        def one(cur, _):
            return solve(cur), ()
        out, _ = jax.lax.scan(one, f, None, length=K)
        return jnp.sum(out)

    float(many(f))
    t0 = time.perf_counter()
    float(many(f))
    dt = (time.perf_counter() - t0) / K
    return {
        "metric": "spectral_poisson_fft_alltoall_256cubed",
        "value": round(dt * 1e3, 2), "unit": "ms/solve",
        "vs_baseline": None,  # BASELINE.json published: {} — first capture
    }


# total wall-clock the battery may use, end to end; the budget guard
# ends it with a complete artifact before an external kill can land
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "2500"))
T_START = time.time()


def _budget_remaining():
    return TOTAL_BUDGET_S - (time.time() - T_START)


class Battery:
    """Holds every section record and owns the output contract.

    ``record()`` prints the per-section line AND the refreshed summary
    line, under one lock — so stdout's last complete line is always the
    full artifact, whatever kills the process next.
    """

    def __init__(self, section_names, headline_metric):
        # RLock: the SIGTERM handler runs on the main thread and may
        # interrupt a record() that already holds the lock
        self._lock = threading.RLock()
        self._names = list(section_names)
        self._done = {}         # section name -> list of records
        self._headline = headline_metric
        self.note = None

    def record(self, name, rec, reprint_summary=True):
        recs = rec if isinstance(rec, list) else [rec]
        with self._lock:
            self._done.setdefault(name, []).extend(recs)
            for r in recs:
                print(json.dumps(r), flush=True)
            if reprint_summary:
                print(json.dumps(self._summary_locked()), flush=True)

    def _summary_locked(self):
        metrics = []
        for name in self._names:
            if name in self._done:
                metrics.extend(self._done[name])
            else:
                metrics.append(_skip_record(
                    name, "pending: section had not run when the "
                          "summary was (re)printed"))
        headline = next(
            (m for m in metrics
             if m["metric"] == self._headline and m.get("value") is not None),
            {"metric": self._headline, "value": None, "unit": "s",
             "vs_baseline": None},
        )
        final = dict(headline)
        if self.note:
            final["battery_note"] = self.note
        final["battery_elapsed_s"] = round(time.time() - T_START, 1)
        final["metrics"] = metrics
        return final

    def print_summary(self):
        with self._lock:
            print(json.dumps(self._summary_locked()), flush=True)

    def final_exit(self, note, rc=1):
        """Print the full summary and exit WITHOUT releasing the lock:
        no other thread can start a partial stdout write between the
        final summary line and process death.

        ``os._exit`` here is a deliberate trade: every final_exit path
        fires only when an external kill is already imminent (driver
        timeout, delivered signal) — the alternative is SIGKILL with no
        artifact.  Tracked child processes are killed first so they
        cannot outlive the battery holding ports or the chip."""
        self._lock.acquire()
        try:
            self.note = note
            with _CHILDREN_LOCK:
                children = list(_CHILDREN)
            for proc in children:
                try:
                    proc.kill()
                except Exception:
                    pass
            # leading newline: if the kill interrupted a half-written
            # stdout line, the summary still starts a fresh line
            sys.stdout.write("\n" + json.dumps(self._summary_locked())
                             + "\n")
            sys.stdout.flush()
        finally:
            os._exit(rc)

# sections that never touch the device
CPU_SECTIONS = [
    ("host_context", bench_host_context),
    ("allreduce_sweep", bench_allreduce_sweep),
]

# device sections, all run from ONE parent process (world_on_tpu is the
# exception: its rank subprocess needs the chip, so it runs before the
# parent first touches the device)
TPU_SECTIONS = [
    ("world_on_tpu", bench_world_on_tpu),
    ("shallow_water", bench_shallow_water),
    ("flash_mfu", bench_flash_mfu),
    ("pallas_census", bench_pallas_census),
    ("dp_resnet", bench_dp_resnet),
    ("gpt2", bench_gpt2_step),
    ("spectral", bench_spectral),
]

HEADLINE = "shallow_water_1800x3600_0.1day_1chip"


def _skip_record(name, reason):
    metric = {"shallow_water": HEADLINE,
              "world_on_tpu": "world_tier_on_tpu_platform"}.get(name, name)
    return {"metric": metric, "value": None, "unit": None,
            "vs_baseline": None, "error": reason}


def main():
    use_compile_cache()
    battery = Battery(
        [n for n, _ in CPU_SECTIONS] + [n for n, _ in TPU_SECTIONS],
        HEADLINE)

    # a complete artifact exists from second zero
    battery.print_summary()

    def _on_signal(signum, frame):
        # a second delivery must not re-enter mid-print (the RLock would
        # let the same thread interleave two summaries into one line)
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        battery.final_exit(f"terminated by signal {signum}")

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    def _budget_guard():
        while True:
            rem = _budget_remaining()
            if rem <= 75:
                battery.final_exit(
                    f"total budget {TOTAL_BUDGET_S:.0f}s exhausted; "
                    "remaining sections recorded as pending")
            time.sleep(max(1.0, min(30.0, rem - 70.0)))

    threading.Thread(target=_budget_guard, daemon=True).start()

    failed = []
    platform = None
    for name, fn in CPU_SECTIONS + TPU_SECTIONS:
        if name == "shallow_water":
            # the world child is done: the parent may claim the chip now
            import jax

            platform = jax.devices()[0].platform
        if platform not in (None, "tpu"):  # no fallback to another device
            failed.append(name)
            battery.record(name, _skip_record(
                name, f"no TPU: jax found {platform}"))
            continue
        try:
            rec = fn()
        except Exception as err:  # record it, run the other sections
            failed.append(name)
            rec = {"metric": name, "value": None, "vs_baseline": None,
                   "error": f"{type(err).__name__}: {err}"[:300]}
        battery.record(name, rec)
        if name == "world_on_tpu" and not rec.get("value"):
            failed.append(name)
    if failed:
        print(f"bench: sections failed: {', '.join(failed)}",
              file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
