"""ICI data-plane leg crossover sweep (the BENCH_ici_leg.json generator).

Measures the hierarchical allreduce with its intra-island legs on the
ICI data plane (``topo/_ici_leg.py`` — MPI4JAX_TPU_ICI_LEG, docs/usage.md
§ Transport tiers and topology) against the native intra paths and the
flat ring, per payload size, on a ``--fake-hosts`` virtual partition:

    python benchmarks/ici_leg_sweep.py \
        --shapes 'np4_2island=4:r0,r1|r2,r3;np8_2island=8:r0,r1,r2,r3|r4,r5,r6,r7' \
        --sizes 65536,1048576,4194304,16777216 --out BENCH_ici_leg.json

This is a DRIVER (run it directly, not under the launcher): the knob
under test is process-wide, so each variant — ``ring``, ``hring``,
``hring+ici`` (MPI4JAX_TPU_ICI_LEG=force), ``hring+q``
(MPI4JAX_TPU_COLL_QUANT=force), ``hring+q+ici`` (both) — runs as its
own launched sub-job, and the rank-0 rows are assembled into the
BENCH_hier_crossover-shaped artifact (``{"note", "config", "sweeps"}``;
rows are ``obs.bench_record`` dicts carrying the ``knobs`` stamp).

Bridge-level with the parent-package shim (no jax import in the
ranks), so it runs in ANY container; every row names the leg backend
it actually measured (``leg_backend``: ``"pallas"`` where the rank
runs jax on a TPU holding the island's chips, ``"numpy"`` — the
bit-identical reference, Python-rate — elsewhere).  Numbers from the numpy twin bound the SCHEDULE (frames,
phases, association), not the TPU kernel.

Timing is the raw-transport shape of ``allreduce_sweep.py --world``:
barrier-synchronized per-call medians through ``bridge.allreduce_raw``
with the algorithm forced per call, constant input re-fed every call
(no in-place growth), and a correctness check per size (exact
variants bit-equal to ``x * n``, quantized within the documented int8
bound) so a silently-degraded leg cannot produce a labeled curve.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = (
    ("ring", "ring", {}),
    ("hring", "hring", {}),
    ("hring+ici", "hring", {"MPI4JAX_TPU_ICI_LEG": "force"}),
    ("hring+q", "hring", {"MPI4JAX_TPU_COLL_QUANT": "force"}),
    ("hring+q+ici", "hring", {"MPI4JAX_TPU_ICI_LEG": "force",
                              "MPI4JAX_TPU_COLL_QUANT": "force"}),
)


def rank_main():
    sys.path.insert(0, REPO)
    import types

    pkg = types.ModuleType("mpi4jax_tpu")
    pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
    sys.modules["mpi4jax_tpu"] = pkg

    import numpy as np

    from mpi4jax_tpu import obs, topo, tune
    from mpi4jax_tpu.runtime import bridge, transport

    F32, SUM = 11, 0
    label = os.environ["M4J_ICI_SWEEP_LABEL"]
    algo = os.environ["M4J_ICI_SWEEP_ALGO"]
    sizes = [int(s) for s in os.environ["M4J_ICI_SWEEP_SIZES"].split(",")]
    code = tune.ALGO_CODES[algo]
    quant = os.environ.get("MPI4JAX_TPU_COLL_QUANT", "") == "force"

    comm = transport.get_world_comm()
    h, n = comm.handle, comm.size()
    t = comm.topology()
    st = topo.ici_leg_status(h)

    # rows go to a FILE (driver-provided path), not stdout: the
    # launcher multiplexes rank streams and can interleave mid-line,
    # which would corrupt JSON rows
    rows_path = os.environ["M4J_ICI_SWEEP_ROWS"]
    rows = []
    for size in sizes:
        x = np.ones(size // 4, np.float32)
        out = np.empty_like(x)
        bridge.allreduce_raw(h, x, out, F32, SUM, algo=code)  # warm + align
        # the labeled curve must measure what the label says: exact
        # variants are bit-equal to x*n (all-ones payloads sum exactly
        # under EVERY association), the quantized wire stays inside
        # its documented bound
        if quant:
            assert float(np.max(np.abs(out / n - 1.0))) < 5e-2, label
        else:
            assert np.array_equal(out, x * n), label
        calls = max(6, min(30, int(4e8 / max(size, 1))))
        times = []
        for _ in range(calls):
            bridge.barrier(h)
            t0 = time.perf_counter()
            bridge.allreduce_raw(h, x, out, F32, SUM, algo=code)
            times.append(time.perf_counter() - t0)
        dt = obs.percentile(times, 50)
        if comm.rank() == 0:
            extra = {}
            if t is not None and t.multi:
                extra["topology"] = t.fingerprint()
                extra["islands"] = [len(m) for m in t.islands]
            if st["active"]:
                extra["leg_backend"] = st["backend"]
            rows.append(obs.bench_record(
                op="allreduce", nbytes=size, seconds=dt, ranks=n,
                tier="world", algo=label, resolved_algo=algo,
                raw_p95_us=round(obs.percentile(times, 95) * 1e6, 1),
                raw_eff_GBps_per_chip=round(
                    2 * (n - 1) / n * size / dt / 1e9, 3),
                **extra,
            ))
    if comm.rank() == 0:
        with open(rows_path, "w") as f:
            json.dump(rows, f)
    print("ici_leg_sweep OK", comm.rank(), flush=True)


def driver():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--shapes",
        default="np4_2island=4:r0,r1|r2,r3",
        help="semicolon list of label=np:fake_hosts partitions")
    ap.add_argument("--sizes", default="65536,1048576,4194304,16777216")
    ap.add_argument("--port", type=int, default=47810)
    ap.add_argument("--out", default=None,
                    help="write the artifact here (default: stdout)")
    args = ap.parse_args()

    port = [args.port]
    fake_hosts, sweeps = {}, {}
    for shape in args.shapes.split(";"):
        label, spec = shape.split("=", 1)
        np_s, hosts = spec.split(":", 1)
        fake_hosts[label] = hosts
        rows = []
        for vlabel, algo, gates in VARIANTS:
            port[0] += int(np_s) + 5
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.pop("MPI4JAX_TPU_COLL_ALGO", None)
            for k in ("MPI4JAX_TPU_ICI_LEG", "MPI4JAX_TPU_COLL_QUANT"):
                env.pop(k, None)
            env.update(gates)
            env["JAX_PLATFORMS"] = "cpu"
            env["M4J_ICI_SWEEP_LABEL"] = vlabel
            env["M4J_ICI_SWEEP_ALGO"] = algo
            env["M4J_ICI_SWEEP_SIZES"] = args.sizes
            rows_path = os.path.join(
                tempfile.gettempdir(),
                f"m4j_ici_sweep_{os.getpid()}_{label}_{vlabel}.json")
            env["M4J_ICI_SWEEP_ROWS"] = rows_path
            try:
                res = subprocess.run(
                    [sys.executable,
                     os.path.join(REPO, "mpi4jax_tpu", "runtime",
                                  "launch.py"),
                     "-n", np_s, "--port", str(port[0]),
                     "--fake-hosts", hosts, os.path.abspath(__file__)],
                    capture_output=True, text=True, timeout=900, cwd=REPO,
                    env=env)
                if res.returncode != 0:
                    sys.stderr.write(res.stderr[-3000:] + res.stdout[-500:])
                    raise SystemExit(
                        f"ici_leg_sweep: variant {vlabel} ({label}) failed")
                with open(rows_path) as f:
                    got = json.load(f)
            finally:
                if os.path.exists(rows_path):
                    os.unlink(rows_path)
            rows.extend(got)
            print(f"# {label} {vlabel}: {len(got)} rows", file=sys.stderr,
                  flush=True)
        sweeps[label] = rows

    artifact = {
        "note": (
            "ICI data-plane leg crossover: benchmarks/ici_leg_sweep.py — "
            "forced hring through bridge.allreduce_raw under "
            "launch --fake-hosts virtual partitions, one sub-job per "
            "process-wide variant (ring / hring / hring+ici / hring+q / "
            "hring+q+ici; gates in each row's knobs stamp).  f32 SUM, "
            "barrier-synchronized raw-transport per-call medians, "
            "constant input re-fed per call.  Rows with leg_backend name "
            "the data plane that actually served the intra legs; "
            "'numpy' is the Pallas fused ring's bit-identical twin "
            "running at Python rate — those curves bound the SCHEDULE "
            "(frames, phases, association, wire codec), not the TPU "
            "kernel, and the +ici variants are expected to trail the "
            "native intra paths off-TPU.  Quantized variants are "
            "approximate by design (checked to the int8 bound in-run)."
        ),
        "config": {
            "env": {"JAX_PLATFORMS": "cpu"},
            "fake_hosts": fake_hosts,
            "dtype": "float32",
            "op": "SUM",
            "host_cores": os.cpu_count(),
        },
        "sweeps": sweeps,
    }
    text = json.dumps(artifact, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"# wrote {args.out}", file=sys.stderr)
    else:
        print(text)


if __name__ == "__main__":
    if os.environ.get("M4J_ICI_SWEEP_LABEL"):
        rank_main()
    else:
        driver()
