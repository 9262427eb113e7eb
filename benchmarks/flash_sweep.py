"""Flash-attention block sweep + canonical-kernel comparison (real TPU).

Times `ops/flash.py::ring_flash_attention` (1-device ring = pure local
flash) across (block_q, block_k) and, when available, jax's own
`pallas.ops.tpu.flash_attention` on the same shape as the reference
point.  One JSON line per config.

    python benchmarks/flash_sweep.py [--shape B T H D]

Round 3 (before PR 1, not this code) read 26.9 TFLOP/s at blocks
1024/1024 against 10.6 for the canonical kernel at (4, 4096, 16, 128)
bf16 causal; not measured on this code.  The reference framework ships
no attention kernels at all (its long-context building block is the
token-ordered sendrecv ring, sendrecv.py:46-125 there).
"""

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=4, default=(4, 4096, 16, 128),
                    metavar=("B", "T", "H", "D"))
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from mpi4jax_tpu.ops.flash import ring_flash_attention

    B, T, H, D = args.shape
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in keys)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    flops = 2 * 2 * B * H * T * T * D * 0.5  # causal
    K = args.reps

    def timed(fa_call, qq, kk, vv):
        @jax.jit
        def many(q, k, v):
            def step(qc, _):
                return fa_call(qc, k, v).astype(qc.dtype), ()
            out, _ = jax.lax.scan(step, q, None, length=K)
            return jnp.sum(out.astype(jnp.float32))

        float(many(qq, kk, vv))  # compile + warmup
        t0 = time.perf_counter()
        float(many(qq, kk, vv))
        return (time.perf_counter() - t0) / K

    for bq, bk in [(1024, 1024), (2048, 1024), (512, 1024),
                   (1024, 512), (512, 512)]:
        fa = jax.shard_map(
            partial(ring_flash_attention, axis="sp", causal=True,
                    interpret=False, block_q=bq, block_k=bk),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        try:
            dt = timed(fa, q, k, v)
            print(json.dumps({"kernel": "ours", "bq": bq, "bk": bk,
                              "ms": round(dt * 1e3, 3),
                              "TFLOPs": round(flops / dt / 1e12, 1)}),
                  flush=True)
        except Exception as err:
            print(json.dumps({"kernel": "ours", "bq": bq, "bk": bk,
                              "error": f"{type(err).__name__}"[:60]}),
                  flush=True)

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash)
    except ImportError:
        return
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # (B,H,T,D)

    def canonical(qc, kc, vc):
        return jax_flash(qc, kc, vc, causal=True)

    dt = timed(canonical, qh, kh, vh)
    print(json.dumps({"kernel": "jax.pallas.ops.tpu.flash_attention",
                      "ms": round(dt * 1e3, 3),
                      "TFLOPs": round(flops / dt / 1e12, 1)}), flush=True)


if __name__ == "__main__":
    main()
