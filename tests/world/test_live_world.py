"""World-tier live re-tuning: a mid-run epoch swap must land on every
rank at the same collective boundary, and — for agreement-free exact
ops (int32 SUM) — must not change a single result bit.

The program is pkg-stub loaded (bridge-level, no jax import), so this
axis runs in every container.  The harness runs the same op sequence
twice — live armed with a mid-run proposal, and live off — and pins:

- every rank reports the SAME nonzero epoch (the rendezvous agreement
  property, here on real sockets rather than the match simulator);
- the swapped run's result digests are bit-identical to the live-off
  run's (int32 SUM is exact under every algorithm the table can name,
  so a swap that changed results would be a dispatch bug, not fp
  reassociation);
- the live-off run reports epoch 0 and zero swaps (the off = bit-for-
  bit guarantee's world half).
"""

import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_port = [26900]

_PROG = r"""
import hashlib, os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu import live
from mpi4jax_tpu.runtime import bridge, transport

comm = transport.get_world_comm()
rank, size = comm.rank(), comm.size()
h = comm.handle

dig = hashlib.sha256()
x = (np.arange(4096, dtype=np.int32) %% 977) + 1
for step in range(30):
    out = bridge.allreduce(h, x + step, 0)  # SUM
    assert out[0] == (x[0] + step) * size, (step, out[0])
    dig.update(out.tobytes())
    if step == 9 and rank == 0 and live.armed():
        # flip every allreduce to recursive doubling mid-run; the
        # rendezvous installs it on all ranks a few boundaries later
        live.propose({"allreduce": [(0, "rd")]}, note="world-test")
st = live.status()
swaps = len(st.get("swaps", []))
print("live_swap rank %%d epoch %%d swaps %%d digest %%s"
      %% (rank, st.get("epoch", 0), swaps, dig.hexdigest()), flush=True)
"""


def _run(np_, live_on):
    _port[0] += np_ + 3
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    # force the TCP path so the installed table actually dispatches
    # (the same-host shm arena would shadow the algorithm choice)
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    env["MPI4JAX_TPU_LIVE_COOLDOWN_OPS"] = "8"   # rendezvous every 2
    with tempfile.TemporaryDirectory(prefix="m4j_live_world_") as td:
        prog = os.path.join(td, "prog.py")
        with open(prog, "w") as f:
            f.write(_PROG % REPO)
        # launcher as a FILE (the test_topology.py idiom): `-m` would
        # import the package, and with it the jax version gate
        args = [sys.executable,
                os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
                "-n", str(np_), "--port", str(_port[0])]
        if live_on:
            args.append("--live")           # the launcher flag axis
        args.append(prog)
        return subprocess.run(args, capture_output=True, text=True,
                              timeout=180, env=env, cwd=REPO)


_LINE_RE = re.compile(
    r"live_swap rank (\d+) epoch (\d+) swaps (\d+) digest ([0-9a-f]{64})")


def _parse(stdout, np_):
    rows = {int(r): (int(e), int(s), d)
            for r, e, s, d in _LINE_RE.findall(stdout)}
    assert sorted(rows) == list(range(np_)), stdout
    return rows


def test_mid_run_swap_same_epoch_and_bit_identical_digests():
    np_ = 2
    live_run = _run(np_, live_on=True)
    assert live_run.returncode == 0, live_run.stderr + live_run.stdout
    off_run = _run(np_, live_on=False)
    assert off_run.returncode == 0, off_run.stderr + off_run.stdout
    live_rows = _parse(live_run.stdout, np_)
    off_rows = _parse(off_run.stdout, np_)

    # agreement: every rank took the swap, at the same epoch
    epochs = {e for e, _, _ in live_rows.values()}
    assert epochs == {1}, live_rows
    assert all(s == 1 for _, s, _ in live_rows.values()), live_rows
    # the commit really happened mid-run (rank 0 logs the boundary)
    assert "[live] epoch 1 committed" in live_run.stderr, \
        live_run.stderr[-2000:]

    # exactness: int32 SUM digests identical across ranks AND across
    # the swapped vs never-swapped runs
    digests = {d for _, _, d in live_rows.values()}
    assert len(digests) == 1, live_rows
    assert digests == {d for _, _, d in off_rows.values()}, \
        (live_rows, off_rows)

    # live off: no epoch, no swaps — bit-for-bit pre-live behavior
    assert all(e == 0 and s == 0 for e, s, _ in off_rows.values()), \
        off_rows
