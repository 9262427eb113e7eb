"""Async progress engine: coalescing equivalence and fault injection.

Three layers of proof that small-send coalescing is transparent:

- bridge level (runs in ANY container — the ranks never import jax):
  a burst program's received bytes digest bit-identically with the
  engine + coalescing on vs fully off;
- package level (like the other in-process world
  tests): ``world_programs/coalesce_ops.py`` under the launcher with
  coalescing on/off produces identical per-rank digests, and the SAME
  program verifies clean under the static analyzer unchanged —
  coalescing is invisible to the match model because buffered sends
  already are its semantics;
- failure injection: a fault landing on a send INSIDE a coalesced run
  (after=N counts logical sends, not wire frames) still tears the job
  down detectably, with the engine queue armed.
"""

import hashlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")

COALESCE_ON = {"MPI4JAX_TPU_PROGRESS_THREAD": "1",
               "MPI4JAX_TPU_COALESCE_BYTES": "4096"}
COALESCE_OFF = {"MPI4JAX_TPU_PROGRESS_THREAD": "0",
                "MPI4JAX_TPU_COALESCE_BYTES": "0"}

# ---- bridge level: runs everywhere (parent-package shim, no jax) ----

_BRIDGE_PROG = r"""
import hashlib, os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu.runtime import bridge, transport

c = transport.get_world_comm()
h, r, n = c.handle, c.rank(), c.size()
digest = hashlib.sha256()
for round_ in range(3):
    for peer in range(n):
        if peer == r:
            continue
        for i in range(24):
            m = 3 + (i %% 4) * 61
            bridge.send(h, np.arange(m, dtype=np.int32) + 10000 * r + i,
                        peer, 1000 * round_ + i)
    for peer in range(n):
        if peer == r:
            continue
        for i in range(24):
            m = 3 + (i %% 4) * 61
            got = bridge.recv(h, (m,), np.int32, peer, 1000 * round_ + i)
            assert got[0] == 10000 * peer + i, (peer, i, got[0])
            digest.update(got.tobytes())
    out = bridge.allreduce(h, np.ones(8), 0)
    assert abs(float(out[0]) - n) < 1e-9
    digest.update(out.tobytes())
bridge.barrier(h)
print("bridge_coalesce digest r%%d %%s" %% (r, digest.hexdigest()),
      flush=True)
print("bridge_coalesce OK", flush=True)
"""


def _port(slot):
    # pid-derived, slot-separated: fixed ports collide with lingering
    # sockets from neighbouring launcher tests on busy CI hosts
    return 26900 + (os.getpid() * 7 + slot * 11) % 800


def _run_bridge_prog(tmp_path, port, env_extra):
    prog = tmp_path / "bridge_coalesce.py"
    prog.write_text(_BRIDGE_PROG % REPO)
    env = dict(os.environ)
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"  # coalescing rides the TCP path
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "mpi4jax_tpu/runtime/launch.py"),
         "-n", "3", "--port", str(port), str(prog)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO,
    )


def _digests(stdout, marker):
    # regex, not line starts: the launcher merges rank stdout streams,
    # which can interleave another rank's partial line ahead of ours
    import re

    return sorted(re.findall(marker + r" (r\d+ [0-9a-f]{64})", stdout))


def test_bridge_level_coalescing_bit_identical(tmp_path):
    res_on = _run_bridge_prog(tmp_path, _port(0), COALESCE_ON)
    assert res_on.returncode == 0, res_on.stderr + res_on.stdout
    assert res_on.stdout.count("bridge_coalesce OK") == 3
    res_off = _run_bridge_prog(tmp_path, _port(1), COALESCE_OFF)
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout
    d_on = _digests(res_on.stdout, "bridge_coalesce digest")
    d_off = _digests(res_off.stdout, "bridge_coalesce digest")
    assert d_on == d_off and len(d_on) == 3, (d_on, d_off)


def test_bridge_level_fault_at_coalesced_boundary(tmp_path):
    """A crash injected on the 30th LOGICAL send of rank 0 — inside a
    coalesced run (24-message bursts merge into container frames) —
    must fail the job loudly with the queue armed, exactly like the
    uncoalesced wire would."""
    env = dict(COALESCE_ON)
    env["MPI4JAX_TPU_FAULT"] = "rank=0,point=send,after=30,action=exit"
    env["MPI4JAX_TPU_TIMEOUT_S"] = "6"
    res = _run_bridge_prog(tmp_path, _port(2), env)
    assert res.returncode != 0
    assert "fault injection" in res.stderr, res.stderr[-2000:]
    # the launcher's post-mortem names the injected rank as first-failing
    assert "rank 0" in res.stderr, res.stderr[-1500:]


# ---- package level: the real ops layer + the static verifier --------


def _run_launcher(np_, port, env_extra, timeout=300):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
         "-n", str(np_), "--port", str(port),
         os.path.join(PROGRAMS, "coalesce_ops.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_coalesce_ops_bit_identical_on_off():
    res_on = _run_launcher(2, _port(3) + 800, COALESCE_ON)
    assert res_on.returncode == 0, res_on.stderr + res_on.stdout
    assert res_on.stdout.count("coalesce_ops OK") == 2
    res_off = _run_launcher(2, _port(4) + 800, COALESCE_OFF)
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout
    d_on = _digests(res_on.stdout, "coalesce_ops digest")
    d_off = _digests(res_off.stdout, "coalesce_ops digest")
    assert d_on == d_off and len(d_on) == 2, (d_on, d_off)


def test_coalesce_ops_verifies_clean_unchanged():
    """The analyzer's verdict is knob-independent: the burst program
    passes the static verifier with zero findings — coalescing never
    changes the schedule the match model sees."""
    from mpi4jax_tpu import analysis

    report = analysis.check_program(
        os.path.join(PROGRAMS, "coalesce_ops.py"), 2)
    assert report.ok, report.format_table()
    assert all(len(v) > 0 for v in report.schedules.values())


def test_coalesce_ops_fault_hang_trips_deadline():
    """action=hang at a coalesced boundary: the unsent container frame
    leaves the receivers starved, and the progress deadline (measured
    from post time with the queue armed) must tear the job down."""
    # rank 0 is the burst sender in the chain topology; after=30 lands
    # inside its second-round burst (24 sends + ring/collective frames)
    env = dict(COALESCE_ON)
    env["MPI4JAX_TPU_FAULT"] = "rank=0,point=send,after=30,action=hang"
    env["MPI4JAX_TPU_TIMEOUT_S"] = "5"
    res = _run_launcher(2, _port(5) + 800, env, timeout=240)
    assert res.returncode != 0
    assert ("MPI4JAX_TPU_TIMEOUT_S" in res.stderr
            or "timed out" in res.stderr), res.stderr[-2500:]
