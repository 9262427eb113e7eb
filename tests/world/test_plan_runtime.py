"""Plan-on vs plan-off equivalence of schedule-plan execution.

Three layers, mirroring the PR 5 coalescing suite's structure:

- bridge level (runs in ANY container — the ranks never import jax): a
  2-rank pipeline executes through the PlanRunner (ticketed posting,
  hoisted recv posts, deferred sends) and its received-bytes digests
  are bit-identical to the direct path, with the runner reporting the
  overlap it achieved and zero signature mismatches;
- package level: ``world_programs/
  false_serialization.py`` under the launcher with MPI4JAX_TPU_PLAN
  pointing at its verified compiled plan vs ``MPI4JAX_TPU_PLAN=0``
  produces identical per-rank digests — and ``launch --plan`` wires the
  whole flow (compile, prove, install) by itself;
- failure injection: a hang injected on a send INSIDE a concurrency
  group (a deferred posted send) still trips the transport deadline and
  tears the job down detectably with the plan armed.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")


def _port(slot):
    return 25400 + (os.getpid() * 5 + slot * 13) % 900


def _digests(stdout, marker):
    return sorted(re.findall(marker + r" (r\d+ [0-9a-f]{64})", stdout))


# ---- bridge level: runs everywhere (parent-package shim, no jax) ----

_BRIDGE_PROG = r"""
import hashlib, os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu.analysis import _events, _plan
from mpi4jax_tpu.runtime import bridge, planrt, transport

c = transport.get_world_comm()
h, r, n = c.handle, c.rank(), c.size()
nxt, prv = (r + 1) %% n, (r - 1 + n) %% n
ROUNDS, SHAPE = 4, (128 * 1024,)   # 512 KB f32: past the detach threshold

events = {}
for rank in range(n):
    evs = []
    for k in range(ROUNDS):
        evs.append(_events.CommEvent(rank, 2 * k, "send",
                                     dest=(rank + 1) %% n, tag=k,
                                     dtype="float32", shape=SHAPE))
        evs.append(_events.CommEvent(rank, 2 * k + 1, "recv",
                                     source=(rank - 1 + n) %% n, tag=k,
                                     dtype="float32", shape=SHAPE))
    events[rank] = evs
comms = {(0,): tuple(range(n))}

rt = None
if os.environ.get("USE_PLAN") == "1":
    plan = _plan.compile_schedules(events, comms)
    assert plan.proved, plan.reasons
    assert plan.rewritten, plan.format()
    assert planrt.install(h, plan, r), "planrt.install refused"
    rt = planrt.get(c)
    assert rt is not None

digest = hashlib.sha256()
for k in range(ROUNDS):
    out_data = np.arange(SHAPE[0], dtype=np.float32) + 1000 * r + k
    if rt is not None:
        assert rt.run_send(out_data, nxt, k), "send not handled"
        got = rt.run_recv(SHAPE, np.float32, prv, k)
        assert got is not None, "recv not handled"
    else:
        bridge.send(h, out_data, nxt, k)
        got = bridge.recv(h, SHAPE, np.float32, prv, k)
    assert got[0] == 1000 * prv + k, (r, k, got[0])
    digest.update(got.tobytes())

if rt is not None:
    rt.flush()
    assert rt.stats["mismatches"] == 0, rt.stats
    assert rt.stats["hoisted_recvs"] > 0, rt.stats
    assert rt.stats["deferred_sends"] > 0, rt.stats
bridge.barrier(h)
print("bridge_plan digest r%%d %%s" %% (r, digest.hexdigest()), flush=True)
print("bridge_plan OK", flush=True)
"""


def _run_bridge_prog(tmp_path, port, env_extra):
    prog = tmp_path / "bridge_plan.py"
    prog.write_text(_BRIDGE_PROG % REPO)
    env = dict(os.environ)
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"  # ticketed posts ride TCP
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "mpi4jax_tpu/runtime/launch.py"),
         "-n", "3", "--port", str(port), str(prog)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO,
    )


def test_bridge_level_plan_execution_bit_identical(tmp_path):
    res_on = _run_bridge_prog(tmp_path, _port(0), {"USE_PLAN": "1"})
    assert res_on.returncode == 0, res_on.stderr + res_on.stdout
    assert res_on.stdout.count("bridge_plan OK") == 3
    res_off = _run_bridge_prog(tmp_path, _port(1), {"USE_PLAN": "0"})
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout
    d_on = _digests(res_on.stdout, "bridge_plan digest")
    d_off = _digests(res_off.stdout, "bridge_plan digest")
    assert d_on == d_off and len(d_on) == 3, (d_on, d_off)


def test_bridge_level_plan_with_engine_off_still_bit_identical(tmp_path):
    # MPI4JAX_TPU_PROGRESS_THREAD=0: posts execute inline, tickets are
    # pre-completed — the plan degrades to serialized execution, never
    # to different results
    res = _run_bridge_prog(tmp_path, _port(2), {
        "USE_PLAN": "1", "MPI4JAX_TPU_PROGRESS_THREAD": "0"})
    assert res.returncode == 0, res.stderr + res.stdout
    res_off = _run_bridge_prog(tmp_path, _port(3), {"USE_PLAN": "0"})
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout
    assert _digests(res.stdout, "bridge_plan digest") == \
        _digests(res_off.stdout, "bridge_plan digest")


# ---- package level: the real ops layer under the launcher ----------


def _run_launcher(args, env_extra, timeout=300):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def _emit_plan(tmp_path, prog, np_):
    plan_path = tmp_path / "plan.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.analyze", prog,
         "--np", str(np_), "--emit-plan", str(plan_path)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr + res.stdout
    return str(plan_path)


def test_false_serialization_plan_on_off_bit_identical(tmp_path):
    prog = os.path.join(PROGRAMS, "false_serialization.py")
    plan_path = _emit_plan(tmp_path, prog, 3)
    res_on = _run_launcher(["-n", "3", "--port", str(_port(4)), prog],
                           {"MPI4JAX_TPU_PLAN": plan_path})
    assert res_on.returncode == 0, res_on.stderr + res_on.stdout
    assert "plan execution disabled" not in res_on.stderr, res_on.stderr
    res_off = _run_launcher(["-n", "3", "--port", str(_port(5)), prog],
                            {"MPI4JAX_TPU_PLAN": "0"})
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout
    d_on = _digests(res_on.stdout, "false_serialization digest")
    d_off = _digests(res_off.stdout, "false_serialization digest")
    assert d_on == d_off and len(d_on) == 3, (d_on, d_off)


def test_launch_plan_flag_compiles_and_installs(tmp_path):
    prog = os.path.join(PROGRAMS, "false_serialization.py")
    res = _run_launcher(
        ["-n", "3", "--port", str(_port(6)), "--plan", prog], {})
    assert res.returncode == 0, res.stderr + res.stdout
    assert "--plan: verified plan" in res.stderr, res.stderr[-2000:]
    assert res.stdout.count("false_serialization OK") == 3


def test_fault_inside_concurrency_group_still_detected(tmp_path):
    # the 2nd logical send of rank 1 hangs INSIDE a plan concurrency
    # group (a deferred posted send on the progress thread): the
    # progress-based deadline must still trip and tear the job down
    prog = os.path.join(PROGRAMS, "false_serialization.py")
    plan_path = _emit_plan(tmp_path, prog, 3)
    res = _run_launcher(
        ["-n", "3", "--port", str(_port(7)), "--timeout", "120", prog],
        {"MPI4JAX_TPU_PLAN": plan_path,
         "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=1,action=hang",
         "MPI4JAX_TPU_TIMEOUT_S": "6"})
    assert res.returncode != 0
    blob = res.stderr
    assert "timed out" in blob or "deadline" in blob or "rank 1" in blob, \
        blob[-2000:]


def test_bucketed_dp_grad_plan_on(tmp_path):
    # bucketed vs per-leaf gradient sync asserts bit-identity inside the
    # program; run it with the plan armed so the bucketed allreduces
    # execute under the runner's cursor too
    prog = os.path.join(PROGRAMS, "bucketed_dp_grad.py")
    plan_path = _emit_plan(tmp_path, prog, 2)
    res = _run_launcher(["-n", "2", "--port", str(_port(8)), prog],
                        {"MPI4JAX_TPU_PLAN": plan_path})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("bucketed_dp_grad OK") == 2
