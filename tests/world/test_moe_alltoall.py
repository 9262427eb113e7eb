"""Alltoall family: quantized / hierarchical equivalence for the MoE
expert exchange.

All bridge-level through the launcher-as-file + the world programs'
parent-package shim, so the whole suite runs in ANY container (no jax
import inside the ranks) — the same pattern as the topology suite.

- ``moe_alltoall_ops.py`` at np=4 (2x2 islands) and np=6 (uneven 4+2),
  shm on and off: forced ring/qalltoall/halltoall/hqalltoall x
  {f32, bf16, i32} bit-compared against the flat default and the numpy
  codec simulators (``topo.simulate_qalltoall`` /
  ``simulate_halltoall`` / ``simulate_hqalltoall``), own-chunk /
  intra-island exactness, int8 error bound, global rank-consistency
  cross-check, i32 degrade;
- ``MPI4JAX_TPU_COLL_QUANT=deny`` degrades qalltoall -> ring and
  hqalltoall -> halltoall (exact bits); ``=force`` upgrades the default
  and forced-ring paths to the quantized wire;
- ``MPI4JAX_TPU_HIER=deny`` degrades hqalltoall to the flat quantized
  exchange;
- a non-contiguous interleaved partition exercises the island-block ->
  world-rank reorder of the hierarchical schedule.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")

_port = [27340]


def _launch(np_, fake_hosts, expect_islands, *, timeout=300,
            env_extra=None):
    _port[0] += np_ + 5
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MPI4JAX_TPU_COLL_ALGO", None)
    env.pop("MPI4JAX_TPU_COLL_QUANT", None)
    env.pop("MPI4JAX_TPU_HIER", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["TOPO_EXPECT_ISLANDS"] = expect_islands
    env.setdefault("MPI4JAX_TPU_TIMEOUT_S", "120")
    if env_extra:
        env.update(env_extra)
    # launcher as a FILE: the rank programs use the parent-package
    # shim, and `-m` would import the package (jax gate) in the
    # launcher process
    return subprocess.run(
        [sys.executable,
         os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
         "-n", str(np_), "--port", str(_port[0]),
         "--fake-hosts", fake_hosts,
         os.path.join(PROGRAMS, "moe_alltoall_ops.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("np_,fake,expect,shm", [
    (4, "r0,r1|r2,r3", "0,0,1,1", "on"),
    (4, "r0,r1|r2,r3", "0,0,1,1", "off"),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1", "on"),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1", "off"),
])
def test_alltoall_family_equivalence(np_, fake, expect, shm):
    env = {"MPI4JAX_TPU_DISABLE_SHM": "1" if shm == "off" else ""}
    res = _launch(np_, fake, expect, env_extra=env)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_alltoall_ops OK") == np_


def test_noncontiguous_islands():
    # islands need not be contiguous rank ranges: the hierarchical
    # alltoall's member-order compaction and (island, member) ->
    # world-rank unpack are exercised by an interleaved partition
    res = _launch(4, "r0,r2|r1,r3", "0,1,0,1")
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_alltoall_ops OK") == 4


@pytest.mark.parametrize("np_,fake,expect", [
    (4, "r0,r1|r2,r3", "0,0,1,1"),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1"),
])
def test_quant_deny_gate(np_, fake, expect):
    # deny degrades qalltoall -> ring and hqalltoall -> halltoall; the
    # program switches every quantized expectation to exact bits
    res = _launch(np_, fake, expect,
                  env_extra={"MPI4JAX_TPU_COLL_QUANT": "deny"})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_alltoall_ops OK") == np_


def test_quant_force_gate():
    # force upgrades the AUTO default and forced ring to qalltoall and
    # forced halltoall to hqalltoall — the program's simulator
    # expectations switch to the quantized twins (i32 stays exact:
    # the dtype is codec-ineligible)
    res = _launch(4, "r0,r1|r2,r3", "0,0,1,1",
                  env_extra={"MPI4JAX_TPU_COLL_QUANT": "force"})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_alltoall_ops OK") == 4


def test_hier_deny_gate():
    # deny degrades hqalltoall to the flat quantized exchange (the
    # quant axis survives — one gate per axis)
    res = _launch(4, "r0,r1|r2,r3", "0,0,1,1",
                  env_extra={"MPI4JAX_TPU_HIER": "deny"})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_alltoall_ops OK") == 4


@pytest.mark.parametrize("shm", ["on", "off"])
def test_moe_ops_live_uneven_islands(shm):
    # the verify-corpus MoE program (router + rank-sharded experts,
    # exact + quantized + forced-hierarchical dispatch) run LIVE on an
    # uneven 3+1 island partition, shm on and off.  The
    # static-verifier + golden-plan coverage of the same program runs
    # everywhere via make verify-corpus.
    _port[0] += 9
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_TIMEOUT_S"] = "120"
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1" if shm == "off" else ""
    res = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
         "-n", "4", "--port", str(_port[0]),
         "--fake-hosts", "r0,r1,r2|r3",
         os.path.join(PROGRAMS, "moe_ops.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("moe_ops OK") == 4
