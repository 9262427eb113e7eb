"""Topology subsystem: discovery, hierarchical equivalence, elastic
rediscovery.

All bridge-level through the launcher-as-file + the world programs'
parent-package shim, so the whole suite runs in ANY container (no jax
import inside the ranks) — the same pattern as the coalescing and
elastic bridge tests.

- ``topo_ops.py`` at np=4 (2x2 islands) and np=6 (uneven 4+2), shm on
  and off: hring/htree x {f32, bf16} x {SUM, MAX} bit-compared against
  the flat default and the numpy schedule simulators
  (``topo.simulate_hring_sum``), rank consistency, hierarchical
  allgather/bcast/reduce, discovery + native-map assertions;
- ``MPI4JAX_TPU_HIER=deny`` runs the same program with the
  hierarchical default degraded (the program's flat-vs-hring pair
  still holds: forced hring degrades to ring bit-for-bit);
- ``MPI4JAX_TPU_ICI_LEG=force`` runs it with the ICI data-plane leg
  active (exact, and composed with ``COLL_QUANT=force`` for the
  in-kernel int8 wire), parity against ``simulate_hring_sum(...,
  intra="ring")`` / ``simulate_ici_q_sum``; ``off`` must be inert;
- elastic: a rank death that EMPTIES an island shrinks np=3 (2+1) to
  np=2 and the rebuilt world re-discovers a clean flat topology.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")

# pid-mixed base (the test_sanitizers.py idiom): concurrent pytest
# processes land in disjoint port windows instead of all racing for
# one fixed base — the forced-ICI-leg launch was flaking on exactly
# that collision.  Per-launch strides below keep launches within one
# process apart; the 23200–24000 window is unused by the other world
# suites.
_port = [23200 + (os.getpid() * 41) % 600]


def _launch(program, np_, fake_hosts, expect_islands, *, timeout=300,
            env_extra=None, extra_args=()):
    _port[0] += np_ + 5
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MPI4JAX_TPU_COLL_ALGO", None)
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
         "--fake-hosts", fake_hosts, *extra_args,
         os.path.join(PROGRAMS, program)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("np_,fake,expect,shm", [
    (4, "r0,r1|r2,r3", "0,0,1,1", "on"),
    (4, "r0,r1|r2,r3", "0,0,1,1", "off"),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1", "on"),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1", "off"),
])
def test_hier_equivalence(np_, fake, expect, shm):
    env = {"MPI4JAX_TPU_DISABLE_SHM": "1" if shm == "off" else ""}
    res = _launch("topo_ops.py", np_, fake, expect, env_extra=env)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_ops OK") == np_


@pytest.mark.parametrize("np_,fake,expect,quant", [
    (4, "r0,r1|r2,r3", "0,0,1,1", False),
    (6, "r0,r1,r2,r3|r4,r5", "0,0,0,0,1,1", False),
    (4, "r0,r1|r2,r3", "0,0,1,1", True),
])
def test_ici_leg_forced_equivalence(np_, fake, expect, quant):
    # MPI4JAX_TPU_ICI_LEG=force routes every f32 SUM hring/htree
    # through the ICI data plane (topo/_ici_leg.py — the Pallas fused
    # ring's numpy twin in a jax-less container): the program's
    # simulator expectation switches to intra="ring" and every exact
    # row must stay bit-identical to the native paths.  With
    # COLL_QUANT=force on top, the leader leg exchanges the in-kernel
    # int8 wire frames and parity is against simulate_ici_q_sum.
    env = {"MPI4JAX_TPU_ICI_LEG": "force"}
    if quant:
        env["MPI4JAX_TPU_COLL_QUANT"] = "force"
    res = _launch("topo_ops.py", np_, fake, expect, env_extra=env)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_ops OK") == np_


def test_ici_leg_off_is_inert():
    # the explicit off mode must leave the native schedules untouched
    res = _launch("topo_ops.py", 4, "r0,r1|r2,r3", "0,0,1,1",
                  env_extra={"MPI4JAX_TPU_ICI_LEG": "off"})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_ops OK") == 4


def test_noncontiguous_islands():
    # islands need not be contiguous rank ranges: the allgather's
    # island-block -> world-rank reorder and the leader ordering
    # (dense ids by lowest member) are exercised by an interleaved
    # partition
    res = _launch("topo_ops.py", 4, "r0,r2|r1,r3", "0,1,0,1")
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_ops OK") == 4


def test_hier_deny_gate():
    # deny degrades the hierarchical default (and forced hring) to the
    # flat twins: the equivalence program still holds — every forced
    # hring IS a ring — except the default-pick assertion, which the
    # program skips when COLL_ALGO is exported
    res = _launch(
        "topo_ops.py", 4, "r0,r1|r2,r3", "0,0,1,1",
        env_extra={"MPI4JAX_TPU_HIER": "deny",
                   # the default-table assertion doesn't apply under
                   # deny; the program skips it when COLL_ALGO is set
                   "MPI4JAX_TPU_COLL_ALGO": "allreduce=ring"})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_ops OK") == 4


def test_full_ops_hier_force_axis():
    # the full op battery under MPI4JAX_TPU_HIER=force on a 2x2
    # partition: every allreduce/allgather upgrades to a hierarchical
    # twin and every large bcast/reduce routes through the leaders —
    # numerics must hold end to end (the forced-ring axis's sibling).
    _port[0] += 11
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_HIER"] = "force"
    res = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
         "-n", "4", "--port", str(_port[0]),
         "--fake-hosts", "r0,r1|r2,r3",
         os.path.join(PROGRAMS, "full_ops.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 4


_ELASTIC_PROG = r"""
import os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu import elastic, topo, tune
from mpi4jax_tpu.runtime import bridge, transport

comm = transport.get_world_comm()
t = comm.topology()
assert t is not None and t.multi, t
assert t.islands == [[0, 1], [2]], t.islands
assert comm.coll_algo("allreduce", 16 << 20) == "hring"

x = np.arange(70000, dtype=np.float32)
done = False
for step in range(6):
    try:
        if comm.rank() == 2 and step == 3:
            os._exit(17)  # island 1's only member dies mid-run
        out = bridge.allreduce(comm.handle, x + step, 0)
        assert np.array_equal(out, (x + step) * comm.size())
        if step >= 4:
            done = True
    except elastic.RankFailure:
        rec = elastic.recover(comm)
        # rank 2 WAS island 1: its death empties the island and the
        # rebuilt np=2 world must re-discover a clean FLAT topology
        t2 = comm.topology()
        assert t2 is not None and not t2.multi, t2
        assert t2.islands == [[0, 1]], t2.islands
        assert bridge.topo_info(comm.handle) == ([0, 0], 1)
        # flat map = flat defaults again (hring would degrade anyway);
        # both survivors share fake-host-0, so the rebuilt WORLD gets
        # the arena back ("shm") unless the suite's tcp axis is on
        assert comm.coll_algo("allreduce", 16 << 20) in ("shm", "ring")
        assert "defaults:topology" not in tune.sources()
        out = bridge.allreduce(comm.handle, x + 99, 0)
        assert np.array_equal(out, (x + 99) * 2), "post-shrink allreduce"
        done = True
        break
assert done
print("topo_elastic OK", comm.rank(), flush=True)
"""


def test_elastic_island_death_rediscovers_flat():
    """np=3 as islands [r0,r1]|[r2]: killing rank 2 empties island 1;
    the survivors shrink to np=2 and re-discover a flat single-island
    topology (sub-comms torn down, native map reinstalled, defaults
    back to flat)."""
    import tempfile

    _port[0] += 23
    with tempfile.TemporaryDirectory(prefix="m4j_topo_elastic_") as td:
        prog = os.path.join(td, "prog.py")
        with open(prog, "w") as f:
            f.write(_ELASTIC_PROG % REPO)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["MPI4JAX_TPU_TIMEOUT_S"] = "15"
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "3", "--port", str(_port[0]), "--elastic",
             "--fake-hosts", "r0,r1|r2", prog],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("topo_elastic OK") == 2, res.stdout
    assert "generation 1" in res.stderr, res.stderr[-2000:]
