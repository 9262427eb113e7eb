"""Elastic worlds: deterministic end-to-end recovery tests.

Bridge level (runs in ANY container — the ranks use the parent-package
shim, no jax): a 3-rank DP training job whose rank 1 is killed by
``MPI4JAX_TPU_FAULT`` shrinks to np=2 (or respawns, per policy),
resumes from the last committed checkpoint, and finishes with the EXACT
state digest of an uninterrupted run; the continuous-batching serving
harness keeps answering requests across the same injected death.  The
launcher exits 0 and its post-mortem names the recovery outcome.

Package level (jax >= the package gate): the DP GPT-2 acceptance
scenario over the real ops layer, with the documented loss-parity
bound.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")
LAUNCHER = os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py")


def _port(slot):
    return 25700 + (os.getpid() * 7 + slot * 13) % 900


def _run(prog, np_, port, env_extra, *args, elastic=True, timeout=240,
         prog_args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"  # deterministic TCP fault points
    env.update(env_extra)
    argv = [sys.executable, LAUNCHER, "-n", str(np_), "--port", str(port)]
    argv += list(args)
    if elastic:
        argv.append("--elastic")
    argv.append(os.path.join(PROGRAMS, prog))
    argv += [str(a) for a in prog_args]
    return subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


def _digests(stdout, marker):
    return sorted(set(re.findall(marker + r" (?:r\d+ )?([0-9a-f]{64})",
                                 stdout)))


FAULT_EXIT = {"MPI4JAX_TPU_FAULT": "rank=1,point=send,after=14,action=exit",
              "MPI4JAX_TPU_TIMEOUT_S": "8"}


# ---- bridge level: training recovery (shrink) ----------------------


def test_shrink_recovery_matches_uninterrupted_run(tmp_path):
    """The acceptance scenario at the bridge level: rank 1 dies
    mid-job, the world shrinks 3 -> 2, training resumes from the last
    committed checkpoint, and the final state digest is BIT-IDENTICAL
    to an uninterrupted 3-rank run (the program's gradient sync is
    world-size invariant by construction)."""
    clean = _run("elastic_train.py", 3, _port(0),
                 {"MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "clean")},
                 prog_args=(12,))
    assert clean.returncode == 0, clean.stderr[-2000:]
    assert clean.stdout.count("elastic_train OK") == 3
    d_clean = _digests(clean.stdout, "elastic_train digest")
    assert len(d_clean) == 1, clean.stdout

    fault = _run("elastic_train.py", 3, _port(1),
                 {**FAULT_EXIT,
                  "MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "fault")},
                 prog_args=(12,))
    assert fault.returncode == 0, fault.stderr[-2000:]
    # two survivors finish; the dead rank prints nothing
    assert fault.stdout.count("elastic_train OK") == 2
    assert _digests(fault.stdout, "elastic_train digest") == d_clean
    # the recovery post-mortem names the outcome (satellite): the
    # generation reached, the slots lost, and the resume step
    assert "completed after recovery" in fault.stderr
    assert "generation 1" in fault.stderr
    assert "lost rank slot(s) [1]" in fault.stderr
    assert re.search(r"resumed from step \d+", fault.stderr), \
        fault.stderr[-800:]
    # survivors really did restore a COMMITTED mid-job checkpoint
    assert re.search(r"resuming from step [1-9]\d*", fault.stderr)


def test_respawn_recovery_all_ranks_finish(tmp_path):
    """respawn policy: the dead slot's program restarts (possibly
    dying again — the fault spec rides the environment), the world
    rebuilds at full size every time, and all 3 ranks finish with the
    uninterrupted digest."""
    res = _run("elastic_train.py", 3, _port(2),
               {**FAULT_EXIT,
                "MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "resp")},
               "--elastic-policy", "respawn", prog_args=(12,))
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("elastic_train OK") == 3
    assert len(_digests(res.stdout, "elastic_train digest")) == 1
    assert "policy respawn" in res.stderr
    assert "completed after recovery" in res.stderr
    # a respawned-and-finished slot is a death, not a loss — the
    # post-mortem must not claim slots were lost when all ranks finished
    assert "(respawned)" in res.stderr
    assert "lost rank slot(s)" not in res.stderr


def test_rank_failure_surfaces_as_exception(tmp_path):
    """MPI4JAX_TPU_ELASTIC turns the bridge's hard abort into a
    catchable RankFailure: a rank that handles it itself exits
    cleanly instead of being os._exit(1)'d."""
    prog = tmp_path / "catch.py"
    prog.write_text(
        "import os, sys, types\n"
        f"REPO = {REPO!r}\n"
        "sys.path.insert(0, REPO)\n"
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(REPO, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "import numpy as np\n"
        "from mpi4jax_tpu.elastic import RankFailure\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "c = transport.get_world_comm()\n"
        # comm creation itself is in the try block: the after=0 recv
        # fault fires inside the topology-discovery allgather at init
        # (comm_init's first collective), and the failure must surface
        # as a catchable RankFailure from WHEREVER the transport first
        # touches the dead peer
        "if c.rank() == 0:\n"
        "    try:\n"
        "        h = c.handle\n"
        "        bridge.recv(h, (4,), np.float64, 1, 7)\n"
        "        print('UNREACHABLE', flush=True)\n"
        "    except RankFailure as e:\n"
        "        print(f'caught RankFailure op={e.op}', flush=True)\n"
        # stay up long enough for the launcher to process rank 1's
        # death while this rank is alive: a survivor that handles the
        # failure itself and winds down is a completed job, not a
        # zero-survivor loss
        "    import time; time.sleep(2)\n"
        "else:\n"
        "    h = c.handle\n"
    )
    env = {"MPI4JAX_TPU_FAULT": "rank=1,point=recv,after=0,action=exit",
           "MPI4JAX_TPU_TIMEOUT_S": "6"}
    res = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "2", "--port", str(_port(3)),
         "--elastic", str(prog)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "MPI4JAX_TPU_DISABLE_SHM": "1", **env}, cwd=REPO)
    assert res.returncode == 0, res.stderr[-1500:]
    assert "caught RankFailure op=" in res.stdout
    assert "UNREACHABLE" not in res.stdout


# ---- bridge level: plans survive recovery (elastic-safe plans) -----


def test_shrink_reproves_and_keeps_the_plan(tmp_path):
    """The elastic-safe-plans acceptance scenario: a PLANNED job (every
    step's gradient allreduces run through an installed proved plan,
    signature-checked per op) loses rank 1 mid-job, shrinks 3 -> 2, and
    ``bridge.rebuild`` re-derives + re-PROVES the plan for the new
    world inside recovery — the job finishes with the plan still
    active, zero signature mismatches, and the EXACT digest of an
    uninterrupted planned run (instead of silently dropping to the
    unplanned path, the pre-PR-12 behavior)."""
    clean = _run("elastic_plan.py", 3, _port(10),
                 {"MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "clean")},
                 prog_args=(10,))
    assert clean.returncode == 0, clean.stderr[-2000:]
    assert clean.stdout.count("elastic_plan OK") == 3
    assert "plan_active=1" in clean.stdout
    d_clean = _digests(clean.stdout, "elastic_plan digest")
    assert len(d_clean) == 1, clean.stdout

    fault = _run("elastic_plan.py", 3, _port(11),
                 {"MPI4JAX_TPU_FAULT":
                      "rank=1,point=send,after=30,action=exit",
                  "MPI4JAX_TPU_TIMEOUT_S": "8",
                  "MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "fault")},
                 prog_args=(10,))
    assert fault.returncode == 0, fault.stderr[-2000:]
    # both survivors finish WITH the plan active and clean signatures
    assert fault.stdout.count("elastic_plan OK") == 2
    assert fault.stdout.count("np=2 plan_active=1 mismatches=0") == 2, \
        fault.stdout
    # recovery really did re-derive + re-prove (not reuse the np=3 plan)
    assert "re-proved plan" in fault.stderr, fault.stderr[-2000:]
    assert "np=2" in fault.stderr
    assert "overlap preserved across recovery" in fault.stderr
    assert "completed after recovery" in fault.stderr
    # bit-identical trajectory: the MAX sync is world-size invariant
    assert _digests(fault.stdout, "elastic_plan digest") == d_clean


# ---- bridge level: serving recovery --------------------------------


def test_serving_survives_rank_death():
    """Continuous batching across an injected worker death: every
    request completes, transcripts match an uninterrupted run exactly
    (in-flight iterations are retried, never committed twice), and the
    job exits 0."""
    clean = _run("elastic_serve.py", 3, _port(4), {}, prog_args=(10,))
    assert clean.returncode == 0, clean.stderr[-2000:]
    assert "elastic_serve OK nreq=10 recoveries=0" in clean.stdout
    d_clean = _digests(clean.stdout, "elastic_serve digest")

    fault = _run("elastic_serve.py", 3, _port(5),
                 {"MPI4JAX_TPU_FAULT":
                      "rank=1,point=recv,after=9,action=exit",
                  "MPI4JAX_TPU_TIMEOUT_S": "8"},
                 prog_args=(10,))
    assert fault.returncode == 0, fault.stderr[-2000:]
    assert "elastic_serve OK nreq=10 recoveries=1" in fault.stdout, \
        fault.stdout
    assert _digests(fault.stdout, "elastic_serve digest") == d_clean
    assert "retrying" in fault.stderr  # in-flight requests were retried


@pytest.mark.parametrize("plane", ["toy", "v2"])
def test_serving_frontend_death_releases_survivors(plane):
    """The rank-0 caveat, made orderly (satellite regression): when the
    FRONTEND dies, the worker promoted to rank 0 broadcasts STOP before
    raising its 'became the frontend' error, so the other survivors
    return from serve_worker instead of hanging in a headless bcast
    until the transport deadline.  Both serving planes share the
    contract."""
    res = _run("serve_frontend_death.py", 3, _port(20 if plane == "toy"
                                                   else 21),
               {"MPI4JAX_TPU_FAULT":
                    "rank=0,point=send,after=12,action=exit",
                "MPI4JAX_TPU_TIMEOUT_S": "8"},
               prog_args=(plane,))
    assert res.returncode == 0, res.stdout + res.stderr[-2000:]
    # exactly one survivor was promoted (and raised only after the
    # release); every other survivor exited its loop normally
    assert res.stdout.count("fd promoted clean") == 1, res.stdout
    assert res.stdout.count("fd worker done") == 1, res.stdout
    assert "fault did not fire" not in res.stdout


# ---- bridge level: serving v2 (disaggregated, KV cache) ------------


def test_serving_v2_commit_point_fault_retry_bit_identical():
    """The commit-point invariant on the v2 plane: a rank killed
    between prefill hand-off (the KV ship) and decode commit forces a
    recovery that drops all rank-local KV and re-prefills every
    in-flight request — and the completed transcripts are BYTE-
    IDENTICAL to an uninterrupted run (the toy adapter is exactly
    prefix-consistent, so a retried iteration cannot drift)."""
    args = ("--fake-hosts", "r0,r1|r2,r3")
    clean = _run("serve_v2.py", 4, _port(22), {}, *args,
                 prog_args=(12, "disagg", "toy"))
    assert clean.returncode == 0, clean.stderr[-2000:]
    assert "serve_v2 OK nreq=12 recoveries=0 mode=disagg" in clean.stdout
    d_clean = _digests(clean.stdout, "serve_v2 digest")
    assert len(d_clean) == 1, clean.stdout

    # rank 1 is the (sole) prefill rank on this mesh: its sends are the
    # KV ships to the decode island — the 15th send dies between a
    # hand-off and the frontend's commit
    fault = _run("serve_v2.py", 4, _port(23), FAULT_EXIT, *args,
                 prog_args=(12, "disagg", "toy"))
    assert fault.returncode == 0, fault.stderr[-2000:]
    assert "serve_v2 OK nreq=12 recoveries=1" in fault.stdout, fault.stdout
    assert _digests(fault.stdout, "serve_v2 digest") == d_clean
    assert "re-prefilling" in fault.stderr  # the KV-drop recovery path


@pytest.mark.parametrize("shm", ["0", "1"])
def test_serving_v2_disagg_bit_consistent_with_colocated(shm):
    """Disaggregated placement is a pure routing choice: the same
    prompts produce byte-identical transcripts whether prefill and
    decode are colocated or split across the 2-island mesh, with the
    shm arena on or off (the KV wire is exact by default)."""
    digests = {}
    for i, mode in enumerate(("colocated", "disagg")):
        res = _run("serve_v2.py", 4, _port(24 + 2 * i + int(shm)),
                   {"MPI4JAX_TPU_DISABLE_SHM": shm},
                   "--fake-hosts", "r0,r1|r2,r3",
                   prog_args=(12, mode, "gpt"))
        assert res.returncode == 0, res.stderr[-2000:]
        assert f"mode={mode}" in res.stdout, res.stdout
        d = _digests(res.stdout, "serve_v2 digest")
        assert len(d) == 1, res.stdout
        digests[mode] = d[0]
    assert digests["colocated"] == digests["disagg"], digests


# ---- obs: recordings carry the world generation --------------------


def test_obs_parts_carry_generation(tmp_path):
    """Recordings dumped after a recovery are stamped with the new
    world generation, and the merged trace surfaces the per-rank
    generations."""
    trace = tmp_path / "trace.json"
    res = _run("elastic_train.py", 3, _port(6),
               {**FAULT_EXIT,
                "MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "ck")},
               "--trace", str(trace), prog_args=(12,))
    assert res.returncode == 0, res.stderr[-2000:]
    parts = sorted(tmp_path.glob("trace.json.rank*.json"))
    assert len(parts) == 2, parts  # the two survivors dumped
    gens = set()
    for p in parts:
        part = json.loads(p.read_text())
        gens.add(int(part.get("generation", -1)))
    assert gens == {1}, gens
    merged = json.loads(trace.read_text())
    assert merged["otherData"].get("generations"), merged["otherData"]
    assert set(merged["otherData"]["generations"].values()) == {1}


# ---- package level: the DP GPT-2 acceptance scenario ---------------


#: documented loss-parity bound (docs/elasticity.md): the recovered
#: run reshards the global batch over fewer ranks, so only float
#: reassociation separates it from the uninterrupted trajectory
LOSS_REL_BOUND = 1e-2


def test_gpt_dp_elastic_loss_parity(tmp_path):
    """np=3 DP GPT-2 training, rank 1 killed mid-job, shrink to np=2,
    resume from the last committed step: the final full-batch loss
    matches an uninterrupted run within the documented bound."""
    clean = _run("gpt_dp_elastic.py", 3, _port(7),
                 {"MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "clean")},
                 timeout=420, prog_args=(8,))
    assert clean.returncode == 0, clean.stderr[-2500:]
    m = re.search(r"final_loss ([0-9.]+)", clean.stdout)
    assert m, clean.stdout
    loss_clean = float(m.group(1))

    fault = _run("gpt_dp_elastic.py", 3, _port(8),
                 {"MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "fault"),
                  "MPI4JAX_TPU_FAULT":
                      "rank=1,point=send,after=60,action=exit",
                  "MPI4JAX_TPU_TIMEOUT_S": "10"},
                 timeout=420, prog_args=(8,))
    assert fault.returncode == 0, fault.stderr[-2500:]
    assert "completed after recovery" in fault.stderr
    m = re.search(r"final_loss ([0-9.]+)", fault.stdout)
    assert m, fault.stdout
    loss_fault = float(m.group(1))
    rel = abs(loss_fault - loss_clean) / max(abs(loss_clean), 1e-9)
    assert rel <= LOSS_REL_BOUND, (loss_clean, loss_fault, rel)


def test_schedules_stay_valid_at_shrunk_sizes():
    """Dense renumbering keeps the verifier's contract: a rank-symmetric
    program's schedule verifies clean at np=3 AND at the shrunk np=2 —
    nothing about a recovered world invalidates static analysis."""
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu import analysis

    def program(x):
        y = m4j.allreduce(x, op=m4j.SUM)
        return m4j.allgather(y)

    for np_ in (3, 2):
        report = analysis.check(program, jnp.arange(4.0), world_size=np_)
        assert report.ok, report.format_table()
