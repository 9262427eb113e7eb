"""Failure detection & teardown: transport deadlines, abort propagation,
the launcher watchdog, and connect deadlines — every path driven by the
deterministic fault injector (``MPI4JAX_TPU_FAULT``).

The contract under test (docs/sharp-bits.md § Hangs, timeouts, and
teardown): with ``MPI4JAX_TPU_TIMEOUT_S`` set, one wedged rank makes
every peer exit nonzero — naming the stuck rank — and the launcher reap
the whole group within roughly 2x the configured deadline; with the
knob unset, peer *death* is still detected immediately via the dead
socket (the historic behavior).
"""

import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")

_port = [25500]  # own range: test_world_tier.py counts up from 24100


def run_launcher(program, np_, timeout=180, env_extra=None, extra_args=()):
    _port[0] += np_ + 3
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [
            sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
            "-n", str(np_), "--port", str(_port[0]), *extra_args,
            os.path.join(PROGRAMS, program),
        ],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )

# keep p2p on the framed TCP path: the shm rings have their own bounded
# waits (also capped by the knob), but the wording asserted below is the
# TCP transport's
TCP = {"MPI4JAX_TPU_DISABLE_SHM": "1"}


def test_hung_rank_trips_deadline_and_reaps_group():
    # the acceptance scenario: rank 1 hangs at its 3rd recv; rank 0's
    # next recv from it must trip the 3 s progress deadline, name the
    # stuck peer, and the launcher must reap the hung rank — all well
    # inside 2x the deadline plus process startup
    t0 = time.monotonic()
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        **TCP,
        "MPI4JAX_TPU_TIMEOUT_S": "3",
        "MPI4JAX_TPU_FAULT": "rank=1,point=recv,after=2,action=hang",
    })
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "fault_ops OK" not in res.stdout
    assert "timed out after 3 s" in res.stderr, res.stderr[-800:]
    assert "recv header from 1" in res.stderr, res.stderr[-800:]
    assert "post-mortem" in res.stderr, res.stderr[-800:]
    assert dt < 40, f"teardown took {dt:.1f}s for a 3s deadline"


def test_hung_rank_shm_path_also_bounded():
    # same wedge under the default same-host arena: the job deadline
    # caps the shm ring/barrier waits too, so the group still tears
    # down promptly (the knob bounds the job, not just one transport)
    t0 = time.monotonic()
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        "MPI4JAX_TPU_DISABLE_SHM": "",
        "MPI4JAX_TPU_TIMEOUT_S": "3",
        "MPI4JAX_TPU_FAULT": "rank=1,point=recv,after=2,action=hang",
    })
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "fault_ops OK" not in res.stdout
    assert "timed out" in res.stderr, res.stderr[-800:]
    assert dt < 40, f"teardown took {dt:.1f}s for a 3s deadline"


def test_killed_rank_detected_without_deadline():
    # knob unset: a crashed rank (simulated by action=exit, code 17) is
    # still detected immediately through the dead socket — the historic
    # fail-fast path, now with the launcher's post-mortem naming the
    # first failure
    t0 = time.monotonic()
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        **TCP,
        "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=2,action=exit",
    })
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "fault_ops OK" not in res.stdout
    # the launcher may notice either casualty first: the crashed rank
    # (code 17) or the peer that aborted on the dead socket — both get
    # named, and the injected crash is visible either way
    assert "post-mortem: rank" in res.stderr, res.stderr[-800:]
    assert "fault injection" in res.stderr, res.stderr[-800:]
    assert dt < 40, f"EOF detection took {dt:.1f}s"


def test_partitioned_rank_fails_both_sides():
    # action=close shuts every socket of rank 1 down mid-schedule (a
    # yanked cable): both sides of the partition must abort
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        **TCP,
        "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=2,action=close",
    })
    assert res.returncode != 0
    assert "fault_ops OK" not in res.stdout
    assert "returned error code" in res.stderr, res.stderr[-800:]


def test_abort_poisons_waiting_third_rank():
    # abort propagation: rank 1 hangs; rank 2 (2 s deadline) times out
    # first and aborts; rank 0 — blocked on rank 2 with a 60 s deadline
    # — must fail via rank 2's poison frame (naming it, carrying the
    # root-cause text) within seconds, NOT its own 60 s deadline.
    # Ranks are spawned directly (no launcher) so no reaper can race
    # the poison delivery; per-rank env carries different deadlines.
    port = 26300 + os.getpid() % 500
    base = dict(os.environ)
    base.pop("XLA_FLAGS", None)
    base.update({
        **TCP,
        "MPI4JAX_TPU_SIZE": "3",
        "MPI4JAX_TPU_COORD": f"127.0.0.1:{port}",
        "MPI4JAX_TPU_FAULT": "rank=1,point=recv,after=1,action=hang",
        "FAULT_OPS_ROUNDS": "8",
        "JAX_PLATFORMS": "cpu",
    })
    deadlines = {0: "60", 1: "60", 2: "2"}
    procs = {}
    for r in range(3):
        env = dict(base)
        env["MPI4JAX_TPU_RANK"] = str(r)
        env["MPI4JAX_TPU_TIMEOUT_S"] = deadlines[r]
        procs[r] = subprocess.Popen(
            [sys.executable, os.path.join(PROGRAMS, "fault_ops.py")],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
    try:
        t0 = time.monotonic()
        out0, err0 = procs[0].communicate(timeout=45)
        dt0 = time.monotonic() - t0
        out2, err2 = procs[2].communicate(timeout=45)
    finally:
        procs[1].kill()  # rank 1 is deliberately hung
        procs[1].communicate()
    assert procs[2].returncode != 0
    assert "timed out" in err2 and "from 1" in err2, err2[-600:]
    assert procs[0].returncode != 0
    assert "rank 2 aborted the job" in err0, err0[-600:]
    assert "timed out" in err0  # the poison carried rank 2's root cause
    assert dt0 < 30, f"poison took {dt0:.1f}s to beat a 60s deadline"


def test_launcher_watchdog_reaps_wedged_job():
    t0 = time.monotonic()
    res = run_launcher("hang_forever.py", 2, timeout=90,
                       extra_args=("--timeout", "3"))
    dt = time.monotonic() - t0
    assert res.returncode == 124, res.returncode
    assert "watchdog" in res.stderr, res.stderr[-600:]
    assert "post-mortem" in res.stderr
    assert dt < 40, f"watchdog reap took {dt:.1f}s for a 3s budget"


def test_launcher_watchdog_quiet_on_healthy_job():
    res = run_launcher("fault_ops.py", 2, timeout=90,
                       extra_args=("--timeout", "80"))
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("fault_ops OK") == 2
    assert "watchdog" not in res.stderr


def test_launcher_sigterm_forwards_and_reaps(tmp_path):
    # scheduler preemption: SIGTERM to the launcher must take the whole
    # rank group down (exit 143) with zero orphans
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HANG_PID_DIR"] = str(tmp_path)
    env["MPI4JAX_TPU_LAUNCH_GRACE_S"] = "2"
    p = subprocess.Popen(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "2",
         "--port", str(26200 + os.getpid() % 500),
         os.path.join(PROGRAMS, "hang_forever.py")],
        env=env, cwd=REPO,
    )
    try:
        deadline = time.monotonic() + 30
        while len(list(tmp_path.glob("pid_*"))) < 2:
            assert time.monotonic() < deadline, "ranks never spawned"
            assert p.poll() is None, "launcher died before spawning"
            time.sleep(0.1)
        pids = [int(f.read_text()) for f in tmp_path.glob("pid_*")]
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert p.returncode == 143, p.returncode
    time.sleep(0.5)
    orphans = []
    for pid in pids:
        try:
            os.kill(pid, 0)
            orphans.append(pid)
        except ProcessLookupError:
            pass
    assert not orphans, f"orphan ranks survived SIGTERM: {orphans}"


def test_launcher_sigint_escalates_past_ignoring_ranks(tmp_path):
    # Ctrl-C: ranks that ignore SIGINT must still be reaped after the
    # grace period (SIGINT -> grace -> SIGTERM -> SIGKILL), exit 130
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HANG_PID_DIR"] = str(tmp_path)
    env["HANG_IGNORE_SIGINT"] = "1"
    env["MPI4JAX_TPU_LAUNCH_GRACE_S"] = "1"
    p = subprocess.Popen(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "2",
         "--port", str(26250 + os.getpid() % 500),
         os.path.join(PROGRAMS, "hang_forever.py")],
        env=env, cwd=REPO,
    )
    try:
        deadline = time.monotonic() + 30
        while len(list(tmp_path.glob("pid_*"))) < 2:
            assert time.monotonic() < deadline, "ranks never spawned"
            assert p.poll() is None, "launcher died before spawning"
            time.sleep(0.1)
        pids = [int(f.read_text()) for f in tmp_path.glob("pid_*")]
        p.send_signal(signal.SIGINT)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert p.returncode == 130, p.returncode
    time.sleep(0.5)
    orphans = []
    for pid in pids:
        try:
            os.kill(pid, 0)
            orphans.append(pid)
        except ProcessLookupError:
            pass
    assert not orphans, f"orphan ranks survived Ctrl-C: {orphans}"


def test_connect_deadline_reports_last_errno():
    # a rank whose lower peer never exists: the bootstrap dial must give
    # up within the configured deadline reporting the last errno, not
    # spin silently
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MPI4JAX_TPU_RANK": "1",
        "MPI4JAX_TPU_SIZE": "2",
        "MPI4JAX_TPU_COORD": f"127.0.0.1:{26350 + os.getpid() % 500}",
        "MPI4JAX_TPU_CONNECT_TIMEOUT_S": "2",
        "JAX_PLATFORMS": "cpu",
    })
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, os.path.join(PROGRAMS, "fault_ops.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "cannot reach rank 0" in res.stderr, res.stderr[-600:]
    assert "within 2 s" in res.stderr, res.stderr[-600:]
    assert dt < 30, f"connect gave up after {dt:.1f}s for a 2s deadline"


def test_connect_hang_bounds_accept_side():
    # rank 1 wedged before dialing: with the connect knob set, rank 0's
    # accept side times out too instead of waiting forever
    t0 = time.monotonic()
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        **TCP,
        "MPI4JAX_TPU_CONNECT_TIMEOUT_S": "2",
        "MPI4JAX_TPU_FAULT": "rank=1,point=connect,after=0,action=hang",
    })
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "no higher rank dialed within 2 s" in res.stderr, (
        res.stderr[-600:])
    assert dt < 40, f"accept gave up after {dt:.1f}s for a 2s deadline"


def test_malformed_fault_spec_fails_loudly():
    # a typo'd injection spec must stop the job, not silently inject
    # nothing and fake a green failure test
    res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
        **TCP, "MPI4JAX_TPU_FAULT": "rank=1,point=typo,action=hang",
    })
    assert res.returncode != 0
    assert "malformed MPI4JAX_TPU_FAULT" in res.stderr, res.stderr[-600:]


def test_deadline_armed_job_still_passes():
    # the knob on a healthy job changes nothing: full rounds complete
    # under both transports with the deadline armed
    for extra in (TCP, {"MPI4JAX_TPU_DISABLE_SHM": ""}):
        res = run_launcher("fault_ops.py", 2, timeout=90, env_extra={
            **extra, "MPI4JAX_TPU_TIMEOUT_S": "30",
        })
        assert res.returncode == 0, res.stderr + res.stdout
        assert res.stdout.count("fault_ops OK") == 2
