"""Self-healing links, multi-process: exactly-once replay under injected
transient faults, heartbeat detection of idle dead links, CRC-caught
wire corruption, retry-budget exhaustion escalating through the elastic
path, and ``MPI4JAX_TPU_RETRY=0`` pinning the historic wire bit-for-bit.

Everything here is bridge-level (parent-package shim, no jax import,
the ``test_uring_world.py`` pattern), so the whole module runs in any
container.  The uring legs probe the resolved native status first and
SKIP visibly when the kernel lacks io_uring.

The contract under test (docs/sharp-bits.md § Self-healing links): with
``MPI4JAX_TPU_RETRY`` armed, a transient link fault is healed IN PLACE
— reconnect, gap replay, seq dedup — and the run's results are
bit-identical to a fault-free run; what cannot heal (budget exhausted,
unreplayable frame) escalates loudly through poison -> abort -> elastic,
and the launcher post-mortem names the failed link while reporting
transient-recovered ranks distinctly from dead ones.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")
LAUNCHER = os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py")

_port = [28300]  # own range (uring_world counts in 27400+)

# the armed layer plus fast, test-friendly backoff
ARMED = {
    "MPI4JAX_TPU_RETRY": "4",
    "MPI4JAX_TPU_RETRY_BACKOFF_MS": "50",
}
RESET_AT_5 = {"MPI4JAX_TPU_FAULT": "rank=0,point=send,after=5,action=reset"}
TCP = {"MPI4JAX_TPU_DISABLE_SHM": "1"}


def run_launcher(program, np_, timeout=120, env_extra=None, extra_args=()):
    _port[0] += np_ + 5
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("MPI4JAX_TPU_TIMEOUT_S", "30")
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [
            sys.executable, LAUNCHER, "-n", str(np_),
            "--port", str(_port[0]), *extra_args,
            os.path.join(PROGRAMS, program),
        ],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def heal_lines(stdout):
    """``{rank: (digest, reconnects, dup_dropped, crc_errors, replayed)}``
    from heal_ops.py's report lines."""
    out = {}
    for m in re.finditer(
            r"heal_ops (\d+) digest (\S+) reconnects (\d+) "
            r"dup_dropped (\d+) crc_errors (\d+) replayed (\d+)", stdout):
        out[int(m.group(1))] = (m.group(2), int(m.group(3)),
                                int(m.group(4)), int(m.group(5)),
                                int(m.group(6)))
    return out


_uring_status_cache = []


def _require_uring():
    """SKIP visibly when the kernel lacks io_uring (probe in a fresh
    subprocess: the knob is resolved once per process)."""
    if not _uring_status_cache:
        code = (
            "import sys, types, os; sys.path.insert(0, %r)\n"
            "pkg = types.ModuleType('mpi4jax_tpu')\n"
            "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
            "sys.modules['mpi4jax_tpu'] = pkg\n"
            "from mpi4jax_tpu.runtime import bridge\n"
            "print('status=' + str(bridge.uring_status()))\n"
            % (REPO, REPO)
        )
        res = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env={**os.environ, "MPI4JAX_TPU_URING": "auto"},
            cwd=REPO,
        )
        status = "probe-failed"
        for line in res.stdout.splitlines():
            if line.startswith("status="):
                status = line[len("status="):]
        _uring_status_cache.append(status)
    status = _uring_status_cache[0]
    if not status.startswith("on"):
        pytest.skip(f"io_uring leg skipped: native status is {status!r} "
                    "on this kernel (poll path still covered)")


def _baseline(env_extra):
    """Fault-free digests under the same knobs (minus fault/slack)."""
    env = {k: v for k, v in env_extra.items()
           if k not in ("MPI4JAX_TPU_FAULT",
                        "MPI4JAX_TPU_RETRY_REPLAY_SLACK")}
    res = run_launcher("heal_ops.py", 2, env_extra=env)
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert set(lines) == {0, 1}, res.stdout
    assert all(v[1] == 0 for v in lines.values()), (
        f"fault-free run recovered something: {lines}")
    return lines[0][0], lines[1][0]


# ---------------- RETRY=0 pins today's path ----------------


def test_retry_disarmed_is_bit_identical_to_unset():
    # MPI4JAX_TPU_RETRY=0 (and unset) both run the historic wire: same
    # digests, no link layer anywhere in stderr, zero counters
    d_unset = _baseline({**TCP})
    res = run_launcher("heal_ops.py", 2, env_extra={
        **TCP, "MPI4JAX_TPU_RETRY": "0"})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == d_unset
    assert "self-heal" not in res.stderr
    assert all(v[1:] == (0, 0, 0, 0) for v in lines.values())


def test_retry_disarmed_fault_still_fails_loudly():
    # unarmed + injected reset: the historic escalation (no retry layer
    # to absorb it) — the job must die loudly, never hang or corrupt
    res = run_launcher("heal_ops.py", 2, env_extra={
        **TCP, **RESET_AT_5, "MPI4JAX_TPU_TIMEOUT_S": "5"})
    assert res.returncode != 0
    assert "fault injection: reset" in res.stderr
    assert "self-heal" not in res.stderr  # disarmed: nothing retried
    assert "post-mortem" in res.stderr, res.stderr[-800:]


# ---------------- exactly-once heal, digest-identical ----------------


@pytest.mark.parametrize("uring", ["0", "1"])
def test_reset_mid_coalesced_heals_bit_identical(uring):
    # the acceptance scenario: engine on (small sends ride coalesced
    # container frames), transient reset mid-run; the armed layer
    # reconnects, replays the gap, dedups — and the digests match the
    # fault-free run bit-for-bit on both ranks
    if uring == "1":
        _require_uring()
    env = {**TCP, **ARMED, "MPI4JAX_TPU_URING": uring,
           "MPI4JAX_TPU_PROGRESS_THREAD": "1"}
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={**env, **RESET_AT_5})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want, res.stderr[-800:]
    assert "fault injection: reset" in res.stderr
    assert re.search(r"self-heal: link to r\d+ recovered", res.stderr)
    assert all(v[1] >= 1 for v in lines.values())  # both sides reconnect
    # the launcher reports the heal as a transient, NOT a rank death
    assert "healed in-place" in res.stderr, res.stderr[-800:]
    assert "not rank deaths" in res.stderr


def test_reset_mid_zc_send_heals_bit_identical():
    # 128 KB payloads: above the MSG_ZEROCOPY floor (64 KB), below the
    # replay-retention ceiling (256 KB) — a reset mid-ZC-send must
    # replay the whole frame and land bit-identical digests
    _require_uring()
    env = {**TCP, **ARMED, "MPI4JAX_TPU_URING": "1",
           "HEAL_OPS_N": "16384"}
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={**env, **RESET_AT_5})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want, res.stderr[-800:]
    assert re.search(r"self-heal: link to r\d+ recovered", res.stderr)
    # at least one side held the in-flight ZC frame and replayed it
    # (the peer may have had nothing in its gap)
    assert any(v[4] >= 1 for v in lines.values()), lines


def test_replay_slack_duplicates_are_dropped():
    # deliberate replay overlap: the sender re-sends frames the
    # receiver already delivered; the seq dedup must DROP them (the
    # exactly-once half of the contract) and the digests stay identical
    env = {**TCP, **ARMED}
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={
        **env, **RESET_AT_5, "MPI4JAX_TPU_RETRY_REPLAY_SLACK": "2"})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want
    assert any(v[2] >= 2 for v in lines.values()), (
        f"replay slack produced no dropped duplicates: {lines}")


def test_corrupt_frame_detected_by_crc_and_healed():
    # a flipped header byte must NEVER parse: the CRC32C catches it,
    # the receiver forces a reconnect, and the replayed frame lands
    # bit-identical — no silent corruption, ever
    env = {**TCP, **ARMED}
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={
        **env,
        "MPI4JAX_TPU_FAULT": "rank=0,point=send,after=5,action=corrupt"})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want
    assert "header CRC mismatch" in res.stderr, res.stderr[-800:]
    assert any(v[3] >= 1 for v in lines.values())  # crc_errors counted


def test_delay_fault_is_transparent():
    # a transient stall below the deadline needs no recovery at all:
    # digests identical, nothing reconnected
    env = {**TCP, **ARMED}
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={
        **env,
        "MPI4JAX_TPU_FAULT": "rank=0,point=send,after=5,action=delay,"
                             "ms=300"})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want
    assert all(v[1] == 0 for v in lines.values())


def test_heartbeat_heals_idle_link_under_shm():
    # shm arena on: traffic rides the rings, so a reset lands on the
    # IDLE TCP link underneath — only the progress thread's heartbeats
    # can find it.  The idle window between the phases is where the
    # ping fails, the link heals, and phase 2 runs on the new epoch.
    env = {
        **ARMED,
        "MPI4JAX_TPU_DISABLE_SHM": "0",
        "MPI4JAX_TPU_PROGRESS_THREAD": "1",
        "MPI4JAX_TPU_HEARTBEAT_S": "0.2",
        "HEAL_OPS_SLEEP_S": "1.5",
    }
    want = _baseline(env)
    res = run_launcher("heal_ops.py", 2, env_extra={**env, **RESET_AT_5})
    assert res.returncode == 0, res.stderr[-800:]
    lines = heal_lines(res.stdout)
    assert (lines[0][0], lines[1][0]) == want
    assert "heartbeat send failed" in res.stderr, res.stderr[-800:]
    assert all(v[1] >= 1 for v in lines.values())


# ---------------- budget exhaustion escalates ----------------


def test_budget_exhaustion_escalates_to_elastic_shrink():
    # a peer that actually DIED is not a transient: the survivors honor
    # the retry budget, declare the link DEAD, and escalate through the
    # PR 9 path — poison, abort, elastic shrink — finishing with the
    # uninterrupted run's exact digest, while the launcher post-mortem
    # names the failed link (and reports no bogus "healed" ranks)
    import tempfile

    with tempfile.TemporaryDirectory(prefix="m4j_heal_base_") as ckpt:
        base = run_launcher("elastic_train.py", 3, env_extra={
            **TCP, "MPI4JAX_TPU_CKPT_DIR": ckpt})
    assert base.returncode == 0, base.stderr[-800:]
    want = set(re.findall(r"elastic_train digest r\d+ (\w+)", base.stdout))
    assert len(want) == 1

    with tempfile.TemporaryDirectory(prefix="m4j_heal_ckpt_") as ckpt:
        res = run_launcher("elastic_train.py", 3, timeout=180, env_extra={
            **TCP,
            "MPI4JAX_TPU_RETRY": "2",
            "MPI4JAX_TPU_RETRY_BACKOFF_MS": "50",
            "MPI4JAX_TPU_TIMEOUT_S": "8",
            "MPI4JAX_TPU_CKPT_DIR": ckpt,
            "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=10,action=exit",
        }, extra_args=("--elastic",))
    assert res.returncode == 0, res.stderr[-800:]
    assert "completed after recovery" in res.stderr, res.stderr[-800:]
    # the budget was honored, then exhausted, then escalated — loudly
    assert re.search(r"self-heal: link to r1 DEAD after \d+ attempt",
                     res.stderr), res.stderr[-800:]
    assert "escalating (poison -> abort -> elastic)" in res.stderr
    # the post-mortem names the link, and nothing is called "healed"
    assert re.search(r"failed link\(s\): rank \d+ -> rank 1", res.stderr)
    assert "healed in-place" not in res.stderr
    got = set(re.findall(r"elastic_train digest r\d+ (\w+)", res.stdout))
    assert want <= got, f"survivor digests diverged: {want} vs {got}"
