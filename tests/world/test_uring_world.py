"""Zero-copy transport floor, multi-process: digest equivalence of the
uring and poll submission paths, the fault-injection matrix under
``MPI4JAX_TPU_URING=1``, and elastic shrink-under-load on the uring leg.

Everything here is bridge-level (parent-package shim, no jax import),
so the whole module runs in any container.  The uring legs probe the
resolved native status first and SKIP with a visible notice when the
kernel lacks io_uring — never silently green on the poll path.
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

URING_ON = {"MPI4JAX_TPU_URING": "1"}
URING_OFF = {"MPI4JAX_TPU_URING": "0"}


def _port(slot):
    return 27400 + (os.getpid() * 7 + slot * 17) % 500


_uring_status_cache = []


def _uring_status():
    """The RESOLVED native uring state in a fresh subprocess (the knob
    is read once per process, so the probe must not run in-process)."""
    if _uring_status_cache:
        return _uring_status_cache[0]
    code = (
        "import sys, types, os; sys.path.insert(0, %r)\n"
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "from mpi4jax_tpu.runtime import bridge\n"
        "print('status=' + str(bridge.uring_status()))\n" % (REPO, REPO)
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
    return status


def _require_uring():
    status = _uring_status()
    if not status.startswith("on"):
        pytest.skip(f"io_uring leg skipped: native status is {status!r} "
                    "on this kernel (poll path still covered)")


# ---- digest equality: mixed send/recv/allreduce program, on vs off --

_MIXED_PROG = r"""
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
    # small-send burst (coalesced containers / staged uring frames)
    for peer in range(n):
        if peer == r:
            continue
        for i in range(16):
            m = 5 + (i %% 3) * 200
            bridge.send(h, np.arange(m, dtype=np.int32) + 7000 * r + i,
                        peer, 900 * round_ + i)
    for peer in range(n):
        if peer == r:
            continue
        for i in range(16):
            m = 5 + (i %% 3) * 200
            got = bridge.recv(h, (m,), np.int32, peer, 900 * round_ + i)
            assert got[0] == 7000 * peer + i, (peer, i, got[0])
            digest.update(got.tobytes())
    # mid-size detached sends (> coalesce threshold: writev batch path)
    mid = np.arange(3000, dtype=np.float64) * (r + 1) + round_
    for peer in range(n):
        if peer != r:
            bridge.send(h, mid, peer, 7000 + round_)
    for peer in range(n):
        if peer != r:
            got = bridge.recv(h, (3000,), np.float64, peer, 7000 + round_)
            digest.update(got.tobytes())
    # sendrecv ring + small and larger allreduce (chunked transfers on
    # the uring leg; the zero-copy gate lives past the kernel's
    # buffering ceiling and is pinned by the cyclic-sends test below)
    got = bridge.sendrecv(h, np.arange(64.0) + r, (64,), np.float64,
                          (r - 1) %% n, (r + 1) %% n, 31 + round_)
    digest.update(got.tobytes())
    out = bridge.allreduce(h, np.ones(8) * (r + 1), 0)
    digest.update(out.tobytes())
    big = bridge.allreduce(h, np.arange(70000, dtype=np.float32) + r, 0)
    digest.update(big.tobytes())
bridge.barrier(h)
print("uring_mixed digest r%%d %%s" %% (r, digest.hexdigest()), flush=True)
print("uring_mixed OK", flush=True)
"""


def _run_mixed(tmp_path, port, env_extra):
    prog = tmp_path / "uring_mixed.py"
    prog.write_text(_MIXED_PROG % REPO)
    env = dict(os.environ)
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"  # the floor under test is TCP
    env["MPI4JAX_TPU_TIMEOUT_S"] = "60"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, LAUNCHER, "-n", "3", "--port", str(port),
         str(prog)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )


def _digests(stdout, marker):
    return sorted(re.findall(marker + r" (r\d+ [0-9a-f]{64})", stdout))


def test_uring_on_off_digest_equality(tmp_path):
    """THE escape-hatch contract: a mixed send/recv/sendrecv/allreduce
    program produces bit-identical per-rank digests with the uring
    submission backend on and off (URING=0 is the poll path)."""
    _require_uring()
    res_off = _run_mixed(tmp_path, _port(0), URING_OFF)
    assert res_off.returncode == 0, res_off.stderr[-2000:] + res_off.stdout
    assert res_off.stdout.count("uring_mixed OK") == 3
    res_on = _run_mixed(tmp_path, _port(1), URING_ON)
    assert res_on.returncode == 0, res_on.stderr[-2000:] + res_on.stdout
    assert res_on.stdout.count("uring_mixed OK") == 3
    d_off = _digests(res_off.stdout, "uring_mixed digest")
    d_on = _digests(res_on.stdout, "uring_mixed digest")
    assert d_off == d_on and len(d_off) == 3, (d_off, d_on)


_CYCLIC_LARGE_PROG = r"""
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
nxt, prv = (r + 1) %% n, (r - 1 + n) %% n
digest = hashlib.sha256()
for k in range(4):
    # every rank sends BEFORE anyone receives: completion relies on the
    # kernel buffering the payload, exactly like the poll path's write
    out = np.arange(128 * 1024, dtype=np.float32) + 1000 * r + k
    bridge.send(h, out, nxt, k)
    got = bridge.recv(h, (128 * 1024,), np.float32, prv, k)
    assert got[0] == 1000 * prv + k, (r, k, got[0])
    digest.update(got.tobytes())
bridge.barrier(h)
print("uring_cyclic digest r%%d %%s" %% (r, digest.hexdigest()), flush=True)
print("uring_cyclic OK", flush=True)
"""


def test_large_cyclic_sends_keep_buffered_completion(tmp_path):
    """The MSG_ZEROCOPY completion-envelope contract: a 3-rank ring of
    512 KiB sends where every rank sends before anyone receives — the
    poll path completes each send once the kernel buffers the payload,
    and the uring path must do the same (a zero-copy send's buffer
    release waits on the RECEIVER, so ZC engaging below the kernel's
    buffering ceiling would turn this into a rendezvous deadlock).
    Runs with the progress engine off (inline blocking sends, the worst
    case) and no deadline armed, so a regression hangs rather than
    degrades."""
    _require_uring()

    def run(port, env_extra):
        prog = tmp_path / "uring_cyclic.py"
        prog.write_text(_CYCLIC_LARGE_PROG % REPO)
        env = dict(os.environ)
        env["MPI4JAX_TPU_DISABLE_SHM"] = "1"
        env["MPI4JAX_TPU_PROGRESS_THREAD"] = "0"
        env.pop("MPI4JAX_TPU_TIMEOUT_S", None)  # unarmed: hang = bug
        env.update(env_extra)
        return subprocess.run(
            [sys.executable, LAUNCHER, "-n", "3", "--port", str(port),
             str(prog)],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )

    res_on = run(_port(8), URING_ON)
    assert res_on.returncode == 0, res_on.stderr[-2000:] + res_on.stdout
    assert res_on.stdout.count("uring_cyclic OK") == 3
    res_off = run(_port(9), URING_OFF)
    assert res_off.returncode == 0, res_off.stderr[-2000:] + res_off.stdout
    d_on = _digests(res_on.stdout, "uring_cyclic digest")
    d_off = _digests(res_off.stdout, "uring_cyclic digest")
    assert d_on == d_off and len(d_on) == 3, (d_on, d_off)


def test_coalesced_wire_survives_batched_writes(tmp_path):
    """Pin the coalesced-frame wire format across the drain-loop write
    batching: the poll path (URING=0, where the container now leaves in
    ONE write) still delivers every burst message with its tag and
    bytes intact — the receive-side splitter parses the same wire
    bytes it always did."""
    res = _run_mixed(tmp_path, _port(2), {**URING_OFF,
                                          "MPI4JAX_TPU_COALESCE_BYTES":
                                          "4096"})
    assert res.returncode == 0, res.stderr[-2000:] + res.stdout
    assert res.stdout.count("uring_mixed OK") == 3


# ---- failure semantics on the uring path ----------------------------

# bridge-level sendrecv ring (parent-package shim, no jax), the shape
# the PR 2 fault matrix injects into
_FAULT_PROG = r"""
import os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu.runtime import bridge, transport

c = transport.get_world_comm()
h, r, n = c.handle, c.rank(), c.size()
base = np.arange(8, dtype=np.float64)
for i in range(6):
    got = bridge.sendrecv(h, base + r + i, (8,), np.float64,
                          (r - 1) %% n, (r + 1) %% n, 40 + i)
    np.testing.assert_allclose(got, base + (r - 1) %% n + i)
print("fault_prog OK", flush=True)
"""


def _run_fault(tmp_path, np_, port, env_extra, timeout=120, args=(),
               program=None):
    prog = program
    if prog is None:
        prog = tmp_path / "uring_fault.py"
        prog.write_text(_FAULT_PROG % REPO)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, LAUNCHER, "-n", str(np_), "--port", str(port),
         *args, str(prog)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("point", ["send", "recv"])
@pytest.mark.parametrize("action", ["hang", "exit", "close"])
def test_fault_matrix_under_uring(tmp_path, point, action):
    """The PR 2 fault-injection matrix on the uring submission path:
    every (action, point) still tears the job down detectably, with
    deadlines measured from post time (the hang cases name the timeout)
    and poison/EOF propagation intact (the exit/close cases)."""
    _require_uring()
    slot = {"hang": 0, "exit": 1, "close": 2}[action] * 2 + \
        {"send": 0, "recv": 1}[point] + 3
    env = {
        **URING_ON,
        "MPI4JAX_TPU_TIMEOUT_S": "3",
        "MPI4JAX_TPU_FAULT":
            f"rank=1,point={point},after=2,action={action}",
    }
    res = _run_fault(tmp_path, 2, _port(slot), env)
    assert res.returncode != 0
    assert res.stdout.count("fault_prog OK") < 2
    assert "post-mortem" in res.stderr, res.stderr[-900:]
    if action == "hang":
        # the progress deadline (anchored at post time on the engine
        # queue) fires and names the configured knob's value
        assert "timed out after 3 s" in res.stderr, res.stderr[-900:]
    else:
        # crash / partition: detected through the dead socket or the
        # injected exit itself, with the injection named
        assert ("fault injection" in res.stderr
                or "returned error code" in res.stderr), res.stderr[-900:]


def test_poison_tears_down_in_one_deadline_under_uring(tmp_path):
    """A hang inside a coalesced burst with the uring leg armed: the
    receivers starve, the post-time deadline fires, and the poison
    frame tears the group down within ~2x the deadline — not the sum of
    per-rank timeouts."""
    _require_uring()
    import time

    prog = tmp_path / "uring_mixed.py"
    prog.write_text(_MIXED_PROG % REPO)
    env = dict(os.environ)
    env.update({
        **URING_ON,
        "MPI4JAX_TPU_DISABLE_SHM": "1",
        "MPI4JAX_TPU_TIMEOUT_S": "4",
        "MPI4JAX_TPU_FAULT": "rank=0,point=send,after=20,action=hang",
    })
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "3", "--port", str(_port(9)),
         str(prog)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    dt = time.monotonic() - t0
    assert res.returncode != 0
    assert "timed out" in res.stderr, res.stderr[-1200:]
    assert dt < 45, f"teardown took {dt:.1f}s for a 4s deadline"


def test_elastic_shrink_under_load_uring(tmp_path):
    """The PR 9 shrink-under-load scenario with the uring backend
    armed: rank 1 dies mid-stream, survivors recover through
    tpucomm_shrink, and training finishes from the committed checkpoint
    (recovery post-mortem names the outcome)."""
    _require_uring()
    env = {
        **URING_ON,
        "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=14,action=exit",
        "MPI4JAX_TPU_TIMEOUT_S": "8",
        "MPI4JAX_TPU_CKPT_DIR": str(tmp_path / "ckpt"),
    }
    res = _run_fault(tmp_path, 3, _port(11), env, timeout=240,
                     args=("--elastic",),
                     program=os.path.join(PROGRAMS, "elastic_train.py"))
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("elastic_train OK") == 2
    assert "completed after recovery" in res.stderr
    assert "generation 1" in res.stderr
