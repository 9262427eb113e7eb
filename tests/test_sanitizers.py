"""Native transport under ThreadSanitizer / AddressSanitizer+UBSan.

Slow tier: builds ``native/tpucomm.cc`` with ``make tsan`` / ``make asan``
(transport-only — no jaxlib headers, no XLA in the loop) and runs a
2-rank loopback pair under each build, failing on ANY sanitizer report.

The rank processes drive the sanitized library through raw ctypes (no
jax import: the sanitizer runtimes would otherwise drown the report in
uninstrumented-interpreter noise), exercising the hot concurrency paths:
bootstrap accept/dial, framed send/recv both directions, allreduce (the
algorithm engine's threaded fan-in), and barrier — in a loop, with the
shm arena on (its lock-free rings are exactly what tsan is for) and off.
"""

import os
import shutil
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
SO_DIR = os.path.join(REPO, "mpi4jax_tpu", "runtime", "_native")

_RANK_SRC = r"""
import ctypes, os, sys
import numpy as np

so = os.environ["SAN_SO"]
rank = int(os.environ["SAN_RANK"])
size = 2
port = int(os.environ["SAN_PORT"])

lib = ctypes.CDLL(so)
lib.tpucomm_init.restype = ctypes.c_int64
lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                             ctypes.c_char_p]
h = lib.tpucomm_init(rank, size, port, b"")
assert h > 0, "tpucomm_init failed"

F32, SUM = 11, 0  # wire codes (tpucomm.h)
n = 1024
buf = np.arange(n, dtype=np.float32) + rank
out = np.zeros_like(buf)
for it in range(20):
    # p2p both directions (framed path + shm rings when arena is on)
    if rank == 0:
        lib.tpucomm_send(h, buf.ctypes.data_as(ctypes.c_void_p),
                         buf.nbytes, 1, it)
        rc = lib.tpucomm_recv(h, out.ctypes.data_as(ctypes.c_void_p),
                              out.nbytes, 1, it)
    else:
        rc = lib.tpucomm_recv(h, out.ctypes.data_as(ctypes.c_void_p),
                              out.nbytes, 0, it)
        lib.tpucomm_send(h, buf.ctypes.data_as(ctypes.c_void_p),
                         buf.nbytes, 0, it)
    assert rc == 0, f"recv failed at iter {it}"
    assert out[3] == 3.0 + (1 - rank), out[3]
    # collective fan-in + barrier
    rc = lib.tpucomm_allreduce(
        h, buf.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p), n, F32, SUM)
    assert rc == 0, f"allreduce failed at iter {it}"
    assert out[1] == 3.0, out[1]  # (1+0) + (1+1)
    assert lib.tpucomm_barrier(h) == 0
lib.tpucomm_finalize(h)
print("san-rank-ok", rank, flush=True)
"""

_REPORT_MARKERS = (
    "WARNING: ThreadSanitizer",
    "ERROR: AddressSanitizer",
    "ERROR: LeakSanitizer",
    "runtime error:",          # UBSan
    "SUMMARY: ThreadSanitizer",
    "SUMMARY: AddressSanitizer",
    "SUMMARY: UndefinedBehaviorSanitizer",
)


def _preload_path(libname):
    gcc = shutil.which("g++") or shutil.which("gcc")
    if gcc is None:
        pytest.skip("no C++ toolchain")
    path = subprocess.run(
        [gcc, f"-print-file-name={libname}"],
        capture_output=True, text=True,
    ).stdout.strip()
    if not path or not os.path.isabs(path) or not os.path.exists(path):
        pytest.skip(f"{libname} not installed")
    return path


def _run_pair(so_path, preload, san_env, port, extra_env):
    env = {
        **os.environ,
        "SAN_SO": so_path,
        "SAN_PORT": str(port),
        "LD_PRELOAD": preload,
        **san_env,
        **extra_env,
    }
    procs = []
    for rank in range(2):
        env_r = {**env, "SAN_RANK": str(rank)}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _RANK_SRC],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env_r,
        ))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            pytest.fail(f"sanitized rank hung: {out[-500:]} {err[-500:]}")
        outs.append((p.returncode, out, err))
    for rank, (rc, out, err) in enumerate(outs):
        blob = out + err
        for marker in _REPORT_MARKERS:
            assert marker not in blob, (
                f"sanitizer report from rank {rank}:\n{blob[-4000:]}"
            )
        assert rc == 0, (
            f"rank {rank} exited {rc} (sanitizer exitcode=66 means a "
            f"report fired):\n{(out + err)[-2000:]}"
        )
        assert f"san-rank-ok {rank}" in out, out


def _build(target):
    res = subprocess.run(
        ["make", "-C", NATIVE, target], capture_output=True, text=True,
    )
    assert res.returncode == 0, f"make {target} failed:\n{res.stderr[-2000:]}"


def _uring_status_of(so, preload, san_env):
    """The sanitized build's RESOLVED uring state, probed in a fresh
    subprocess (the knob resolves once per process)."""
    code = (
        "import ctypes\n"
        "lib = ctypes.CDLL(%r)\n"
        "lib.tpucomm_uring_status.restype = ctypes.c_char_p\n"
        "print('status=' + lib.tpucomm_uring_status().decode())\n" % so
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        env={**os.environ, "LD_PRELOAD": preload, **san_env,
             "MPI4JAX_TPU_URING": "1"},
    )
    for line in res.stdout.splitlines():
        if line.startswith("status="):
            return line[len("status="):]
    return "probe-failed: " + (res.stderr or res.stdout)[-200:]


def _uring_env(uring, so, preload, san_env):
    """Env for a sanitized uring leg — skips VISIBLY (never silently
    green on the poll path) when the kernel lacks io_uring."""
    if uring == "1":
        status = _uring_status_of(so, preload, san_env)
        if not status.startswith("on"):
            pytest.skip(f"io_uring leg skipped: sanitized build reports "
                        f"{status!r} on this kernel (URING=0 leg still "
                        "covered)")
    return {"MPI4JAX_TPU_URING": uring}


@pytest.mark.parametrize("uring", ["0", "1"])
@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_loopback_pair(shm, uring):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    san = {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"}
    extra = {"MPI4JAX_TPU_JOBID": f"tsan{shm}{uring}{os.getpid()}",
             **_uring_env(uring, so, preload, san)}
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    _run_pair(
        so, preload, san,
        26200 + (os.getpid() + (7 if shm == "on" else 0)
                 + (29 if uring == "1" else 0)) % 900,
        extra,
    )


# ---- async progress engine under TSan ------------------------------
#
# The progress thread is the first truly concurrent writer the
# transport has had (descriptors cross the lock-free submission queue,
# completions cross a futex, coalesced frames are assembled off the
# posting thread), so it gets its own sanitized battery: a slow
# loopback pingpong with send BURSTS (forcing the coalescing path) plus
# a 3-rank allreduce/barrier loop, queue armed, failing on any report.

_ENGINE_RANK_SRC = r"""
import ctypes, os, sys
import numpy as np

so = os.environ["SAN_SO"]
rank = int(os.environ["SAN_RANK"])
size = int(os.environ["SAN_SIZE"])
port = int(os.environ["SAN_PORT"])

lib = ctypes.CDLL(so)
lib.tpucomm_init.restype = ctypes.c_int64
lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                             ctypes.c_char_p]
h = lib.tpucomm_init(rank, size, port, b"")
assert h > 0, "tpucomm_init failed"

F32, SUM = 11, 0  # wire codes (tpucomm.h)
n = 256
buf = np.arange(n, dtype=np.float32) + rank
out = np.zeros_like(buf)
p = lambda a: a.ctypes.data_as(ctypes.c_void_p)
dest = (rank + 1) % size
src = (rank - 1 + size) % size
for it in range(12):
    # burst of detached small sends: the engine queues them and the
    # progress thread coalesces adjacent ones into container frames
    for i in range(8):
        rc = lib.tpucomm_send(h, p(buf), buf.nbytes, dest, it * 8 + i)
        assert rc == 0, f"send failed at iter {it}.{i}"
    for i in range(8):
        rc = lib.tpucomm_recv(h, p(out), out.nbytes, src, it * 8 + i)
        assert rc == 0, f"recv failed at iter {it}.{i}"
    assert out[3] == 3.0 + src, out[3]
    rc = lib.tpucomm_allreduce(
        h, p(buf), p(out), n, F32, SUM)
    assert rc == 0, f"allreduce failed at iter {it}"
    assert out[0] == sum(range(size)), out[0]
    # quantized wire formats under the engine: forced qring (chunked
    # codec frames + the TLS scratch the progress thread owns) and qrd
    # (whole-buffer packed exchanges).  On an arena comm (shm on) they
    # are exact no-ops; on TCP the result is approximate.
    QRING, QRD = 5, 6
    nq = 3000  # several codec blocks, uneven chunks at size 3
    qbuf = (np.arange(nq, dtype=np.float32) % 17 - 8) * (rank + 1)
    qout = np.zeros_like(qbuf)
    expect = (np.arange(nq, dtype=np.float64) % 17 - 8) * sum(
        r + 1 for r in range(size))
    for algo in (QRING, QRD):
        rc = lib.tpucomm_allreduce_algo(
            h, p(qbuf), p(qout), nq, F32, SUM, algo)
        assert rc == 0, f"quantized allreduce failed at iter {it}"
        denom = max(abs(expect).max(), 1e-6)
        assert abs(qout - expect).max() / denom < 3e-2, algo
    assert lib.tpucomm_barrier(h) == 0
lib.tpucomm_finalize(ctypes.c_int64(h))
print("san-rank-ok", rank, flush=True)
"""


def _run_group(src, n_ranks, so_path, preload, san_env, port, extra_env):
    env = {
        **os.environ,
        "SAN_SO": so_path,
        "SAN_PORT": str(port),
        "SAN_SIZE": str(n_ranks),
        "LD_PRELOAD": preload,
        **san_env,
        **extra_env,
    }
    procs = []
    for rank in range(n_ranks):
        env_r = {**env, "SAN_RANK": str(rank)}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", src],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env_r,
        ))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            pytest.fail(f"sanitized rank hung: {out[-500:]} {err[-500:]}")
        outs.append((p.returncode, out, err))
    for rank, (rc, out, err) in enumerate(outs):
        blob = out + err
        for marker in _REPORT_MARKERS:
            assert marker not in blob, (
                f"sanitizer report from rank {rank}:\n{blob[-4000:]}"
            )
        assert rc == 0, (
            f"rank {rank} exited {rc} (sanitizer exitcode=66 means a "
            f"report fired):\n{(out + err)[-2000:]}"
        )
        assert f"san-rank-ok {rank}" in out, out


@pytest.mark.parametrize("uring", ["0", "1"])
@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_progress_engine_three_ranks(shm, uring):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    san = {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"}
    extra = {
        "MPI4JAX_TPU_JOBID": f"tsaneng{shm}{uring}{os.getpid()}",
        "MPI4JAX_TPU_PROGRESS_THREAD": "1",
        "MPI4JAX_TPU_COALESCE_BYTES": "4096",
        **_uring_env(uring, so, preload, san),
    }
    if shm == "off":
        # TCP path: this is where detached sends coalesce on the wire
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    _run_group(
        _ENGINE_RANK_SRC, 3, so, preload, san,
        28200 + (os.getpid() + (13 if shm == "on" else 0)
                 + (31 if uring == "1" else 0)) % 900,
        extra,
    )


# ---- hierarchical collectives under TSan ---------------------------
#
# The topology subsystem adds a third concurrency shape: one engine op
# fans out across THREE communicators (the world op runs intra-island
# shm reduces, leader-tier TCP rounds, and intra bcasts on sub-comms
# borrowing the world's sockets, with per-leg observability events
# appended from whichever thread executes).  A three-rank two-island
# (r0,r1 | r2) loop drives forced hring/htree allreduces plus
# hierarchically routed bcasts, queue armed, shm on and off — 0
# reports required.

_HIER_RANK_SRC = r"""
import ctypes, os, sys
import numpy as np

so = os.environ["SAN_SO"]
rank = int(os.environ["SAN_RANK"])
size = int(os.environ["SAN_SIZE"])
port = int(os.environ["SAN_PORT"])

lib = ctypes.CDLL(so)
lib.tpucomm_init.restype = ctypes.c_int64
lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                             ctypes.c_char_p]
lib.tpucomm_split.restype = ctypes.c_int64
lib.tpucomm_split.argtypes = [ctypes.c_int64, ctypes.c_int, ctypes.c_int]
lib.tpucomm_set_topology.restype = ctypes.c_int
lib.tpucomm_set_topology.argtypes = [
    ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
    ctypes.c_int64, ctypes.c_int64]
h = lib.tpucomm_init(rank, size, port, b"")
assert h > 0, "tpucomm_init failed"

# islands r0,r1 | r2 (MPI4JAX_TPU_FAKE_HOSTS in the env governs the
# arena gating; this mirrors it for the native map)
islands = [0, 0, 1]
intra_h = lib.tpucomm_split(h, islands[rank], rank)
lead_h = lib.tpucomm_split(h, 0 if rank in (0, 2) else -1, rank)
arr = (ctypes.c_int32 * size)(*islands)
rc = lib.tpucomm_set_topology(
    h, arr, size, intra_h if rank < 2 else 0, lead_h if rank != 1 else 0)
assert rc == 0, f"set_topology failed rc={rc}"

F32, SUM = 11, 0
HRING, HTREE = 7, 8
n = 3000
p = lambda a: a.ctypes.data_as(ctypes.c_void_p)
buf = (np.arange(n, dtype=np.float32) % 13) * (rank + 1)
expect = (np.arange(n, dtype=np.float32) % 13) * sum(
    r + 1 for r in range(size))
out = np.zeros_like(buf)
big = np.zeros(70000, np.float32)
for it in range(12):
    for algo in (HRING, HTREE):
        rc = lib.tpucomm_allreduce_algo(h, p(buf), p(out), n, F32, SUM,
                                        algo)
        assert rc == 0, f"hier allreduce failed at iter {it}"
        assert np.array_equal(out, expect), f"iter {it} algo {algo}"
    # >= 64 KiB bcast routes hierarchically (leader tier + islands)
    if rank == 1:
        big[:] = np.arange(70000, dtype=np.float32) + it
    rc = lib.tpucomm_bcast(h, p(big), ctypes.c_int64(big.nbytes), 1)
    assert rc == 0
    assert big[7] == 7.0 + it, big[7]
    assert lib.tpucomm_barrier(h) == 0

# forced-qalltoall burst (the MoE dispatch wire): the int8 codec packs
# and unpacks concurrently with the progress/writer threads; own-rank
# chunk stays exact, every chunk inside the codec error bound
QA2A = 9
cnt = 700
lib.tpucomm_alltoall_algo.restype = ctypes.c_int
lib.tpucomm_alltoall_algo.argtypes = [
    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ctypes.c_int, ctypes.c_int]
base = np.stack([
    np.stack([(np.arange(cnt, dtype=np.float32) % 7 - 3) * (s + 1 + 2 * d)
              for d in range(size)])
    for s in range(size)])
sx = base[rank].copy()
rx = np.zeros_like(sx)
want = base[:, rank]
bound = np.max(np.abs(base)) / 127.0 * 0.5 + 1e-6
for it in range(8):
    rc = lib.tpucomm_alltoall_algo(h, p(sx), p(rx), cnt, F32, QA2A)
    assert rc == 0, f"qalltoall failed at iter {it}"
    assert np.array_equal(rx[rank], want[rank]), f"own chunk iter {it}"
    assert np.max(np.abs(rx - want)) <= bound, f"codec bound iter {it}"
    assert lib.tpucomm_barrier(h) == 0

lib.tpucomm_finalize(ctypes.c_int64(intra_h))
lib.tpucomm_finalize(ctypes.c_int64(lead_h))
lib.tpucomm_finalize(ctypes.c_int64(h))
print("san-rank-ok", rank, flush=True)
"""


@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_hier_two_islands_three_ranks(shm):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    extra = {
        "MPI4JAX_TPU_JOBID": f"tsanhier{shm}{os.getpid()}",
        "MPI4JAX_TPU_PROGRESS_THREAD": "1",
        # the virtual partition is what grants the intra-island arena
        # while withholding the world one
        "MPI4JAX_TPU_FAKE_HOSTS": "r0,r1|r2",
    }
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    _run_group(
        _HIER_RANK_SRC, 3, so, preload,
        {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"},
        28500 + (os.getpid() + (17 if shm == "on" else 0)) % 900,
        extra,
    )


# ---- elastic shrink under load (TSan) ------------------------------
#
# The recovery bootstrap is the second lifecycle the transport's
# threads cross (engine shutdown + socket close + a fresh dial/accept
# mesh while the survivors' writer/progress threads wind down), so it
# gets a sanitized battery too: a 3-rank engine-armed load loop whose
# rank 1 vanishes mid-stream; the survivors detect the failure on a
# live op, abort-propagate, tpucomm_shrink into a 2-rank world at a
# re-derived port, and run the SAME load to completion — 0 reports
# required.

_SHRINK_RANK_SRC = r"""
import ctypes, os, sys
import numpy as np

so = os.environ["SAN_SO"]
rank = int(os.environ["SAN_RANK"])
size = int(os.environ["SAN_SIZE"])
port = int(os.environ["SAN_PORT"])

lib = ctypes.CDLL(so)
lib.tpucomm_init.restype = ctypes.c_int64
lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                             ctypes.c_char_p]
lib.tpucomm_shrink.restype = ctypes.c_int64
lib.tpucomm_shrink.argtypes = [ctypes.c_int64, ctypes.c_int,
                               ctypes.c_int, ctypes.c_int,
                               ctypes.c_char_p]
h = lib.tpucomm_init(rank, size, port, b"")
assert h > 0, "tpucomm_init failed"

F32, SUM = 11, 0
n = 256
p = lambda a: a.ctypes.data_as(ctypes.c_void_p)

def load_iter(h, rank, size, it, must=False):
    '''One iteration of engine-armed load; returns False on the first
    transport failure (must=False) or asserts success (must=True).'''
    buf = np.arange(n, dtype=np.float32) + rank
    out = np.zeros_like(buf)
    dest = (rank + 1) % size
    src = (rank - 1 + size) % size
    for i in range(6):
        rc = lib.tpucomm_send(h, p(buf), buf.nbytes, dest, it * 8 + i)
        if rc:
            assert not must, f"send failed post-shrink at {it}.{i}"
            return False
    for i in range(6):
        rc = lib.tpucomm_recv(h, p(out), out.nbytes, src, it * 8 + i)
        if rc:
            assert not must, f"recv failed post-shrink at {it}.{i}"
            return False
    rc = lib.tpucomm_allreduce(h, p(buf), p(out), n, F32, SUM)
    if rc:
        assert not must, f"allreduce failed post-shrink at {it}"
        return False
    assert out[0] == sum(range(size)), out[0]
    rc = lib.tpucomm_barrier(h)
    if rc:
        assert not must, f"barrier failed post-shrink at {it}"
        return False
    return True

failed = False
for it in range(8):
    if rank == 1 and it == 3:
        # the injected death: vanish mid-stream with the mesh live
        # (peers see a reset on their next op touching this rank)
        print("san-rank-ok", rank, flush=True)
        os._exit(0)
    if not load_iter(h, rank, size, it):
        failed = True
        break

assert failed, "survivors must observe the rank death"
lib.tpucomm_abort_all()
new_rank = {0: 0, 2: 1}[rank]
h2 = lib.tpucomm_shrink(h, new_rank, 2, port + 7, b"")
assert h2 > 0, "tpucomm_shrink bootstrap failed"
for it in range(6):
    load_iter(h2, new_rank, 2, 100 + it, must=True)
lib.tpucomm_finalize(ctypes.c_int64(h2))
print("san-rank-ok", rank, flush=True)
"""


@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_shrink_under_load_three_ranks(shm):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    extra = {
        "MPI4JAX_TPU_JOBID": f"tsanshr{shm}{os.getpid()}",
        "MPI4JAX_TPU_PROGRESS_THREAD": "1",
        "MPI4JAX_TPU_COALESCE_BYTES": "4096",
        # bound every wait: a survivor parked on the dead rank's
        # socket (or the shm barrier, shm=on) must fail over, not hang
        "MPI4JAX_TPU_TIMEOUT_S": "10",
        "MPI4JAX_TPU_CONNECT_TIMEOUT_S": "30",
    }
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    _run_group(
        _SHRINK_RANK_SRC, 3, so, preload,
        {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"},
        28400 + (os.getpid() + (19 if shm == "on" else 0)) % 900,
        extra,
    )


# ---- self-healing reconnect (TSan + ASan) --------------------------
#
# The reconnect path is a fourth lifecycle the threads cross: an
# injected RST mid-stream, the victim thread parking the fd while a
# fresh dial races the peer's accept, the hello exchange, gap replay
# from the retain ring, and seq dedup on the receiver — all while the
# progress thread (engine legs) keeps polling the same link set.  A
# 2-rank armed pair heals an injected reset and finishes the SAME
# deterministic load, 0 reports required.  The shm-on leg resets the
# idle TCP link under the arena and recovers it via heartbeats.

_HEAL_RANK_SRC = r"""
import ctypes, os, sys, time
import numpy as np

so = os.environ["SAN_SO"]
rank = int(os.environ["SAN_RANK"])
size = 2
port = int(os.environ["SAN_PORT"])

lib = ctypes.CDLL(so)
lib.tpucomm_init.restype = ctypes.c_int64
lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                             ctypes.c_char_p]
h = lib.tpucomm_init(rank, size, port, b"")
assert h > 0, "tpucomm_init failed"

F32, SUM = 11, 0  # wire codes (tpucomm.h)
n = 1024
buf = np.arange(n, dtype=np.float32) + rank
out = np.zeros_like(buf)
p = lambda a: a.ctypes.data_as(ctypes.c_void_p)

# phase 1: p2p pingpong — the injected reset lands here (point=send
# counts transmissions) and the armed layer must heal it in place
for it in range(12):
    if rank == 0:
        lib.tpucomm_send(h, p(buf), buf.nbytes, 1, it)
        rc = lib.tpucomm_recv(h, p(out), out.nbytes, 1, it)
    else:
        rc = lib.tpucomm_recv(h, p(out), out.nbytes, 0, it)
        lib.tpucomm_send(h, p(buf), buf.nbytes, 0, it)
    assert rc == 0, f"recv failed at iter {it}"
    assert out[3] == 3.0 + (1 - rank), out[3]

# shm-on leg: park the wire so the heartbeat (not an op) finds the
# reset link and heals it before phase 2
sleep_s = float(os.environ.get("SAN_SLEEP_S", "0"))
if sleep_s > 0:
    time.sleep(sleep_s)

# phase 2: collectives over the healed link
for it in range(8):
    rc = lib.tpucomm_allreduce(h, p(buf), p(out), n, F32, SUM)
    assert rc == 0, f"allreduce failed at iter {it}"
    assert out[1] == 3.0, out[1]
    assert lib.tpucomm_barrier(h) == 0

cnt = (ctypes.c_int64 * 6)()
lib.tpucomm_link_counters(*[ctypes.byref(cnt, 8 * i) for i in range(6)])
assert cnt[1] >= 1, f"no reconnect recorded (counters {list(cnt)})"
lib.tpucomm_finalize(ctypes.c_int64(h))
print("san-rank-ok", rank, flush=True)
"""


def _heal_env(shm, uring, so, preload, san, tag):
    extra = {
        "MPI4JAX_TPU_JOBID": f"{tag}{shm}{uring}{os.getpid()}",
        "MPI4JAX_TPU_RETRY": "4",
        "MPI4JAX_TPU_RETRY_BACKOFF_MS": "50",
        "MPI4JAX_TPU_TIMEOUT_S": "60",
        "MPI4JAX_TPU_FAULT": "rank=0,point=send,after=5,action=reset",
        **_uring_env(uring, so, preload, san),
    }
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    else:
        # shm traffic can't be reset, so the fault lands on the idle
        # TCP link underneath — only heartbeats can find it
        extra["MPI4JAX_TPU_PROGRESS_THREAD"] = "1"
        extra["MPI4JAX_TPU_HEARTBEAT_S"] = "0.2"
        extra["SAN_SLEEP_S"] = "2.0"
    return extra


@pytest.mark.parametrize("uring", ["0", "1"])
@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_self_heal_reconnect(shm, uring):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    san = {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"}
    _run_group(
        _HEAL_RANK_SRC, 2, so, preload, san,
        28700 + (os.getpid() + (23 if shm == "on" else 0)
                 + (41 if uring == "1" else 0)) % 400,
        _heal_env(shm, uring, so, preload, san, "tsanheal"),
    )


@pytest.mark.parametrize("uring", ["0", "1"])
@pytest.mark.parametrize("shm", ["on", "off"])
def test_asan_self_heal_reconnect(shm, uring):
    _build("asan")
    preload = _preload_path("libasan.so")
    so = os.path.join(SO_DIR, "libtpucomm_asan.so")
    san = {
        "ASAN_OPTIONS": "exitcode=66 detect_leaks=0 halt_on_error=1",
        "UBSAN_OPTIONS": "halt_on_error=1 print_stacktrace=1",
    }
    _run_group(
        _HEAL_RANK_SRC, 2, so, preload, san,
        29100 + (os.getpid() + (23 if shm == "on" else 0)
                 + (41 if uring == "1" else 0)) % 400,
        _heal_env(shm, uring, so, preload, san, "asanheal"),
    )


@pytest.mark.parametrize("uring", ["0", "1"])
@pytest.mark.parametrize("shm", ["on", "off"])
def test_asan_loopback_pair(shm, uring):
    _build("asan")
    preload = _preload_path("libasan.so")
    so = os.path.join(SO_DIR, "libtpucomm_asan.so")
    san = {
        "ASAN_OPTIONS": "exitcode=66 detect_leaks=0 halt_on_error=1",
        "UBSAN_OPTIONS": "halt_on_error=1 print_stacktrace=1",
    }
    extra = {"MPI4JAX_TPU_JOBID": f"asan{shm}{uring}{os.getpid()}",
             **_uring_env(uring, so, preload, san)}
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    _run_pair(
        so, preload, san,
        27200 + (os.getpid() + (7 if shm == "on" else 0)
                 + (37 if uring == "1" else 0)) % 900,
        extra,
    )


# ---- live re-tuning under TSan -------------------------------------
#
# The live controller is a NEW concurrent reader of the transport's
# state: its thread walks the obs ring via tpucomm_obs_peek while op
# threads append, and a mid-run epoch commit promotes staged decision
# tables (engine quiesced, comm lock held) while the dispatch path
# reads them per call.  A 2-rank pair runs the full Python stack
# (bridge + armed controller) against the sanitized .so, proposes a
# swap mid-loop, and requires 0 reports — shm on and off.

_LIVE_RANK_SRC = r"""
import os, sys, types
REPO = os.environ["SAN_REPO"]
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu import live
from mpi4jax_tpu.runtime import bridge

rank = int(os.environ["SAN_RANK"])
port = int(os.environ["SAN_PORT"])
h = bridge.comm_init(rank, 2, "127.0.0.1:%d" % port)
assert live.armed(), "controller must arm under MPI4JAX_TPU_LIVE=auto"
x = np.arange(2048, dtype=np.int32)
for it in range(60):
    out = bridge.allreduce(h, x + it, 0)  # SUM
    assert out[0] == 2 * it, (it, out[0])
    if it == 20 and rank == 0:
        live.propose({"allreduce": [(0, "rd")]}, note="tsan")
st = live.status()
assert st["epoch"] >= 1, st
assert st["errors"] == 0, st
bridge.comm_finalize(h)
print("san-rank-ok", rank, flush=True)
"""


def _live_env(shm, tag):
    extra = {
        "SAN_REPO": REPO,
        "MPI4JAX_TPU_NATIVE_LIB": os.path.join(
            SO_DIR, "libtpucomm_tsan.so"),
        "MPI4JAX_TPU_JOBID": f"{tag}{shm}{os.getpid()}",
        "MPI4JAX_TPU_LIVE": "auto",
        "MPI4JAX_TPU_LIVE_WINDOW": "64",
        "MPI4JAX_TPU_LIVE_COOLDOWN_OPS": "8",
    }
    if shm == "off":
        extra["MPI4JAX_TPU_DISABLE_SHM"] = "1"
    return extra


@pytest.mark.parametrize("shm", ["on", "off"])
def test_tsan_live_retune_pair(shm):
    _build("tsan")
    preload = _preload_path("libtsan.so")
    so = os.path.join(SO_DIR, "libtpucomm_tsan.so")
    san = {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"}
    _run_group(
        _LIVE_RANK_SRC, 2, so, preload, san,
        29500 + (os.getpid() + (19 if shm == "on" else 0)) % 400,
        _live_env(shm, "tsanlive"),
    )
