"""ctypes bridge to the native tpucomm transport (L3/L4 of the stack).

The reference's registration layer imports Cython extensions and registers
XLA custom-call targets (/root/reference/mpi4jax/_src/xla_bridge/__init__.py).
Here the native library is loaded with ctypes and invoked from *ordered host
callbacks* — on TPU that callback IS the HBM→host staging path (the
structural twin of the reference GPU bridge's
cudaMemcpy-to-host → MPI → copy-back sequence,
mpi_xla_bridge_gpu.pyx:233-251), with XLA managing the device↔host
transfers.

Fail-fast: a nonzero return from any native call prints
``tpucomm_<Op> returned error code N`` and hard-exits the process (the
analog of the reference's abort_on_error → MPI_Abort,
mpi_xla_bridge.pyx:67-91); peers then fail on their sockets and exit too.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
from typing import Optional

import numpy as np

from ..utils import config, dtypes as _dtypes

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SO_PATH = os.path.join(os.path.dirname(__file__), "_native", "libtpucomm.so")
# the hash of the sources the library was built from, beside the library
# (native/Makefile writes the same stamp: sha256 of the three files' bytes
# concatenated in this order)
_STAMP_PATH = _SO_PATH + ".sha256"
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC = os.path.join(_NATIVE_DIR, "tpucomm.cc")
_FFI_SRC = os.path.join(_NATIVE_DIR, "tpucomm_ffi.cc")
_SOURCES = (_SRC, _FFI_SRC, os.path.join(_NATIVE_DIR, "tpucomm.h"))

_lib: Optional[ctypes.CDLL] = None


def _source_hash() -> str:
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(digest: str) -> None:
    """Build transport + XLA FFI handlers from the committed sources;
    a failed build raises with the compiler's output."""
    # Build to a temp path and atomically rename: concurrent launcher ranks
    # may rebuild simultaneously, and a sibling rank must never CDLL-load a
    # partially written .so.
    import jax.ffi as _jffi

    os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
    tmp = f"{_SO_PATH}.tmp.{os.getpid()}"
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O2", "-std=c++17", "-fPIC", "-Wall", "-pthread", "-shared",
        "-o", tmp, f"-I{_NATIVE_DIR}", f"-I{_jffi.include_dir()}",
        _SRC, _FFI_SRC,
        # glibc < 2.34 keeps shm_open in librt (an empty stub after):
        # without it the link succeeds but dlopen fails with an undefined
        # symbol
        "-lrt",
    ]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"building the native transport failed "
                f"({' '.join(cmd)}):\n{r.stderr}"
            )
        os.replace(tmp, _SO_PATH)
        with open(f"{_STAMP_PATH}.tmp.{os.getpid()}", "w") as f:
            f.write(digest + "\n")
        os.replace(f"{_STAMP_PATH}.tmp.{os.getpid()}", _STAMP_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _stale(digest: str) -> bool:
    """True unless the library beside us was built from these exact
    sources (a hash, not mtimes: a copied tree keeps neither)."""
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() != digest or not os.path.exists(_SO_PATH)
    except FileNotFoundError:
        return True


def get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    so_path = config.native_lib_override()
    if so_path is not None:
        # explicit library (sanitizer builds, cross-build tests): load it
        # verbatim — no staleness check, no rebuild
        if not os.path.exists(so_path):
            raise RuntimeError(
                f"MPI4JAX_TPU_NATIVE_LIB={so_path} does not exist"
            )
        return _finish_lib_setup(ctypes.CDLL(so_path))
    digest = _source_hash()
    if _stale(digest):
        _build(digest)
    return _finish_lib_setup(ctypes.CDLL(_SO_PATH))


def _finish_lib_setup(lib: ctypes.CDLL) -> ctypes.CDLL:
    global _lib, _exec_fn
    lib.tpucomm_init.restype = ctypes.c_int64
    lib.tpucomm_init.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
    ]
    lib.tpucomm_set_logging.argtypes = [ctypes.c_int]
    # batched dispatch entry (async progress engine): one cached
    # descriptor struct per (comm, op) and ONE ctypes call per op —
    # no per-call marshalling of 6-8 scalar arguments (measured ~12 us
    # of Python overhead per 1 KB allreduce on the classic path, ~3 us
    # on this one).  Guarded like split/dup: a stale prebuilt .so keeps
    # serving through the classic per-op entries.
    if hasattr(lib, "tpucomm_execute"):
        lib.tpucomm_execute.restype = ctypes.c_int
        lib.tpucomm_execute.argtypes = [ctypes.c_int64, ctypes.c_void_p]
        _exec_fn = lib.tpucomm_execute
    # ticketed non-blocking posting (schedule-plan execution); guarded
    # like split/dup: a stale prebuilt .so simply reports plans
    # unavailable (post_available) instead of failing at load
    if hasattr(lib, "tpucomm_post"):
        lib.tpucomm_post.restype = ctypes.c_int64
        lib.tpucomm_post.argtypes = [ctypes.c_int64, ctypes.c_void_p]
        lib.tpucomm_wait_ticket.restype = ctypes.c_int
        lib.tpucomm_wait_ticket.argtypes = [ctypes.c_int64, ctypes.c_int64]
    # elastic recovery (guarded like split/dup: a stale prebuilt .so
    # reports recovery unavailable instead of failing at load)
    if hasattr(lib, "tpucomm_shrink"):
        lib.tpucomm_shrink.restype = ctypes.c_int64
        lib.tpucomm_shrink.argtypes = [
            ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p,
        ]
    # topology subsystem (guarded like split/dup: a stale prebuilt .so
    # keeps the flat transport; discovery then only feeds the Python
    # probes and the topology-keyed tune cache)
    if hasattr(lib, "tpucomm_set_topology"):
        lib.tpucomm_set_topology.restype = ctypes.c_int
        lib.tpucomm_set_topology.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64,
        ]
        lib.tpucomm_topo_info.restype = ctypes.c_int
        lib.tpucomm_topo_info.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
    # guarded: a stale prebuilt .so without split/dup must still serve
    # the other ops (split then fails at call time, not load time)
    if hasattr(lib, "tpucomm_split"):
        lib.tpucomm_split.restype = ctypes.c_int64
        lib.tpucomm_split.argtypes = [
            ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ]
    if hasattr(lib, "tpucomm_dup"):
        lib.tpucomm_dup.restype = ctypes.c_int64
        lib.tpucomm_dup.argtypes = [ctypes.c_int64]
    # collective algorithm engine (guarded like split/dup: a stale
    # prebuilt .so keeps serving the fixed schedules)
    if hasattr(lib, "tpucomm_set_coll_table"):
        lib.tpucomm_set_coll_table.restype = None
        lib.tpucomm_set_coll_table.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
        ]
    if hasattr(lib, "tpucomm_coll_algo_for"):
        lib.tpucomm_coll_algo_for.restype = ctypes.c_int
        lib.tpucomm_coll_algo_for.argtypes = [
            ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
        ]
    if config.debug_enabled():
        lib.tpucomm_set_logging(1)
    _lib = lib
    return lib


def set_native_logging(enabled: bool) -> None:
    get_lib().tpucomm_set_logging(1 if enabled else 0)


# ---------------- XLA FFI fast path ----------------
#
# Typed FFI handlers in native/tpucomm_ffi.cc, registered as cpu custom-call
# targets (≙ the reference's register_custom_call_target loop,
# xla_bridge/__init__.py:26-31 there).  When available, world-tier
# primitives lower straight to these — no Python in the dispatch path.

_FFI_TARGETS = {
    "tpucomm_shift2": "TpucommShift2Ffi",
    "tpucomm_allreduce": "TpucommAllreduceFfi",
    "tpucomm_reduce": "TpucommReduceFfi",
    "tpucomm_scan": "TpucommScanFfi",
    "tpucomm_bcast": "TpucommBcastFfi",
    "tpucomm_allgather": "TpucommAllgatherFfi",
    "tpucomm_gather": "TpucommGatherFfi",
    "tpucomm_scatter": "TpucommScatterFfi",
    "tpucomm_alltoall": "TpucommAlltoallFfi",
    "tpucomm_barrier": "TpucommBarrierFfi",
    "tpucomm_send": "TpucommSendFfi",
    "tpucomm_recv": "TpucommRecvFfi",
    "tpucomm_sendrecv": "TpucommSendrecvFfi",
    # token-operand variants (explicit-token mode wire format)
    "tpucomm_allreduce_t": "TpucommAllreduceTokFfi",
    "tpucomm_reduce_t": "TpucommReduceTokFfi",
    "tpucomm_scan_t": "TpucommScanTokFfi",
    "tpucomm_bcast_t": "TpucommBcastTokFfi",
    "tpucomm_allgather_t": "TpucommAllgatherTokFfi",
    "tpucomm_gather_t": "TpucommGatherTokFfi",
    "tpucomm_scatter_t": "TpucommScatterTokFfi",
    "tpucomm_alltoall_t": "TpucommAlltoallTokFfi",
    "tpucomm_barrier_t": "TpucommBarrierTokFfi",
    "tpucomm_send_t": "TpucommSendTokFfi",
    "tpucomm_recv_t": "TpucommRecvTokFfi",
    "tpucomm_shift2_t": "TpucommShift2TokFfi",
    "tpucomm_sendrecv_t": "TpucommSendrecvTokFfi",
}

_ffi_status: Optional[bool] = None


def ffi_available() -> bool:
    """Register the native FFI targets once; True if the fast path is up.

    Disabled by ``MPI4JAX_TPU_DISABLE_FFI=1`` (falls back to the host
    callback path) or when the library was built without the handlers.
    """
    global _ffi_status
    if _ffi_status is not None:
        return _ffi_status
    if config.ffi_disabled():
        _ffi_status = False
        return False
    if config.plan_spec() is not None:
        # schedule-plan execution lives in the host-executor layer; the
        # native FFI custom calls would bypass the plan runner entirely,
        # so a plan-enabled process keeps the callback dispatch route
        _ffi_status = False
        return False
    if config.elastic_enabled():
        # FFI lowering bakes the comm HANDLE into the compiled program
        # (an i64 attr); after a recovery rebinds the world to a fresh
        # native comm, such a baked handle would address the dead one.
        # The callback route resolves comm.handle per call, so rebound
        # comms keep working — elastic processes stay on it.
        _ffi_status = False
        return False
    try:
        import jax.ffi as jffi

        lib = get_lib()
        for target, symbol in _FFI_TARGETS.items():
            jffi.register_ffi_target(
                target, jffi.pycapsule(getattr(lib, symbol)), platform="cpu"
            )
        _ffi_status = True
    except (AttributeError, OSError, ImportError):
        _ffi_status = False
    return _ffi_status


def set_coll_table(coded_table) -> bool:
    """Push the tune package's merged decision table into the native
    layer: ``{op_kind: [(min_bytes, algo_code), ...]}``.  Returns False
    when the loaded .so predates the engine (fixed schedules serve)."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_set_coll_table"):
        return False
    for op_kind, entries in coded_table.items():
        n = len(entries)
        mins = (ctypes.c_int64 * n)(*[int(e[0]) for e in entries])
        algos = (ctypes.c_int32 * n)(*[int(e[1]) for e in entries])
        lib.tpucomm_set_coll_table(int(op_kind), mins, algos, n)
    return True


def stage_coll_table(coded_table) -> bool:
    """Park candidate decision tables in the native staging slots
    WITHOUT touching dispatch (same coding as :func:`set_coll_table`);
    :func:`commit_coll_tables` promotes them atomically.  False when
    the loaded .so predates the live re-tuning entry points."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_stage_coll_table"):
        return False
    for op_kind, entries in coded_table.items():
        n = len(entries)
        mins = (ctypes.c_int64 * n)(*[int(e[0]) for e in entries])
        algos = (ctypes.c_int32 * n)(*[int(e[1]) for e in entries])
        lib.tpucomm_stage_coll_table(int(op_kind), mins, algos, n)
    return True


def commit_coll_tables(handle, epoch: int) -> bool:
    """Promote every staged table to live under the comm lock with the
    progress engine quiesced, stamping ``epoch`` — the swap half of the
    live re-tuning protocol (all ranks call this at the same collective
    boundary).  False when the .so predates it."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_commit_coll_tables"):
        return False
    rc = lib.tpucomm_commit_coll_tables(_i64(handle), _i64(epoch))
    if rc != 0:
        raise ValueError(f"bad comm handle {handle}")
    return True


def coll_epoch():
    """The live decision-table epoch (0 = the offline-installed table),
    or None when the loaded .so predates live re-tuning."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_coll_epoch"):
        return None
    fn = lib.tpucomm_coll_epoch
    fn.restype = ctypes.c_int64
    return int(fn())


def coll_algo_for(handle, op_kind: int, nbytes: int):
    """The TpuCollAlgo code that would serve (comm, op kind, payload) —
    including the shm code when the arena path wins.  None when the
    loaded .so predates the engine."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_coll_algo_for"):
        return None
    code = lib.tpucomm_coll_algo_for(_i64(handle), int(op_kind), _i64(nbytes))
    if code < 0:
        raise ValueError(f"bad comm handle {handle}")
    return code



def uring_status():
    """Resolved state of the native io_uring submission backend:
    ``"on"``, ``"on(no-zerocopy)"``, ``"off"`` (MPI4JAX_TPU_URING=0),
    or ``"unavailable(<reason>)"`` — or None when the loaded .so
    predates the uring generation entirely (the layout probe: such a
    build has no uring path and never writes the obs ``syscalls``
    field, so it must read as uring-unavailable, not be misparsed)."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_uring_status"):
        return None
    fn = lib.tpucomm_uring_status
    fn.restype = ctypes.c_char_p
    return (fn() or b"").decode(errors="replace")


def syscall_count():
    """Process-total transport syscalls since load (write/read/writev/
    poll/io_uring_enter; futexes excluded) — benchmarks read deltas of
    this for their syscalls-per-message column.  None on a pre-uring
    .so (no counter)."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_syscall_count"):
        return None
    fn = lib.tpucomm_syscall_count
    fn.restype = ctypes.c_int64
    return int(fn())


def quant_available() -> bool:
    """True when the loaded native library carries the quantized
    collective engine (qring/qrd wire formats + the codec exports) —
    the gate the ops layer uses before routing
    ``allreduce(compression="int8")`` to the native path."""
    return hasattr(get_lib(), "tpucomm_quant_packed_bytes")


def quant_packed_bytes(count: int) -> int:
    """On-wire bytes of ``count`` elements under the native int8+scales
    codec (``4 * ceil(count/256) + count``)."""
    lib = get_lib()
    lib.tpucomm_quant_packed_bytes.restype = ctypes.c_int64
    lib.tpucomm_quant_packed_bytes.argtypes = [ctypes.c_int64]
    return int(lib.tpucomm_quant_packed_bytes(_i64(count)))


def quant_pack(buf: np.ndarray) -> np.ndarray:
    """Pack a float array through the NATIVE wire codec (the exact
    bytes qring/qrd put on the wire); returns the packed int8 buffer.
    Raises on an ineligible dtype — mirrors the engine's gate."""
    buf = _contig(buf)
    out = np.empty(quant_packed_bytes(buf.size), np.int8)
    rc = get_lib().tpucomm_quant_pack(
        _ptr(buf), _i64(buf.size), _dtypes.wire_code(buf.dtype), _ptr(out))
    if rc != 0:
        raise TypeError(
            f"dtype {buf.dtype} has no quantized wire format (real "
            "floating dtypes only)")
    return out


def quant_unpack(packed: np.ndarray, count: int, dtype) -> np.ndarray:
    """Inverse of :func:`quant_pack` (native codec)."""
    packed = _contig(packed)
    out = np.empty(int(count), dtype)
    rc = get_lib().tpucomm_quant_unpack(
        _ptr(packed), _i64(count), _dtypes.wire_code(out.dtype), _ptr(out))
    if rc != 0:
        raise TypeError(
            f"dtype {out.dtype} has no quantized wire format (real "
            "floating dtypes only)")
    return out


def shm_info(handle: int):
    """(active, slot_bytes, ring_bytes) for a comm's same-host fast
    paths — 'active' False means the comm runs on TCP only (cross-host
    members, MPI4JAX_TPU_DISABLE_SHM, or arena creation failed soft)."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_shm_info"):
        # stale prebuilt .so from before the symbol existed (get_lib
        # keeps it when a rebuild isn't possible) — report inactive
        # rather than failing a healthy transport
        return False, 0, 0
    slot = ctypes.c_int64(0)
    ring = ctypes.c_int64(0)
    rc = lib.tpucomm_shm_info(ctypes.c_int64(handle), ctypes.byref(slot),
                              ctypes.byref(ring))
    if rc < 0:
        raise ValueError(f"bad comm handle {handle}")
    return bool(rc), slot.value, ring.value


def _abort(opname: str, rc: int):
    # include the native layer's human-readable reason, the analog of the
    # reference's ierr -> MPI_Error_string conversion before MPI_Abort
    # (mpi_xla_bridge.pyx:67-91 there)
    detail = ""
    try:
        lib = get_lib()
        lib.tpucomm_last_error.restype = ctypes.c_char_p
        text = (lib.tpucomm_last_error() or b"").decode(errors="replace")
        if text:
            detail = f": {text}"
    except Exception:
        lib = None
    print(
        f"tpucomm_{opname} returned error code {rc}{detail}",
        file=sys.stderr, flush=True,
    )
    # job-wide abort propagation: poison every peer socket (non-blocking)
    # so the group tears down within one deadline instead of waiting for
    # per-rank timeouts to cascade; peers without a pending recv still
    # observe the shutdown sockets and abort as before
    try:
        if lib is not None and hasattr(lib, "tpucomm_abort_all"):
            lib.tpucomm_abort_all()
    except Exception:
        pass
    # elastic worlds (docs/elasticity.md): surface the failure as an
    # exception the recovery layer can catch instead of killing the
    # process.  The poison/shutdown above already ran, so every peer
    # unblocks within one deadline and reaches ITS recovery point too —
    # the same propagation that used to cascade the teardown now
    # cascades the recovery.  The old world is unusable either way
    # (sockets are shut down); elastic.recover() rebuilds it.
    if config.elastic_enabled():
        from ..elastic import RankFailure

        raise RankFailure(f"tpucomm_{opname} failed{detail}", op=opname)
    os._exit(1)


def _check(opname: str, rc: int):
    if rc != 0:
        _abort(opname, rc)


def comm_init(rank: int, size: int, coord: str, hosts=None) -> int:
    lib = get_lib()
    host, _, port = coord.partition(":")
    if hosts is None:
        hosts = os.environ.get("MPI4JAX_TPU_HOSTS", "")
    handle = lib.tpucomm_init(
        rank, size, int(port or 49817), hosts.encode()
    )
    if handle == 0:
        _abort("init", 1)
    _post_init_setup(lib, handle, rank, size, install_plan=True)
    return handle


def _post_init_setup(lib, handle, rank: int, size: int, *,
                     install_plan: bool) -> None:
    """The selection/telemetry layers every fresh world needs, shared by
    :func:`comm_init` and elastic recovery's :func:`rebuild`."""
    # topology discovery FIRST (it is collective, and the tune install
    # below keys the persistent cache on the discovered fingerprint).
    # MPI4JAX_TPU_TOPO=off skips it entirely; a malformed FAKE_HOSTS
    # spec stays fail-fast (the native bootstrap already exited on it).
    topology = None
    if size > 1 and config.topo_mode() != "off":
        topology = _install_topology(lib, handle, rank, size)
    # the tune layer only sees the topology when the native layer can
    # actually RUN the hierarchical schedules: on a stale .so (no
    # tpucomm_set_topology) its set_coll_table drops the unknown hring
    # code, and a flipped default table would silently degrade large
    # allreduces to the small-payload tree — discovery then serves the
    # Python probes only, and the flat defaults/caches stay in force
    tune_topology = (topology
                     if hasattr(lib, "tpucomm_set_topology") else None)
    # collective algorithm engine: load the persistent autotune cache and
    # push the merged decision table natively — every dispatch path
    # (eager / callback / FFI) then resolves the algorithm per call.
    # Soft for infrastructure problems (a selection-layer hiccup must
    # never take down a healthy transport; the built-in heuristics
    # serve), but a malformed MPI4JAX_TPU_COLL_ALGO stays fail-fast —
    # silently ignoring the operator's force is worse than stopping
    # (same contract as the boolean knob parser).
    try:
        from .. import tune

        tune.install(size, topology=tune_topology)
    except ValueError:
        raise
    except Exception as e:  # pragma: no cover - defensive
        import warnings

        warnings.warn(f"collective algorithm table install failed: {e}")
    # observability: arm the recorder when MPI4JAX_TPU_TRACE asks for a
    # dump, or re-arm (now with the native ring + clock alignment) when
    # the program called obs.start() before any comm existed.  Arming
    # MUST AGREE ACROSS RANKS (the same contract as DISABLE_SHM /
    # COLL_ALGO): the alignment handshake below is collective, so a
    # divergent condition — TRACE exported on one host of a multi-host
    # job, obs.start() on a subset of ranks — pairs one rank's
    # handshake against another rank's first user op and aborts on the
    # transport's schedule checks.  The launcher sets TRACE uniformly.
    from .. import obs

    if config.trace_path() is not None or obs.enabled():
        _install_obs(lib, handle, rank, size)
    # live re-tuning: arm the drift controller + boundary hook when
    # MPI4JAX_TPU_LIVE=auto.  Arming MUST AGREE ACROSS RANKS (the epoch
    # rendezvous bcasts at agreed boundaries — a rank without the hook
    # would pair another rank's rendezvous against its next user op);
    # the launcher exports the knob uniformly.  Knob parse errors stay
    # fail-fast; infrastructure problems degrade soft like the tune
    # install above — a live-plane hiccup must never take down a
    # healthy transport.
    if config.live_mode() == "auto":
        try:
            from .. import live

            live.arm(lib, handle, rank, size)
        except ValueError:
            raise
        except Exception as e:  # pragma: no cover - defensive
            import warnings

            warnings.warn(f"live re-tuning arm failed: {e}")
    # schedule-plan execution: when MPI4JAX_TPU_PLAN names a verified
    # plan file (launch --plan), attach this rank's schedule to the
    # world comm.  Soft like the tune install above: a bad plan file
    # warns and the job runs the historic path.
    if install_plan and config.plan_spec() is not None:
        try:
            from . import planrt

            planrt.maybe_install_from_env(handle, rank, size)
        except Exception as e:  # pragma: no cover - defensive
            import warnings

            warnings.warn(f"schedule-plan install failed: {e}")


#: topology sub-communicator handles (intra-island, leaders) cached per
#: world handle — they borrow the world's sockets, so they must be
#: finalized BEFORE the world (comm_finalize / rebuild do)
_topo_handles: dict = {}

#: per-rank ROLES of those sub-comms, keyed by world handle: the ICI
#: data-plane leg (topo/_ici_leg.py) needs to know which handle is the
#: intra comm vs the leaders comm (plus this rank / island), which the
#: positional _topo_handles list cannot encode (non-leaders have no
#: leader handle, singleton islands no intra handle)
_topo_subcomms: dict = {}

_ici_leg_mod = None

#: live re-tuning collective-boundary hook (``mpi4jax_tpu.live`` sets it
#: while armed, None otherwise): called with the comm handle at the TOP
#: of every collective wrapper, before dispatch — the point where all
#: ranks of an SPMD program are at the same per-comm collective index,
#: so an epoch rendezvous injected here lands at the same boundary
#: everywhere.  The None default keeps MPI4JAX_TPU_LIVE=off at one
#: module-global load per collective — pre-live behavior bit-for-bit.
_live_boundary = None


def set_live_boundary(fn) -> None:
    """Install (or clear, ``None``) the live boundary hook."""
    global _live_boundary
    _live_boundary = fn


def _ici_leg_hook(handle, buf, out, dtype_code, op_code, algo) -> bool:
    """Pre-dispatch probe for the ICI data-plane leg: resolves to False
    in a couple of dict lookups on ineligible calls (flat comms and
    sub-comms never have a _topo_subcomms entry; the leg is f32 SUM
    only — wire codes from native/tpucomm.h) so the native fast paths
    keep their cost profile.  The full gate chain lives in
    ``topo._ici_leg.maybe_allreduce``."""
    global _ici_leg_mod
    if int(handle) not in _topo_subcomms:
        return False
    if dtype_code != 11 or op_code != 0:
        return False
    if _ici_leg_mod is None:
        from ..topo import _ici_leg

        _ici_leg_mod = _ici_leg
    return _ici_leg_mod.maybe_allreduce(
        handle, buf, out, dtype_code, op_code, algo)


def _install_topology(lib, handle, rank: int, size: int):
    """Run the discovery handshake, derive the sub-communicators on a
    multi-island world, and install the map natively.  COLLECTIVE:
    every rank runs it at the same position inside comm_init/rebuild.
    Returns the Topology (registered for ``WorldComm.topology()``), or
    None when discovery failed soft.

    Failure softness is asymmetric: the collectives themselves abort
    the job on transport errors (nothing to soften), but a native layer
    predating the topology exports, or a set_topology rejection, leaves
    the comm FLAT with a warning — locality awareness must never take
    down a healthy transport."""
    from .. import topo

    try:
        t = topo.discover(handle, rank, size)
    except Exception as e:
        # an elastic-mode TRANSPORT failure (peer died mid-handshake)
        # must propagate as the catchable RankFailure it is — only
        # discovery-layer problems (unparseable fingerprints, mixed
        # versions) soften to a flat transport
        if config.elastic_enabled():
            from ..elastic import RankFailure

            if isinstance(e, RankFailure):
                raise
        import warnings

        warnings.warn(f"topology discovery failed; transport stays "
                      f"flat: {e}")
        return None
    subs = []
    intra_h = leader_h = None
    if t.multi and hasattr(lib, "tpucomm_set_topology"):
        # both splits are collective over the world: EVERY rank calls
        # both, members or not (color -1 opts out of the leaders comm)
        my_island = t.island_of[rank]
        intra_h = split(handle, my_island, rank)
        am_leader = rank == t.leaders[my_island]
        leader_h = split(handle, 0 if am_leader else -1, rank)
        if len(t.islands[my_island]) == 1 and intra_h is not None:
            # a singleton island's intra comm is a size-1 shell the
            # schedules never touch; drop it rather than cache it
            comm_finalize(intra_h)
            intra_h = None
        subs = [h for h in (intra_h, leader_h) if h is not None]
    if hasattr(lib, "tpucomm_set_topology"):
        arr = (ctypes.c_int32 * size)(*t.island_of)
        rc = lib.tpucomm_set_topology(
            _i64(handle), arr, size, _i64(intra_h or 0),
            _i64(leader_h or 0))
        if rc != 0:
            import warnings

            warnings.warn(
                "native topology install was rejected; hierarchical "
                "schedules stay degraded to their flat twins")
    if subs:
        _topo_handles[int(handle)] = subs
        _topo_subcomms[int(handle)] = {
            "topology": t,
            "rank": rank,
            "island": t.island_of[rank],
            "intra": intra_h,
            "leader": leader_h,
        }
    topo._register(handle, t)
    return t


def _teardown_topology(handle) -> None:
    """Finalize the cached topology sub-comms of a world handle (they
    borrow its sockets — native finalize order requires children
    first) and forget its registry entries."""
    _topo_subcomms.pop(int(handle), None)
    for sub in _topo_handles.pop(int(handle), []):
        try:
            get_lib().tpucomm_finalize(_i64(sub))
        except Exception:  # pragma: no cover - teardown path
            pass
    try:
        from .. import topo

        topo._forget(handle)
    except Exception:  # pragma: no cover - teardown path
        pass


def topo_info(handle):
    """The NATIVE layer's installed island map for a comm:
    ``(island_of, n_islands)``, or None when the comm is flat or the
    loaded .so predates the topology subsystem."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_topo_info"):
        return None
    size = comm_size(handle)
    arr = (ctypes.c_int32 * size)()
    n = ctypes.c_int32(0)
    rc = lib.tpucomm_topo_info(_i64(handle), arr, ctypes.byref(n))
    if rc == -1:
        raise ValueError(f"bad comm handle {handle}")
    if rc != 0:
        return None
    return list(arr), int(n.value)


def shrink_available() -> bool:
    """True when the loaded .so carries the elastic recovery bootstrap."""
    return hasattr(get_lib(), "tpucomm_shrink")


def rebuild(old_handle, new_rank: int, new_size: int, base_port: int,
            hosts: str = "") -> int:
    """Elastic recovery's native step (``mpi4jax_tpu.elastic`` is the
    caller): finalize the dead world (``old_handle``; 0/None when none
    was ever created) and bootstrap a fresh one over the survivors at
    the re-derived ``base_port``, then rerun the per-world setup
    (decision table for the new size, obs re-arm with a new clock
    handshake).  Schedule plans are ELASTIC-SAFE: a plan is proved for
    one (program, np) shape, so the dead world's runner is dropped and
    the plan is re-derived AND re-proved for the new size inside the
    recovery (``planrt.reinstall_after_rebuild`` — from the
    ``MPI4JAX_TPU_PLAN`` bundle or a registered plan source), and only
    a freshly-proved, signature-checked plan executes on the recovered
    world; anything less degrades loudly to the always-correct
    token-order path (docs/elasticity.md)."""
    lib = get_lib()
    if not hasattr(lib, "tpucomm_shrink"):
        raise RuntimeError(
            "elastic recovery needs a native library with the "
            "tpucomm_shrink bootstrap; rebuild native/")
    # the dead world's topology sub-comms borrow its sockets: finalize
    # them BEFORE the native shrink finalizes the world (the documented
    # sub-comm teardown order), and drop the stale Topology — the
    # rebuilt world re-discovers below, so a shrink that emptied an
    # island cleanly re-derives the (possibly now flat) map
    if old_handle:
        _teardown_topology(old_handle)
        _live_disarm()
    handle = lib.tpucomm_shrink(
        _i64(old_handle or 0), int(new_rank), int(new_size),
        int(base_port), (hosts or "").encode())
    if handle == 0:
        _abort("shrink", 1)
    _post_init_setup(lib, handle, new_rank, new_size, install_plan=False)
    # the plan layer last: the rebuilt transport/selection/obs stack is
    # live, so the re-proof can install onto a working world.  Soft
    # like the comm_init install — a plan problem must never take a
    # recovered job down.
    try:
        from . import planrt

        planrt.reinstall_after_rebuild(old_handle, handle, new_rank,
                                       new_size)
    except Exception as e:  # pragma: no cover - defensive
        import warnings

        warnings.warn(f"schedule-plan reinstall failed after recovery: "
                      f"{e}")
    return handle


def _live_disarm(handle=None) -> None:
    """Stop the live controller + clear the boundary hook, if armed
    (a dying world's hook must not rendezvous on a dead handle).
    ``handle`` restricts the disarm to that comm's controller — closing
    an unrelated sub-comm leaves the world's controller running."""
    if _live_boundary is None:
        return
    try:
        from .. import live

        live.disarm(handle=handle)
    except Exception:  # pragma: no cover - defensive teardown
        set_live_boundary(None)


def comm_finalize(handle) -> None:
    """Close one native communicator (drains its engine first; cached
    topology sub-comms go first — they borrow its sockets)."""
    _live_disarm(handle)
    _teardown_topology(handle)
    get_lib().tpucomm_finalize(_i64(handle))


_obs_dump_registered = False


def _install_obs(lib, handle, rank: int, size: int) -> None:
    """Run the clock-alignment handshake, arm the recorder, and
    schedule the per-rank dump at interpreter exit.

    The handshake is COLLECTIVE, which is why arming must agree across
    ranks (see the call site): every armed rank runs it here at the
    same program position — a barrier, then each rank samples its unix
    clock inside the same barrier-exit window and allgathers the
    samples; the median minus the local sample is this rank's offset
    onto the job-global timeline.  It runs BEFORE recording starts, so
    its own collectives never pollute the recording.

    Re-arming resets the recorder: spans recorded before the comm
    existed are dropped in favor of a recording whose every event is on
    the aligned timeline.
    """
    global _obs_dump_registered
    from .. import obs

    offset_s = 0.0
    if size > 1:
        import time

        barrier(handle)
        t_local = time.time()
        all_t = np.sort(allgather(handle, np.array([t_local], np.float64),
                                  size).ravel())
        offset_s = float(all_t[size // 2]) - t_local
    obs.start(lib=lib, rank=rank, size=size, clock_offset_s=offset_s)
    if not _obs_dump_registered:
        _obs_dump_registered = True
        import atexit

        atexit.register(_dump_obs_at_exit)


def _dump_obs_at_exit() -> None:
    base = config.trace_path()
    if base is None:
        return
    try:
        # drain pending async dispatch first: a span recorded for an op
        # whose effects have not executed yet would be a lie
        import jax

        jax.effects_barrier()
    except Exception:
        pass
    try:
        from .. import obs

        path = obs.dump(base)
        print(f"[obs] recording written to {path}", file=sys.stderr,
              flush=True)
    except Exception as e:  # pragma: no cover - defensive teardown path
        print(f"[obs] recording dump failed: {e}", file=sys.stderr,
              flush=True)


def _contig(a) -> np.ndarray:
    # NB: np.ascontiguousarray promotes 0-d arrays to 1-d; preserve shape
    a = np.asarray(a)
    return a if a.flags.c_contiguous else a.copy(order="C")


# ---------------- batched dispatch (async progress engine) ----------------
#
# Mirror of ``struct TpuOpExec`` in native/tpucomm.h (field-for-field).
# The hot wrappers below pack ONE cached descriptor per (comm, op kind)
# and make a single pre-argtyped native call instead of marshalling each
# scalar argument through ctypes on every op — the host-dispatch share
# the BENCH_r05 72 us in-jit vs 48 us transport gap is made of.

class _OpExec(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_int32),
        ("algo", ctypes.c_int32),
        ("sbuf", ctypes.c_void_p),
        ("rbuf", ctypes.c_void_p),
        ("snbytes", ctypes.c_int64),
        ("rnbytes", ctypes.c_int64),
        ("count", ctypes.c_int64),
        ("dtype", ctypes.c_int32),
        ("rop", ctypes.c_int32),
        ("peer", ctypes.c_int32),
        ("peer2", ctypes.c_int32),
        ("tag", ctypes.c_int32),
        ("tag2", ctypes.c_int32),
    ]


#: TpuObsOp codes (tpucomm.h) used as TpuOpExec.kind
_K_SEND, _K_RECV, _K_SENDRECV, _K_SHIFT2, _K_BARRIER, _K_BCAST = range(6)
_K_GATHER, _K_SCATTER, _K_ALLGATHER, _K_ALLTOALL = 6, 7, 8, 9
_K_ALLREDUCE, _K_REDUCE, _K_SCAN = 10, 11, 12

_exec_fn = None          # lib.tpucomm_execute with argtypes preset

# The descriptor / output-buffer caches are THREAD-LOCAL: a cached
# struct is mutated then passed to a GIL-releasing native call, so two
# threads sharing one entry could interleave mutate-and-call (the comm
# lock serializes the native side, not the Python-side packing).  Ops
# on one comm are normally serialized upstream by ordered effects, but
# "sharing one WorldComm between threads is safe" is a documented
# contract (docs/sharp-bits.md § Communicator hygiene) and stays true.
_tls = __import__("threading").local()

#: per-thread cache size bound: dicts are cleared (not evicted LRU —
#: simplicity over perfection; a clear costs one re-population) past
#: this many entries, so pathological shape churn cannot pin memory
_CACHE_CAP = 64


def _tls_cache(name):
    d = getattr(_tls, name, None)
    if d is None:
        d = {}
        setattr(_tls, name, d)
    return d


def _exec_desc(handle, kind, *const_fields):
    """The cached (handle_c, descriptor, byref) triple for one comm+op;
    callers mutate the descriptor's per-call fields and invoke
    ``_exec_fn(handle_c, ref)``.

    ``const_fields`` are (name, value) pairs baked into the cached
    descriptor: ctypes Structure field stores cost ~0.3 us each through
    the descriptor protocol, so per-op constants (dtype, reduce op,
    root, forced algorithm) are part of the cache key and written once
    instead of on every call."""
    cache = _tls_cache("exec")
    key = (handle, kind) + tuple(v for _, v in const_fields)
    ent = cache.get(key)
    if ent is None:
        if len(cache) >= _CACHE_CAP:
            cache.clear()
        d = _OpExec()
        d.kind = kind
        for name, value in const_fields:
            setattr(d, name, value)
        ent = (ctypes.c_int64(int(handle)), d, ctypes.byref(d))
        cache[key] = ent
    return ent


def _data_ptr(a: np.ndarray) -> int:
    # ~0.3 us cheaper per access than a.ctypes.data (which builds a
    # ctypeslib helper object every time) — measurable on the 1 KB path
    return a.__array_interface__["data"][0]


# Reusable output buffers for the ordered-callback hot path: a fresh
# multi-MB np.empty per op costs page faults that dominate large-message
# in-jit timings (glibc returns big frees to the kernel immediately) —
# the 16 MiB allreduce measured 0.859 GB/s/rank in-jit vs 0.935 at the
# transport before reuse.  Safe because callback results are COPIED
# into the XLA output buffer before the (ordered) callback returns; a
# caller that hands the result on as its own array passes reuse=False
# (the default).  Keyed by
# (comm, op, shape, dtype) so alternating shapes each keep a buffer
# instead of thrashing one slot; bounded like the descriptor cache
# (large buffers bound at 16 entries per thread).
_OUT_CACHE_CAP = 16


def _reused_out(handle, kind, shape, dtype):
    """(buffer, data pointer) for the per-(comm, op, shape) reusable
    output — the pointer is cached with the buffer so the hot path
    never pays the per-access np.ctypes traversal."""
    cache = _tls_cache("out")
    shape = tuple(shape)
    key = (handle, kind, shape, dtype)
    ent = cache.get(key)
    if ent is None:
        if len(cache) >= _OUT_CACHE_CAP:
            cache.clear()
        out = np.empty(shape, dtype)
        ent = (out, _data_ptr(out))
        cache[key] = ent
    return ent


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def _i64(v) -> ctypes.c_int64:
    return ctypes.c_int64(int(v))


# ---------------- ticketed non-blocking posting (plan execution) ----------
#
# The schedule-plan runner (runtime/planrt.py) posts descriptors on the
# progress engine WITHOUT waiting: hoisted receives start reading the
# wire during host compute, deferred sends stream without blocking the
# callback.  The engine drains FIFO, so post order is wire order — the
# exact contract the analysis-side equivalence prover verified.  Every
# ticket must be waited exactly once (the runner owns that bookkeeping,
# including keeping the numpy buffers alive until the wait returns).


def post_available() -> bool:
    """True when the loaded .so carries the ticketed posting entry."""
    return hasattr(get_lib(), "tpucomm_post")


def _post(handle, d: "_OpExec") -> int:
    lib = get_lib()
    ticket = lib.tpucomm_post(_i64(handle), ctypes.byref(d))
    if ticket == 0:
        _abort("Post", 1)
    return ticket


def post_send(handle, buf: np.ndarray, dest: int, tag: int):
    """Non-blocking send post.  Returns ``(ticket, keepalive)`` — hold
    ``keepalive`` (the contiguous payload and its descriptor) until
    :func:`wait_ticket` returns for this ticket.

    OWNERSHIP CONTRACT: the caller must own ``buf``'s storage for the
    ticket's whole lifetime.  A host-callback operand ndarray does NOT
    qualify — it aliases an XLA-owned buffer that is only valid for the
    callback's duration, and the progress thread reads the descriptor
    later.  The plan runner (runtime/planrt.py) satisfies this with
    pooled payload copies; drive this entry directly only with buffers
    you allocated."""
    buf = _contig(buf)
    d = _OpExec()
    d.kind = _K_SEND
    d.sbuf = _data_ptr(buf)
    d.snbytes = buf.nbytes
    d.peer = dest
    d.tag = tag
    return _post(handle, d), (buf, d)


def post_recv_into(handle, out: np.ndarray, source: int, tag: int):
    """Non-blocking recv post into a caller-owned buffer (same
    ownership contract as :func:`post_send`: ``out`` must stay alive
    and unread until :func:`wait_ticket` returns for the ticket).
    Returns ``(ticket, keepalive)``."""
    d = _OpExec()
    d.kind = _K_RECV
    d.rbuf = _data_ptr(out)
    d.rnbytes = out.nbytes
    d.peer2 = source
    d.tag = tag
    return _post(handle, d), d


def post_recv(handle, shape, dtype, source: int, tag: int):
    """Non-blocking recv post into a fresh buffer.  Returns
    ``(ticket, out, keepalive)``; ``out`` is valid after
    :func:`wait_ticket` returns 0 for the ticket."""
    out = np.empty(shape, dtype)
    ticket, d = post_recv_into(handle, out, source, tag)
    return ticket, out, d


def wait_ticket(handle, ticket: int) -> None:
    """Block until a posted op completes; aborts the process on a
    nonzero op result exactly like the synchronous entry points."""
    rc = get_lib().tpucomm_wait_ticket(_i64(handle), ctypes.c_int64(ticket))
    _check("WaitTicket", rc)


def split(handle, color: int, key: int):
    """Collective sub-communicator creation; None when color < 0."""
    h = get_lib().tpucomm_split(_i64(handle), int(color), int(key))
    if h == 0:
        _abort("Split", 1)
    return None if h == -1 else h


def dup(handle):
    h = get_lib().tpucomm_dup(_i64(handle))
    if h == 0:
        _abort("Dup", 1)
    return h


def comm_rank(handle) -> int:
    return get_lib().tpucomm_rank(_i64(handle))


def comm_size(handle) -> int:
    return get_lib().tpucomm_size(_i64(handle))


# Every function below takes/returns contiguous numpy arrays.  The hot
# ops ride the batched descriptor entry (one cached struct + one native
# call) when the loaded .so carries it; ``reuse=True`` additionally
# reuses the output buffer per (comm, op) — callback-path only (results
# are copied into XLA buffers before the callback returns, see
# _reused_out).

def send(handle, buf: np.ndarray, dest: int, tag: int):
    buf = _contig(buf)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_SEND)
        d.sbuf = _data_ptr(buf)
        d.snbytes = buf.nbytes
        d.peer = dest
        d.tag = tag
        _check("Send", _exec_fn(hc, ref))
        return
    rc = get_lib().tpucomm_send(
        _i64(handle), _ptr(buf), _i64(buf.nbytes), dest, tag
    )
    _check("Send", rc)


def recv(handle, shape, dtype, source: int, tag: int,
         reuse: bool = False) -> np.ndarray:
    if reuse:
        out, optr = _reused_out(handle, _K_RECV, shape, np.dtype(dtype))
    else:
        out = np.empty(shape, dtype)
        optr = None
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_RECV)
        d.rbuf = optr if optr is not None else _data_ptr(out)
        d.rnbytes = out.nbytes
        d.peer2 = source
        d.tag = tag
        _check("Recv", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_recv(
        _i64(handle), _ptr(out), _i64(out.nbytes), source, tag
    )
    _check("Recv", rc)
    return out


def recv_status(handle, shape, dtype, source: int, tag: int):
    """recv + (source, tag, byte count) from the transport frame header.

    zeros (not empty): a message shorter than the buffer fills only its
    prefix, and the tail must be deterministic, not heap garbage.
    """
    out = np.zeros(shape, dtype)
    src = ctypes.c_int32()
    tg = ctypes.c_int32()
    cnt = ctypes.c_int64()
    rc = get_lib().tpucomm_recv_status(
        _i64(handle), _ptr(out), _i64(out.nbytes), source, tag,
        ctypes.byref(src), ctypes.byref(tg), ctypes.byref(cnt),
    )
    _check("Recv", rc)
    return out, src.value, tg.value, cnt.value


def sendrecv_status(handle, sendbuf, recv_shape, recv_dtype, source, dest,
                    sendtag, recvtag):
    sendbuf = _contig(sendbuf)
    out = np.zeros(recv_shape, recv_dtype)  # deterministic short-message tail
    src = ctypes.c_int32()
    tg = ctypes.c_int32()
    cnt = ctypes.c_int64()
    rc = get_lib().tpucomm_sendrecv_status(
        _i64(handle), _ptr(sendbuf), _i64(sendbuf.nbytes), dest,
        _ptr(out), _i64(out.nbytes), source, sendtag, recvtag,
        ctypes.byref(src), ctypes.byref(tg), ctypes.byref(cnt),
    )
    _check("Sendrecv", rc)
    return out, src.value, tg.value, cnt.value


def sendrecv(handle, sendbuf, recv_shape, recv_dtype, source, dest, tag,
             reuse: bool = False):
    sendbuf = _contig(sendbuf)
    optr = None
    if reuse:
        out, optr = _reused_out(handle, _K_SENDRECV, recv_shape,
                                np.dtype(recv_dtype))
        if out is sendbuf:  # eager chain fed the cached out back in
            out, optr = np.empty(recv_shape, recv_dtype), None
    else:
        out = np.empty(recv_shape, recv_dtype)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_SENDRECV)
        d.sbuf = _data_ptr(sendbuf)
        d.snbytes = sendbuf.nbytes
        d.peer = dest
        d.rbuf = optr if optr is not None else _data_ptr(out)
        d.rnbytes = out.nbytes
        d.peer2 = source
        d.tag = tag
        d.tag2 = tag
        _check("Sendrecv", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_sendrecv(
        _i64(handle), _ptr(sendbuf), _i64(sendbuf.nbytes), dest,
        _ptr(out), _i64(out.nbytes), source, tag,
    )
    _check("Sendrecv", rc)
    return out


def shift2(handle, buf, lo: int, hi: int, tag: int) -> np.ndarray:
    """Bidirectional neighbor exchange: ``buf`` is the (2, ...) stack
    [to_lo, to_hi]; returns [from_lo, from_hi] (walls = passthrough)."""
    buf = _contig(buf)
    out = np.empty_like(buf)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_SHIFT2)
        d.sbuf = _data_ptr(buf)
        d.rbuf = _data_ptr(out)
        d.snbytes = buf.nbytes // 2
        d.peer = int(lo)
        d.peer2 = int(hi)
        d.tag = int(tag)
        _check("Shift2", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_shift2(
        _i64(handle), _ptr(buf), _ptr(out), _i64(buf.nbytes // 2),
        int(lo), int(hi), int(tag),
    )
    _check("Shift2", rc)
    return out


def barrier(handle):
    if _live_boundary is not None:
        _live_boundary(handle)
    if _exec_fn is not None:
        hc, _, ref = _exec_desc(handle, _K_BARRIER)
        _check("Barrier", _exec_fn(hc, ref))
        return
    _check("Barrier", get_lib().tpucomm_barrier(_i64(handle)))


def bcast(handle, buf, root) -> np.ndarray:
    if _live_boundary is not None:
        _live_boundary(handle)
    out = _contig(buf).copy()
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_BCAST, ("peer", root))
        d.rbuf = _data_ptr(out)
        d.rnbytes = out.nbytes
        _check("Bcast", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_bcast(_i64(handle), _ptr(out), _i64(out.nbytes), root)
    _check("Bcast", rc)
    return out


def allreduce_raw(handle, buf: np.ndarray, out: np.ndarray, dtype_code: int,
                  op_code: int, algo: Optional[int] = None):
    """Zero-marshalling allreduce over pre-shaped contiguous buffers —
    the tuner/benchmark inner loop.  ``algo`` is a TpuCollAlgo code
    forced for this call (None/0 = engine selection); forcing against a
    pre-engine .so raises — silently running the default schedule under
    a forced label would poison equivalence tests and tuning data.

    The ICI data-plane leg (``topo/_ici_leg.py``) intercepts BEFORE
    both native paths: an eligible hierarchical f32 SUM on a topology
    comm runs its intra-island phase over the Pallas ring instead of
    the native shm/TCP legs (quiet fallthrough otherwise — the knob
    parser is the loud guard)."""
    if _live_boundary is not None:
        _live_boundary(handle)
    if _ici_leg_hook(handle, buf, out, dtype_code, op_code, algo):
        return
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_ALLREDUCE, ("dtype", dtype_code),
                                ("rop", op_code), ("algo", int(algo or 0)))
        d.sbuf = _data_ptr(buf)
        d.rbuf = _data_ptr(out)
        d.count = buf.size
        _check("Allreduce", _exec_fn(hc, ref))
        return
    lib = get_lib()
    if algo and not hasattr(lib, "tpucomm_allreduce_algo"):
        raise RuntimeError(
            "forced collective algorithms need a native library with the "
            "algorithm engine (tpucomm_allreduce_algo); rebuild native/"
        )
    if algo:
        rc = lib.tpucomm_allreduce_algo(
            _i64(handle), _ptr(buf), _ptr(out), _i64(buf.size),
            dtype_code, op_code, int(algo),
        )
    else:
        rc = lib.tpucomm_allreduce(
            _i64(handle), _ptr(buf), _ptr(out), _i64(buf.size),
            dtype_code, op_code,
        )
    _check("Allreduce", rc)


def allreduce(handle, buf, op_code: int, out: Optional[np.ndarray] = None,
              algo: Optional[int] = None, reuse: bool = False) -> np.ndarray:
    """``out`` lets hot loops reuse the result buffer: a fresh multi-MB
    allocation per call costs page faults that dominate large-message
    timings (glibc returns big frees to the kernel immediately).
    ``reuse=True`` does the same per (comm, op, shape) automatically —
    safe on the ordered-callback path only (see _reused_out)."""
    buf = _contig(buf)
    if out is None and reuse and _exec_fn is not None:
        # fused fast path for the hottest op: one (thread-local) dict
        # hit resolves the handle, the fully-populated descriptor (out
        # pointer and count baked in), AND the reusable output buffer —
        # the steady state pays one input pointer fetch, one field
        # store, and one native call
        cache = _tls_cache("ar")
        key = (handle, buf.dtype.num, buf.shape, op_code, algo or 0)
        ent = cache.get(key)
        if ent is None:
            if len(cache) >= _OUT_CACHE_CAP:
                cache.clear()
            res = np.empty_like(buf)
            d = _OpExec()
            d.kind = _K_ALLREDUCE
            d.dtype = _dtypes.wire_code(buf.dtype)
            d.rop = op_code
            d.algo = int(algo or 0)
            d.rbuf = _data_ptr(res)
            d.count = buf.size
            ent = (ctypes.c_int64(int(handle)), ctypes.byref(d), res, d)
            cache[key] = ent
        hc, ref, res = ent[0], ent[1], ent[2]
        if res is not buf:
            # the fused path returns before allreduce_raw, so it pays
            # the boundary hook itself (exactly once per collective)
            if _live_boundary is not None:
                _live_boundary(handle)
            ent[3].sbuf = _data_ptr(buf)
            _check("Allreduce", _exec_fn(hc, ref))
            return res
    if out is None and reuse:
        cached, _ = _reused_out(handle, _K_ALLREDUCE, buf.shape, buf.dtype)
        if cached is not buf:
            out = cached
    if (out is None or out.shape != buf.shape or out.dtype != buf.dtype
            or not out.flags.c_contiguous or out is buf):
        out = np.empty_like(buf)
    allreduce_raw(handle, buf, out, _dtypes.wire_code(buf.dtype), op_code,
                  algo=algo)
    return out


def reduce(handle, buf, op_code: int, root: int,
           reuse: bool = False) -> np.ndarray:
    if _live_boundary is not None:
        _live_boundary(handle)
    buf = _contig(buf)
    optr = None
    if reuse:
        out, optr = _reused_out(handle, _K_REDUCE, buf.shape, buf.dtype)
        if out is buf:
            out, optr = np.empty_like(buf), None
    else:
        out = np.empty_like(buf)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(
            handle, _K_REDUCE, ("dtype", _dtypes.wire_code(buf.dtype)),
            ("rop", op_code), ("peer", root))
        d.sbuf = _data_ptr(buf)
        d.rbuf = optr if optr is not None else _data_ptr(out)
        d.count = buf.size
        _check("Reduce", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_reduce(
        _i64(handle), _ptr(buf), _ptr(out), _i64(buf.size),
        _dtypes.wire_code(buf.dtype), op_code, root,
    )
    _check("Reduce", rc)
    return out


def scan(handle, buf, op_code: int, reuse: bool = False) -> np.ndarray:
    if _live_boundary is not None:
        _live_boundary(handle)
    buf = _contig(buf)
    optr = None
    if reuse:
        out, optr = _reused_out(handle, _K_SCAN, buf.shape, buf.dtype)
        if out is buf:
            out, optr = np.empty_like(buf), None
    else:
        out = np.empty_like(buf)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(
            handle, _K_SCAN, ("dtype", _dtypes.wire_code(buf.dtype)),
            ("rop", op_code))
        d.sbuf = _data_ptr(buf)
        d.rbuf = optr if optr is not None else _data_ptr(out)
        d.count = buf.size
        _check("Scan", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_scan(
        _i64(handle), _ptr(buf), _ptr(out), _i64(buf.size),
        _dtypes.wire_code(buf.dtype), op_code,
    )
    _check("Scan", rc)
    return out


def allgather_raw(handle, buf: np.ndarray, out: np.ndarray,
                  algo: Optional[int] = None):
    """Zero-marshalling allgather (tuner/benchmark inner loop); ``algo``
    as in :func:`allreduce_raw` (raises on a pre-engine .so)."""
    if _live_boundary is not None:
        _live_boundary(handle)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_ALLGATHER,
                                ("algo", int(algo or 0)))
        d.sbuf = _data_ptr(buf)
        d.snbytes = buf.nbytes
        d.rbuf = _data_ptr(out)
        _check("Allgather", _exec_fn(hc, ref))
        return
    lib = get_lib()
    if algo and not hasattr(lib, "tpucomm_allgather_algo"):
        raise RuntimeError(
            "forced collective algorithms need a native library with the "
            "algorithm engine (tpucomm_allgather_algo); rebuild native/"
        )
    if algo:
        rc = lib.tpucomm_allgather_algo(
            _i64(handle), _ptr(buf), _i64(buf.nbytes), _ptr(out), int(algo)
        )
    else:
        rc = lib.tpucomm_allgather(
            _i64(handle), _ptr(buf), _i64(buf.nbytes), _ptr(out)
        )
    _check("Allgather", rc)


def allgather(handle, buf, size: int, algo: Optional[int] = None,
              reuse: bool = False) -> np.ndarray:
    buf = _contig(buf)
    if reuse:
        out, optr = _reused_out(handle, _K_ALLGATHER, (size,) + buf.shape,
                                buf.dtype)
        if _exec_fn is not None:
            # returns before allgather_raw: pay the boundary hook here
            if _live_boundary is not None:
                _live_boundary(handle)
            hc, d, ref = _exec_desc(handle, _K_ALLGATHER,
                                    ("algo", int(algo or 0)))
            d.sbuf = _data_ptr(buf)
            d.snbytes = buf.nbytes
            d.rbuf = optr
            _check("Allgather", _exec_fn(hc, ref))
            return out
    else:
        out = np.empty((size,) + buf.shape, buf.dtype)
    allgather_raw(handle, buf, out, algo=algo)
    return out


def gather(handle, buf, size: int, root: int, rank: int) -> np.ndarray:
    if _live_boundary is not None:
        _live_boundary(handle)
    buf = _contig(buf)
    # non-root only sends (the native call ignores recvbuf off-root) and
    # gets its input back — the exact reference contract
    # (gather.py:213-226 there)
    out = np.empty((size,) + buf.shape, buf.dtype) if rank == root else buf
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_GATHER)
        d.sbuf = _data_ptr(buf)
        d.snbytes = buf.nbytes
        d.rbuf = _data_ptr(out)
        d.peer = root
        _check("Gather", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_gather(
        _i64(handle), _ptr(buf), _i64(buf.nbytes), _ptr(out), root
    )
    _check("Gather", rc)
    return out


def scatter(handle, buf, root: int) -> np.ndarray:
    if _live_boundary is not None:
        _live_boundary(handle)
    buf = _contig(buf)
    out = np.empty(buf.shape[1:], buf.dtype)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_SCATTER)
        d.sbuf = _data_ptr(buf)
        d.rbuf = _data_ptr(out)
        d.rnbytes = out.nbytes
        d.peer = root
        _check("Scatter", _exec_fn(hc, ref))
        return out
    rc = get_lib().tpucomm_scatter(
        _i64(handle), _ptr(buf), _ptr(out), _i64(out.nbytes), root
    )
    _check("Scatter", rc)
    return out


def alltoall_raw(handle, buf: np.ndarray, out: np.ndarray,
                 algo: Optional[int] = None,
                 dtype_code: Optional[int] = None):
    """Zero-marshalling alltoall (tuner/benchmark inner loop); ``algo``
    as in :func:`allreduce_raw` (raises on a pre-engine .so).

    The typed entry (per-chunk element count + dtype) is what makes the
    quantized/hierarchical schedules (qalltoall/halltoall/hqalltoall)
    resolvable — the legacy byte-chunk call always runs the exact
    exchange.  ``dtype_code`` overrides the wire code derived from
    ``buf.dtype`` (bf16 payloads carried as uint16 bit views).
    """
    if _live_boundary is not None:
        _live_boundary(handle)
    count = buf.size // buf.shape[0]
    if dtype_code is None:
        dtype_code = _dtypes.wire_code(buf.dtype)
    if _exec_fn is not None:
        hc, d, ref = _exec_desc(handle, _K_ALLTOALL,
                                ("dtype", int(dtype_code)),
                                ("algo", int(algo or 0)))
        d.sbuf = _data_ptr(buf)
        d.rbuf = _data_ptr(out)
        d.count = count
        _check("Alltoall", _exec_fn(hc, ref))
        return
    lib = get_lib()
    if not hasattr(lib, "tpucomm_alltoall_algo"):
        if algo:
            raise RuntimeError(
                "forced collective algorithms need a native library with "
                "the algorithm engine (tpucomm_alltoall_algo); rebuild "
                "native/"
            )
        rc = lib.tpucomm_alltoall(
            _i64(handle), _ptr(buf), _ptr(out),
            _i64(buf.nbytes // buf.shape[0])
        )
    else:
        rc = lib.tpucomm_alltoall_algo(
            _i64(handle), _ptr(buf), _ptr(out), _i64(count),
            int(dtype_code), int(algo or 0)
        )
    _check("Alltoall", rc)


def alltoall(handle, buf, algo: Optional[int] = None) -> np.ndarray:
    buf = _contig(buf)
    out = np.empty_like(buf)
    alltoall_raw(handle, buf, out, algo=algo)
    return out
