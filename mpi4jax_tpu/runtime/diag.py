"""Layer-by-layer environment diagnostics.

    python -m mpi4jax_tpu.runtime.diag [--device] [--json]

Checks, in dependency order, each seam a job can fail on — native
build, transport loopback, launcher, and (with ``--device``) the
accelerator claim / compile / host-callback capabilities — and prints
one PASS/FAIL line per check (or one JSON object with ``--json``).
The reference has no analog; its failure modes surface as mpirun
aborts.  Device checks run in subprocesses with timeouts, so a probe
that hangs is reported, not inherited; the diag process itself never
touches the accelerator (one process holds a chip at a time).

Exit code: number of failed checks.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_snippet(code: str, timeout: int, env_extra=None):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", REPO)
    if env_extra:
        env.update(env_extra)
    try:
        res = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=timeout, env=env,
        )
        return res.returncode, res.stdout, res.stderr
    except subprocess.TimeoutExpired as err:
        return None, err.stdout or "", err.stderr or ""


def check_native_build():
    """The C++ transport builds/loads and reports its symbols."""
    from . import bridge

    lib = bridge.get_lib()
    missing = [s for s in ("tpucomm_init", "tpucomm_allreduce",
                           "tpucomm_sendrecv", "tpucomm_split")
               if not hasattr(lib, s)]
    return not missing, f"missing symbols: {missing}" if missing else "loaded"


def check_ffi():
    """XLA FFI handlers are exported (cpu fast path)."""
    from ..utils import config

    if config.ffi_disabled():
        # a deliberate kill switch is a configuration, not a failure —
        # report healthy with the reason (the callback path serves)
        return True, "disabled by MPI4JAX_TPU_DISABLE_FFI (callback path)"
    from . import bridge

    return bridge.ffi_available(), "tpucomm_ffi handlers"


def check_coll_algo_engine():
    """The collective algorithm engine resolves a decision table, and
    the quantized wire formats (qring/qrd) are available and sane: the
    native int8+scales codec round-trips a random payload within the
    per-block error bound (|err| <= blockwise absmax / 127)."""
    import numpy as np

    from .. import tune
    from . import bridge

    info = tune.describe()
    picks = info["picks"]
    detail = " ".join(
        f"{op}@1KB={picks[op]['1KB']} @16MB={picks[op]['16MB']}"
        for op in ("allreduce", "allgather")
    )
    detail += " [" + "+".join(info["sources"]) + "]"
    # the engine must agree with itself: every pick is a real algorithm
    ok = all(
        picks[op][k] in tune.TRACE_ALGOS
        for op in picks for k in picks[op]
    )
    if not bridge.quant_available():
        # a stale prebuilt library keeps every exact collective working
        # (same tolerance as obs: unobserved, not broken) — report the
        # missing capability without failing the check
        return ok, detail + " quant=UNAVAILABLE (native library " \
            "predates the quantized engine; rebuild native/ to enable " \
            "qring/qrd)"
    from ..ops import quantized as q

    # wire-format loopback: pack through the NATIVE codec, unpack,
    # assert the per-block quantization error bound, and cross-check
    # the packed bytes against the documented numpy reference
    rng = np.random.RandomState(3)
    x = (rng.randn(1000) * 5).astype(np.float32)
    packed = bridge.quant_pack(x)
    if packed.size != bridge.quant_packed_bytes(x.size):
        return False, detail + " quant packed-size mismatch"
    scales, codes = q.quant_pack_ref(x)
    ref = np.concatenate([scales.view(np.int8), codes])
    if not np.array_equal(packed, ref):
        return False, detail + " quant codec diverges from the " \
            "documented reference (native vs quant_pack_ref)"
    back = bridge.quant_unpack(packed, x.size, np.float32)
    nb = (x.size + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK
    for b in range(nb):
        blk = slice(b * q.QUANT_BLOCK, min(x.size, (b + 1) * q.QUANT_BLOCK))
        bound = np.max(np.abs(x[blk])) / 127.0 * 0.5 + 1e-9
        if np.max(np.abs(back[blk] - x[blk])) > bound:
            return False, detail + f" quant error bound violated in " \
                f"block {b}"
    ratio = x.nbytes / packed.nbytes
    detail += f" quant=qring,qrd (codec round-trip ok, {ratio:.2f}x wire)"
    # the alltoall family (MoE expert exchange): the typed engine entry
    # is what makes qalltoall/halltoall/hqalltoall resolvable; the
    # quantized members additionally need the codec probed above
    if hasattr(bridge.get_lib(), "tpucomm_alltoall_algo"):
        fam = sorted(tune.A2A_ALGOS)
        ok = ok and all(
            tune._check_algo(a, "alltoall") == a for a in fam)
        detail += " alltoall=" + ",".join(fam)
        detail += (f" (default@1KB={tune.get_algorithm('alltoall', 1024)}"
                   f" @16MB={tune.get_algorithm('alltoall', 16 << 20)})")
    else:
        detail += " alltoall=EXACT-ONLY (library predates the typed " \
            "alltoall engine entry; rebuild native/)"
    return ok, detail


def check_observability(port):
    """The structured recorder end to end, no sockets: a size-1 native
    comm records loopback ops into the event ring, the recording shows
    them in ``obs.stats()``, and the exported trace validates against
    the Chrome trace-event schema."""
    import ctypes

    import numpy as np

    from .. import obs
    from ..obs import _native
    from . import bridge

    lib = bridge.get_lib()
    if not _native.available(lib):
        return False, ("native library predates the event ring "
                       "(no tpucomm_obs_enable); rebuild native/")
    h = lib.tpucomm_init(0, 1, int(port), b"")
    if h == 0:
        return False, "size-1 comm init failed"
    try:
        obs.start(lib=lib, rank=0, size=1)
        x = np.arange(16.0)
        bridge.send(h, x, 0, 7)           # self-delivery loopback
        got = bridge.recv(h, x.shape, x.dtype, 0, 7)
        if not np.allclose(got, x):
            return False, "loopback payload mismatch"
        bridge.allreduce(h, x, 0)
        stats = obs.stats()
        ops = {row["op"] for row in stats["per_op"]}
        if not {"Send", "Recv", "Allreduce"} <= ops:
            return False, f"recorded ops {sorted(ops)} missing Send/Recv/" \
                          "Allreduce"
        # every native row must carry the dispatch-phase split (the
        # async progress engine's queue-time vs wire-time attribution)
        native_rows = [r for r in stats["per_op"] if r["src"] == "native"]
        if not native_rows or any("dispatch_frac" not in r
                                  for r in native_rows):
            return False, "native stats rows missing dispatch_frac"
        count = sum(row["count"] for row in stats["per_op"])
        trace = obs.merge_parts([{
            "rank": 0, "size": 1, "events": obs.events(),
            "dropped": obs.dropped(),
        }])
        errors = obs.validate_chrome_trace(trace)
        if errors:
            return False, f"trace schema errors: {errors[:3]}"
        spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        if not any("dispatch_us" in (e.get("args") or {}) for e in spans):
            return False, "trace spans missing dispatch_us"
        from ..utils import config as _config

        engine = ("on" if _config.progress_thread_enabled() else "off")
        return True, (f"{count} loopback events recorded, stats ops "
                      f"{sorted(ops)}, dispatch split present, trace "
                      f"validates ({obs.default_capacity_events()}-event "
                      f"ring; progress engine {engine}, coalesce "
                      f"{_config.coalesce_bytes()} B)")
    finally:
        obs.stop()
        lib.tpucomm_finalize(ctypes.c_int64(h))


def check_transport_loopback(port):
    """2-rank world job over the real launcher + TCP transport."""
    import tempfile

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import mpi4jax_tpu as m\n"
        "import numpy as np, jax.numpy as jnp\n"
        "c = m.get_default_comm()\n"
        "out = m.allreduce(jnp.arange(4.0), op=m.SUM, comm=c)\n"
        "assert np.allclose(np.asarray(out), np.arange(4.0) * 2), out\n"
        "got = m.sendrecv(jnp.arange(3.0) + c.rank(), shift=1, comm=c)\n"
        "assert np.allclose(np.asarray(got), np.arange(3.0) + 1 - c.rank())\n"
        "from mpi4jax_tpu.runtime import bridge\n"
        "act, slot, ring = bridge.shm_info(c.handle)\n"
        # the transport-floor state: on / off / unavailable(<reason>);
        # a pre-uring .so (no status symbol) reads as unavailable, never
        # as a misparsed guess
        "us = bridge.uring_status()\n"
        "if us is None:\n"
        "    us = 'unavailable(native library predates the uring backend)'\n"
        "print('loopback-ok shm=%%d ring_kb=%%d algo16mb=%%s uring=%%s' %% "
        "(act, ring // 1024, c.coll_algo('allreduce', 16 << 20), us))\n"
        % REPO
    )
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag.py", delete=False
    ) as f:
        f.write(code)
        prog = f.name
    try:
        res = subprocess.run(
            [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "2",
             "--port", str(port), prog],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        )
        rc, out, err = res.returncode, res.stdout, res.stderr
    except subprocess.TimeoutExpired:
        return False, "timed out (deadlock or port conflict?)"
    finally:
        os.unlink(prog)
    ok = rc == 0 and out.count("loopback-ok") == 2
    if not ok:
        return False, (err.strip() or out)[-200:]
    detail = "2-rank allreduce+sendrecv"
    for line in out.splitlines():
        if line.startswith("loopback-ok"):
            detail += " [" + line[len("loopback-ok "):] + "]"
            break
    return True, detail


def check_failure_detection(port):
    """Transport deadlines + teardown: a deterministically hung rank is
    detected within the configured deadline on a loopback pair, and the
    resolved timeout knobs are reported."""
    import tempfile

    from ..utils import config

    cfg_t = config.transport_timeout_s()
    cfg_c = config.connect_timeout_s()
    knobs = (f"timeout_s={cfg_t:g}" if cfg_t else "timeout_s=off(0)") \
        + f" connect_s={cfg_c:g}"

    deadline_s = 3.0
    # bridge-level ranks (no jax import): rank 1's first recv hangs via
    # the injector; rank 0's recv from it must trip the deadline and
    # name the stuck peer, and the launcher must reap the hung rank
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "c = transport.get_world_comm()\n"
        "h = c.handle\n"
        "if c.rank() == 0:\n"
        "    bridge.send(h, np.arange(4.0), 1, 7)\n"
        "    bridge.recv(h, (4,), np.float64, 1, 7)\n"
        "    print('UNREACHABLE', flush=True)\n"
        "else:\n"
        "    bridge.recv(h, (4,), np.float64, 0, 7)\n"
        % REPO
    )
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag_fault.py", delete=False
    ) as f:
        f.write(code)
        prog = f.name
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "MPI4JAX_TPU_TIMEOUT_S": str(deadline_s),
        "MPI4JAX_TPU_DISABLE_SHM": "1",
        "MPI4JAX_TPU_FAULT": "rank=1,point=recv,after=0,action=hang",
    }
    t0 = time.perf_counter()
    # own process group: if detection regresses, killpg reaps the
    # launcher AND its (deliberately hung-forever) ranks — a plain
    # subprocess.run timeout would SIGKILL only the launcher and leak
    # the injected hang as a permanent orphan
    import signal as _signal

    proc = subprocess.Popen(
        [sys.executable, "-m", "mpi4jax_tpu.runtime.launch", "-n", "2",
         "--port", str(port), prog],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO, start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except (OSError, ProcessLookupError):
            proc.kill()
        proc.communicate()
        return False, (f"{knobs}; injected recv-hang NOT detected "
                       f"within 60 s (deadline {deadline_s:g} s)")
    finally:
        os.unlink(prog)
    dt = time.perf_counter() - t0
    detected = (
        proc.returncode != 0
        and "UNREACHABLE" not in out
        and "timed out" in err
        and "from 1" in err  # the stuck peer is named
    )
    if not detected:
        return False, (knobs + "; " + (err.strip() or out.strip())[-180:])
    return True, (f"{knobs}; injected recv-hang detected in {dt:.1f}s "
                  f"({deadline_s:g}s deadline, stuck peer named)")


def check_self_healing(port):
    """The link-layer self-healing path end to end on a loopback 2-rank
    job: a transient ``reset`` is injected mid-run (MPI4JAX_TPU_FAULT),
    the armed link layer (MPI4JAX_TPU_RETRY) reconnects within ONE
    backoff window (the recovery line says ``[attempt 1/...]``),
    deliberate replay overlap (RETRY_REPLAY_SLACK) proves the seq dedup
    actually drops duplicates, both ranks finish with bit-identical
    digests, and the reconnect + dup-dropped counters surface through
    ``obs.stats()['self_healing']``."""
    import re
    import tempfile

    from ..utils import config
    from . import bridge

    if not hasattr(bridge.get_lib(), "tpucomm_link_counters"):
        return True, ("UNAVAILABLE: native library predates the "
                      "self-healing link layer (no tpucomm_link_counters); "
                      "rebuild native/ to enable it")
    backoff_ms = 100.0
    knobs = (f"retry={config.retry_budget() or 4} "
             f"backoff_ms={backoff_ms:g} crc={config.wire_crc_mode()}")
    code = (
        "import sys, types, os; sys.path.insert(0, %r)\n"
        # parent-package shim: bridge-level ranks must work even where
        # the package's jax gate blocks the full import
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "import numpy as np\n"
        "from mpi4jax_tpu import obs\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "c = transport.get_world_comm()\n"
        "h = c.handle\n"
        "obs.start(lib=bridge.get_lib(), rank=c.rank(), size=c.size())\n"
        "x = np.arange(256.0) + c.rank()\n"
        "digest = 0.0\n"
        "for it in range(12):\n"
        "    if c.rank() == 0:\n"
        "        bridge.send(h, x, 1, it)\n"
        "        got = bridge.recv(h, x.shape, x.dtype, 1, it)\n"
        "    else:\n"
        "        got = bridge.recv(h, x.shape, x.dtype, 0, it)\n"
        "        bridge.send(h, x, 0, it)\n"
        "    assert np.allclose(got, np.arange(256.0) + (1 - c.rank()))\n"
        "    out = bridge.allreduce(h, x, 2)\n"
        "    digest += float(out.sum())\n"
        "sh = obs.stats().get('self_healing', {})\n"
        # one write() so the two ranks' report lines can't interleave
        # in the launcher's stdout pump
        "sys.stdout.write('diag_heal %%d %%r %%d %%d\\n' %% (\n"
        "    c.rank(), digest,\n"
        "    sh.get('reconnects', 0), sh.get('dup_dropped', 0)))\n"
        "sys.stdout.flush()\n"
        % (REPO, REPO)
    )
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag_heal.py", delete=False
    ) as f:
        f.write(code)
        prog = f.name
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "MPI4JAX_TPU_DISABLE_SHM": "1",
        "MPI4JAX_TPU_TIMEOUT_S": "30",
        "MPI4JAX_TPU_RETRY": "4",
        "MPI4JAX_TPU_RETRY_BACKOFF_MS": f"{backoff_ms:g}",
        # deliberate replay overlap: the receiver must DROP the
        # duplicates, proving the seq dedup (not just the reconnect)
        "MPI4JAX_TPU_RETRY_REPLAY_SLACK": "1",
        "MPI4JAX_TPU_FAULT": "rank=0,point=send,after=5,action=reset",
    }
    t0 = time.perf_counter()
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "2", "--port", str(port), prog],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return False, f"{knobs}; healing run hung (no reconnect?)"
    finally:
        os.unlink(prog)
    dt = time.perf_counter() - t0
    lines = {
        int(m.group(1)): (m.group(2), int(m.group(3)), int(m.group(4)))
        for m in re.finditer(
            r"diag_heal (\d+) (\S+) (\d+) (\d+)", res.stdout)
    }
    # recovery within one backoff window == the link came back on the
    # FIRST reconnect attempt (each later attempt waits another window)
    first_window = re.search(
        r"self-heal: link to r\d+ recovered .*\[attempt 1/", res.stderr)
    ok = (
        res.returncode == 0
        and len(lines) == 2
        and lines[0][0] == lines[1][0]          # bit-identical digests
        and "fault injection: reset" in res.stderr
        and first_window is not None
        and all(v[1] >= 1 for v in lines.values())   # reconnects in stats
        and any(v[2] >= 1 for v in lines.values())   # dups dropped in stats
        and "healed in-place" in res.stderr     # launcher post-mortem
    )
    if not ok:
        tail = (res.stderr.strip() or res.stdout.strip())[-220:]
        return False, f"{knobs}; healing run failed: {tail}"
    return True, (f"{knobs}; injected link reset healed on attempt 1 "
                  f"(one backoff window), digests bit-identical, "
                  f"reconnects={lines[0][1]}+{lines[1][1]} "
                  f"dup_dropped={lines[0][2]}+{lines[1][2]} via "
                  f"obs.stats() in {dt:.1f}s")


def check_elasticity(port):
    """Elastic recovery end to end on a loopback 3-rank job: rank 1 is
    deterministically killed mid-run (MPI4JAX_TPU_FAULT), the survivors
    shrink to np=2 through the launcher's generation protocol and the
    native tpucomm_shrink bootstrap, resume from the last committed
    checkpoint, and the job exits 0 with bit-identical results."""
    import tempfile

    from ..utils import config
    from . import bridge

    if not bridge.shrink_available():
        return True, ("UNAVAILABLE: native library predates elastic "
                      "recovery (no tpucomm_shrink); rebuild native/ "
                      "to enable it")
    knobs = (f"policy={config.elastic_policy()} "
             f"grace_s={config.elastic_grace_s():g}")
    code = (
        "import sys, types, os; sys.path.insert(0, %r)\n"
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "import hashlib\n"
        "import numpy as np\n"
        "from mpi4jax_tpu.elastic import training\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "def step_fn(state, step, comm):\n"
        "    g = bridge.allreduce(comm.handle,\n"
        "                         np.cos(np.arange(8) * (step + 1)), 2)\n"
        "    return state - 0.1 * g\n"
        "comm = transport.get_world_comm()\n"
        "state = training.run(step_fn, np.zeros(8), steps=8,\n"
        "                     save_every=2)\n"
        "d = hashlib.sha256(state.tobytes()).hexdigest()[:16]\n"
        "print('diag_elastic digest', d, flush=True)\n"
        % (REPO, REPO)
    )
    with tempfile.TemporaryDirectory(prefix="m4j_diag_elastic_") as td:
        prog = os.path.join(td, "prog.py")
        with open(prog, "w") as f:
            f.write(code)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "MPI4JAX_TPU_DISABLE_SHM": "1",
            "MPI4JAX_TPU_TIMEOUT_S": "6",
            "MPI4JAX_TPU_CKPT_DIR": os.path.join(td, "ckpt"),
            "MPI4JAX_TPU_FAULT": "rank=1,point=send,after=10,action=exit",
        }
        t0 = time.perf_counter()
        # the launcher runs as a FILE (not -m): the rank program uses
        # the parent-package shim so the whole check works even where
        # the package's jax gate blocks imports, and -m would defeat
        # that by importing the package in the launcher process
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "3", "--port", str(port), "--elastic", prog],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
    dt = time.perf_counter() - t0
    import re

    digests = set(re.findall(r"diag_elastic digest (\w+)", res.stdout))
    ok = (
        res.returncode == 0
        and "completed after recovery" in res.stderr
        and "generation 1" in res.stderr
        and len(digests) == 1  # both survivors, identical state
        and res.stdout.count("diag_elastic digest") == 2
    )
    if not ok:
        tail = (res.stderr.strip() or res.stdout.strip())[-220:]
        return False, f"{knobs}; recovery run failed: {tail}"
    return True, (f"{knobs}; injected rank death recovered np=3->np=2 "
                  f"in {dt:.1f}s (exit 0, survivors bit-identical, "
                  "resume from committed checkpoint)")


def check_serving(port):
    """Serving v2 end to end on a loopback 3-rank job under forced
    disaggregation (docs/serving.md): roles derive to frontend=r0 /
    prefill=r1 / decode=r2, one request is prefilled on rank 1, its KV
    shipped to rank 2 and decoded there, the KV wire bytes show up in
    each worker's ``obs.stats()`` tier rows, and a second submit over
    the queue cap is shed with a loud verdict instead of admitted."""
    import tempfile

    from ..utils import config

    knobs = (f"roles={config.serve_roles()} "
             f"max_batch={config.serve_max_batch()} "
             f"queue_cap={config.serve_queue_cap()} "
             f"slo_ms={config.serve_slo_ms():g}")
    code = (
        "import sys, types, os; sys.path.insert(0, %r)\n"
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "from mpi4jax_tpu import obs, serving\n"
        "from mpi4jax_tpu.runtime import transport\n"
        "comm = transport.get_world_comm()\n"
        "_ = comm.handle\n"
        "obs.start(rank=comm.rank(), size=comm.size())\n"
        "adapter = serving.ToyAdapter()\n"
        "if comm.rank() != 0:\n"
        "    roles = serving.serve_worker(comm, adapter,\n"
        "                                 roles_mode='disagg')\n"
        "    st = obs.stats()\n"
        "    kv = st.get('tier_bytes', {}).get('kv', 0)\n"
        "    phases = sorted({r['phase'] for r in st['per_op']\n"
        "                     if 'phase' in r})\n"
        "    msg = ' '.join(['diag_serving worker', str(comm.rank()),\n"
        "                    roles.role_of(comm.rank()), str(kv),\n"
        "                    ','.join(phases)])\n"
        "    sys.stdout.write(msg + chr(10)); sys.stdout.flush()\n"
        "else:\n"
        "    server = serving.Server(comm, adapter, max_batch=2,\n"
        "                            chunk_tokens=4, queue_cap=1,\n"
        "                            roles_mode='disagg')\n"
        "    ok_v = server.submit([3, 1, 4, 1, 5], max_new=4)\n"
        "    assert ok_v.admitted, ok_v.reason\n"
        "    shed_v = server.submit([2, 7], max_new=4)\n"
        "    assert not shed_v.admitted, 'over-cap submit was admitted'\n"
        "    server.run_until_drained()\n"
        "    server.stop()\n"
        "    req = server.completed[0]\n"
        "    msg = ' '.join(['diag_serving frontend', server.roles.mode,\n"
        "                    str(len(server.completed)),\n"
        "                    str(len(req.generated)),\n"
        "                    str(server.admission.shed), shed_v.reason])\n"
        "    sys.stdout.write(msg + chr(10)); sys.stdout.flush()\n"
        % (REPO, REPO)
    )
    with tempfile.TemporaryDirectory(prefix="m4j_diag_serving_") as td:
        prog = os.path.join(td, "prog.py")
        with open(prog, "w") as f:
            f.write(code)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "MPI4JAX_TPU_DISABLE_SHM": "1",
            "MPI4JAX_TPU_TIMEOUT_S": "8",
        }
        t0 = time.perf_counter()
        # launcher as a FILE (not -m) for the same reason as
        # check_elasticity: the rank program's parent-package shim must
        # survive environments where the package's jax gate blocks
        # imports
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "3", "--port", str(port), prog],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
    dt = time.perf_counter() - t0
    import re

    fe = re.search(r"diag_serving frontend (\S+) (\d+) (\d+) (\d+) (.+)",
                   res.stdout)
    workers = {
        int(m.group(1)): (m.group(2), int(m.group(3)), m.group(4))
        for m in re.finditer(
            r"diag_serving worker (\d+) (\S+) (\d+) (\S*)", res.stdout)
    }
    ok = (
        res.returncode == 0
        and fe is not None
        and fe.group(1) == "disagg"
        and int(fe.group(2)) == 1          # the admitted request drained
        and int(fe.group(3)) == 4          # all 4 tokens generated
        and int(fe.group(4)) >= 1          # the over-cap submit was shed
        and "capacity" in fe.group(5)
        and "SHED" in res.stderr           # ... loudly
        and workers.get(1, ("", 0, ""))[0] == "prefill"
        and workers.get(2, ("", 0, ""))[0] == "decode"
        and workers[1][1] > 0 and workers[2][1] > 0  # KV bytes in stats
        and "prefill" in workers[1][2]
        and "kv_xfer" in workers[1][2]
        and "decode" in workers[2][2]
        and "kv_xfer" in workers[2][2]
    )
    if not ok:
        tail = (res.stderr.strip() or res.stdout.strip())[-220:]
        return False, f"{knobs}; serving run failed: {tail}"
    return True, (f"{knobs}; np=3 disagg roles prefill=r1 decode=r2, "
                  f"request prefilled r1 -> KV {workers[1][1]} B shipped "
                  f"-> decoded r2, kv tier bytes in both workers' stats, "
                  f"over-cap submit shed loudly in {dt:.1f}s")


def check_live_retune(port):
    """The live re-tuning brain end to end on a loopback 2-rank job:
    drift is forced by pointing ``MPI4JAX_TPU_TUNE_MODEL`` at a synthetic
    cost model that predicts the pinned ``ring`` algorithm absurdly fast
    (so real loopback timings drift immediately) while ``rd`` stays
    modest (so the candidate overlay re-picks it), and the check asserts
    the armed controller detects the drift, rank 0 proposes, the epoch
    rendezvous installs the new table on BOTH ranks at the same epoch,
    and the swap report names the old -> new winner."""
    import re
    import tempfile

    from ..utils import config

    window, cooldown = 32, 8
    knobs = (f"window={window} cooldown={cooldown} "
             f"drift_pct=50 quant={config.quant_mode()}")
    model = json.dumps({
        "version": 1, "world_size": 2, "topology": None,
        "dtype": "float32", "knobs": {}, "source": "diag-forced",
        "samples": {
            # ring predicted ~1us at 256 KiB: any real loopback timing
            # drifts; rd modest so the overlaid candidate re-picks it
            "allreduce/ring": {"1024": 1e-7, "262144": 1e-6},
            "allreduce/rd": {"1024": 2e-6, "262144": 5e-6},
        },
        "wire_frac": {}, "dispatch_frac": {},
    })
    code = (
        "import sys, types, os, time; sys.path.insert(0, %r)\n"
        # parent-package shim: bridge-level ranks must work even where
        # the package's jax gate blocks the full import
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "import numpy as np\n"
        "from mpi4jax_tpu import live\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "c = transport.get_world_comm()\n"
        "h = c.handle\n"
        "assert live.armed(), 'live controller failed to arm'\n"
        "x = np.zeros(65536, dtype=np.float32)\n"  # 256 KiB payload
        "deadline = time.time() + 45\n"
        "ops = 0\n"
        "while time.time() < deadline:\n"
        "    bridge.allreduce(h, x, 0)\n"
        "    ops += 1\n"
        "    if live.status().get('epoch', 0) > 0:\n"
        "        break\n"
        "    time.sleep(0.002)\n"
        "st = live.status()\n"
        "sw = st.get('swaps', [])\n"
        "changes = ';'.join(sw[0]['report'].get('changes', [])) if sw "
        "else ''\n"
        # one write() so the two ranks' report lines can't interleave
        "sys.stdout.write('diag_live %%d epoch %%d ops %%d errors %%d "
        "changes %%r\\n' %% (\n"
        "    c.rank(), st.get('epoch', 0), ops, st.get('errors', -1),\n"
        "    changes))\n"
        "sys.stdout.flush()\n"
        % (REPO, REPO)
    )
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag_live.py", delete=False
    ) as f:
        f.write(code)
        prog = f.name
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag_live_model.json", delete=False
    ) as f:
        f.write(model)
        model_path = f.name
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # TCP path so the installed table actually dispatches (the
        # same-host shm arena would shadow the algorithm choice)
        "MPI4JAX_TPU_DISABLE_SHM": "1",
        "MPI4JAX_TPU_TIMEOUT_S": "60",
        "MPI4JAX_TPU_TUNE_MODEL": model_path,
        "MPI4JAX_TPU_COLL_ALGO": "allreduce=ring",
        "MPI4JAX_TPU_LIVE": "auto",
        "MPI4JAX_TPU_LIVE_WINDOW": str(window),
        "MPI4JAX_TPU_LIVE_DRIFT_PCT": "50",
        "MPI4JAX_TPU_LIVE_COOLDOWN_OPS": str(cooldown),
    }
    t0 = time.perf_counter()
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "2", "--port", str(port), prog],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return False, f"{knobs}; live retune run hung"
    finally:
        os.unlink(prog)
        os.unlink(model_path)
    dt = time.perf_counter() - t0
    lines = {
        int(m.group(1)): (int(m.group(2)), int(m.group(3)),
                          int(m.group(4)), m.group(5))
        for m in re.finditer(
            r"diag_live (\d+) epoch (\d+) ops (\d+) errors (\d+) "
            r"changes '([^']*)'", res.stdout)
    }
    ok = (
        res.returncode == 0
        and len(lines) == 2
        # agreement: BOTH ranks installed the same nonzero epoch
        and lines[0][0] == lines[1][0] >= 1
        # the re-pick: report names the old -> new winner
        and all("ring -> rd" in v[3] for v in lines.values())
        # the commit really went through the rendezvous
        and "[live] epoch 1 committed" in res.stderr
        # controller thread never swallowed an exception
        and all(v[2] == 0 for v in lines.values())
    )
    if not ok:
        tail = (res.stderr.strip() or res.stdout.strip())[-220:]
        return False, f"{knobs}; live retune failed: {tail}"
    ops = max(v[1] for v in lines.values())
    return True, (f"{knobs}; forced model drift detected, epoch "
                  f"{lines[0][0]} rendezvous re-picked "
                  f"'{lines[0][3]}' on both ranks after {ops} ops "
                  f"in {dt:.1f}s")


def check_topology(port):
    """The topology subsystem end to end on a loopback 4-rank job
    virtually partitioned into two islands (MPI4JAX_TPU_FAKE_HOSTS):
    discovery agrees on the island map, the world arena is withheld
    while each island's intra sub-comm gets one, the native layer
    reports the installed map, the decision table defaults the 16 MB
    allreduce to the hierarchical ring, and a forced hring matches the
    flat result bit-for-bit on integer-valued floats.  The report line
    names the intra-island data plane: ``intra=ici(<backend>)`` when
    the ICI leg is active on this comm (``MPI4JAX_TPU_ICI_LEG``),
    ``intra=native`` otherwise — integer payloads keep the bit-parity
    assertions valid either way (every association sums them exactly)."""
    import tempfile

    from ..utils import config

    if config.topo_mode() == "off":
        return True, "disabled by MPI4JAX_TPU_TOPO=off (flat transport)"
    code = (
        "import sys, types, os; sys.path.insert(0, %r)\n"
        # parent-package shim: the bridge-level ranks must work even
        # where the package's jax gate blocks the full import
        "pkg = types.ModuleType('mpi4jax_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')]\n"
        "sys.modules['mpi4jax_tpu'] = pkg\n"
        "import numpy as np\n"
        "from mpi4jax_tpu import topo, tune\n"
        "from mpi4jax_tpu.runtime import bridge, transport\n"
        "c = transport.get_world_comm()\n"
        "t = c.topology()\n"
        "assert t is not None and t.multi, t\n"
        "assert t.islands == [[0, 1], [2, 3]], t.islands\n"
        "act, _, _ = bridge.shm_info(c.handle)\n"
        "assert not act, 'world arena must be withheld under FAKE_HOSTS'\n"
        "info = bridge.topo_info(c.handle)\n"
        "assert info == ([0, 0, 1, 1], 2), info\n"
        "pick = c.coll_algo('allreduce', 16 << 20)\n"
        "assert pick == 'hring', pick  # the locality-aware default\n"
        "x = np.arange(70000, dtype=np.float32) + c.rank()\n"
        # the flat reference must be FORCED: the multi-island default
        # table already resolves this payload to hring
        "ref = bridge.allreduce(c.handle, x, 0,\n"
        "                       algo=tune.ALGO_CODES['ring'])\n"
        "out = bridge.allreduce(c.handle, x, 0,\n"
        "                       algo=tune.ALGO_CODES['hring'])\n"
        "assert np.array_equal(out, ref), 'hring diverged from flat ring'\n"
        "sim = topo.simulate_hring_sum(\n"
        "    [np.arange(70000, dtype=np.float32) + r for r in range(4)],\n"
        "    t.islands)\n"
        "assert np.array_equal(out, sim), 'hring diverged from simulator'\n"
        "st = topo.ici_leg_status(c.handle)\n"
        "intra = ('ici(' + st['backend'] + ')') if st['active'] \\\n"
        "    else 'native'\n"
        "if c.rank() == 0:\n"
        "    print('topology-ok', t.render(), 'fp=' + t.fingerprint(),\n"
        "          'algo16mb=' + pick, 'intra=' + intra, flush=True)\n"
        % (REPO, REPO)
    )
    with tempfile.NamedTemporaryFile(
        "w", suffix="_m4j_diag_topo.py", delete=False
    ) as f:
        f.write(code)
        prog = f.name
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "MPI4JAX_TPU_TIMEOUT_S": os.environ.get(
            "MPI4JAX_TPU_TIMEOUT_S", "60"),
    }
    env.pop("MPI4JAX_TPU_COLL_ALGO", None)  # the check asserts defaults
    # ...including the default TABLE: a user's topology-keyed cache
    # must not steer the pick this check pins
    env["MPI4JAX_TPU_TUNE_CACHE"] = os.path.join(
        tempfile.gettempdir(), "m4j_diag_no_cache_sentinel.json")
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "mpi4jax_tpu", "runtime", "launch.py"),
             "-n", "4", "--port", str(port),
             "--fake-hosts", "r0,r1|r2,r3", prog],
            capture_output=True, text=True, timeout=150, env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return False, "timed out (deadlock or port conflict?)"
    finally:
        os.unlink(prog)
    if res.returncode != 0 or "topology-ok" not in res.stdout:
        return False, (res.stderr.strip() or res.stdout.strip())[-220:]
    for line in res.stdout.splitlines():
        if line.startswith("topology-ok"):
            return True, line[len("topology-ok "):]
    return False, "no topology report line"


def check_static_verify():
    """The static communication verifier reaches correct verdicts: a
    known-bad snippet (tag mismatch) is flagged with the right finding
    kind and a known-good snippet verifies clean — all without spawning
    a process or opening a socket."""
    import tempfile

    bad = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import jax.numpy as jnp\n"
        "import mpi4jax_tpu as m4j\n"
        "comm = m4j.get_default_comm()\n"
        "x = jnp.arange(3.0)\n"
        "if comm.rank() == 0:\n"
        "    m4j.send(x, dest=1, tag=5, comm=comm)\n"
        "else:\n"
        "    m4j.recv(x, source=0, tag=7, comm=comm)\n"
    )
    good = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import jax.numpy as jnp\n"
        "import mpi4jax_tpu as m4j\n"
        "comm = m4j.get_default_comm()\n"
        "out = m4j.allreduce(jnp.arange(4.0), op=m4j.SUM, comm=comm)\n"
        "assert float(out[1]) == 2.0, out\n"
    )
    t0 = time.perf_counter()
    verdicts = []
    for name, src, want_rc in (("bad", bad, 3), ("good", good, 0)):
        with tempfile.NamedTemporaryFile(
            "w", suffix=f"_m4j_diag_{name}.py", delete=False
        ) as f:
            f.write(src)
            prog = f.name
        try:
            env = dict(os.environ)
            env.setdefault("PYTHONPATH", REPO)
            env.setdefault("JAX_PLATFORMS", "cpu")
            res = subprocess.run(
                [sys.executable, "-m", "mpi4jax_tpu.analyze", prog,
                 "-n", "2", "--json"],
                capture_output=True, text=True, timeout=150, env=env,
                cwd=REPO,
            )
        except subprocess.TimeoutExpired:
            return False, f"analyzer hung on the {name} snippet"
        finally:
            os.unlink(prog)
        if res.returncode != want_rc:
            return False, (
                f"{name} snippet: expected exit {want_rc}, got "
                f"{res.returncode}: {(res.stderr or res.stdout)[-150:]}"
            )
        if name == "bad":
            data = json.loads(res.stdout)
            kinds = {f["kind"] for f in data["findings"]}
            if "tag_mismatch" not in kinds:
                return False, f"bad snippet flagged as {sorted(kinds)}"
            verdicts.append("tag_mismatch flagged")
        else:
            verdicts.append("clean verified")
    dt = time.perf_counter() - t0
    return True, (f"{' + '.join(verdicts)} in {dt:.1f}s, "
                  "no process spawned, no live comm")


def check_schedule_plan(port):
    """The schedule compiler end to end: a pipeline schedule compiles
    into a plan with hoisted receives and deferred sends, the
    equivalence prover accepts it (and rejects a reordering-unsafe
    one), and a size-1 native comm executes a verified plan through the
    runner bit-identically to the direct path — ticketed posting on the
    progress engine, no processes, no sockets."""
    import ctypes

    import numpy as np

    from ..analysis import _events, _plan
    from . import bridge, planrt

    # -- compile + prove, pure analysis (no native) --------------------
    big = (64 * 1024,)

    def ev(rank, i, kind, **kw):
        return _events.CommEvent(rank, i, kind, dtype="float32",
                                 shape=big, **kw)

    pipeline = {0: [ev(0, 0, "send", dest=1, tag=0),
                    ev(0, 1, "recv", source=1, tag=0)],
                1: [ev(1, 0, "send", dest=0, tag=0),
                    ev(1, 1, "recv", source=0, tag=0)]}
    comms = {(0,): (0, 1)}
    plan = _plan.compile_schedules(pipeline, comms)
    if not (plan.proved and plan.rewritten):
        return False, f"pipeline plan not proved+rewritten: {plan.reasons}"
    if not any(op.hoisted for rp in plan.ranks.values() for op in rp.ops):
        return False, "pipeline plan hoisted no recv"
    # the deadlock-by-construction shape must be left unrewritten
    from ..analysis import _match

    unsafe = {0: [ev(0, 0, "send", dest=1, tag=0),
                  ev(0, 1, "recv", source=1, tag=0)],
              1: [ev(1, 0, "recv", source=0, tag=0),
                  ev(1, 1, "send", dest=0, tag=0)]}
    findings = _match.match_schedules(unsafe, comms)
    plan2 = _plan.compile_schedules(unsafe, comms, findings=findings)
    if plan2.rewritten or not plan2.proved:
        return False, "order-critical schedule was not left unrewritten"

    # -- execute a verified plan on a size-1 loopback comm --------------
    if not bridge.post_available():
        return False, ("native library predates ticketed posting "
                       "(no tpucomm_post); rebuild native/")
    n_msgs, shape = 3, (512,)
    events = {0: []}
    for k in range(n_msgs):
        events[0].append(_events.CommEvent(0, 2 * k, "send", dest=0,
                                           tag=k, dtype="float32",
                                           shape=shape))
        events[0].append(_events.CommEvent(0, 2 * k + 1, "recv", source=0,
                                           tag=k, dtype="float32",
                                           shape=shape))
    loop_plan = _plan.compile_schedules(events, {(0,): (0,)},
                                        detach_threshold=0)
    if not loop_plan.proved:
        return False, f"loopback plan not proved: {loop_plan.reasons}"
    h = bridge.get_lib().tpucomm_init(0, 1, int(port), b"")
    if h == 0:
        return False, "size-1 comm init failed"
    try:
        class _C:  # planrt.get keys on .handle
            handle = h

        if not planrt.install(h, loop_plan, 0):
            return False, "planrt.install refused a proved plan"
        rt = planrt.get(_C())
        for k in range(n_msgs):
            x = np.arange(shape[0], dtype=np.float32) + k
            if not rt.run_send(x, 0, k):
                return False, f"runner did not handle send {k}"
            got = rt.run_recv(shape, np.float32, 0, k)
            if got is None or not np.array_equal(got, x):
                return False, f"plan-executed loopback payload {k} wrong"
        rt.flush()
        stats = dict(rt.stats)
        if stats["mismatches"]:
            return False, f"runner reported mismatches: {stats}"
        from ..utils import config as _config

        mode = _config.plan_spec() or "off"
        return True, (f"pipeline plan proved+rewritten "
                      f"({plan.proof.get('interleavings')} interleavings), "
                      "unsafe schedule left unrewritten, plan-executed "
                      f"loopback bit-identical ({stats['deferred_sends']} "
                      f"deferred send(s), {stats['hoisted_recvs']} hoisted "
                      f"recv(s); MPI4JAX_TPU_PLAN={mode})")
    finally:
        planrt.detach(h)
        bridge.get_lib().tpucomm_finalize(ctypes.c_int64(h))


def check_device_claim():
    """A fresh process can claim the accelerator."""
    rc, out, _ = _run_snippet(
        "import jax; d = jax.devices(); print('claim-ok', d[0].platform)",
        timeout=150,
    )
    if rc is None:
        return False, "claim HUNG (another process holding the chip?)"
    # require an explicit non-cpu platform: a jax that fell back to cpu
    # must not report the accelerator healthy
    platform = ""
    for line in out.splitlines():
        parts = line.split()
        if parts[:1] == ["claim-ok"] and len(parts) == 2:
            platform = parts[1]
    ok = rc == 0 and bool(platform) and platform != "cpu"
    detail = out.strip().splitlines()[-1] if out.strip() else "no output"
    if rc == 0 and platform == "cpu":
        detail = "claim fell back to cpu (accelerator plugin failed?)"
    return ok, detail


def check_device_compile():
    """The backend can compile + run a trivial program."""
    rc, out, err = _run_snippet(
        "import jax, jax.numpy as jnp;"
        "print('compile-ok', float(jnp.arange(8.0).sum()))",
        timeout=240,
    )
    if rc is None:
        return False, "compile HUNG"
    ok = rc == 0 and "compile-ok" in out
    return ok, out.strip().splitlines()[-1] if ok else (err or out)[-200:]


def check_host_callbacks():
    """Host callbacks (the in-jit world-op path) are implemented."""
    rc, out, err = _run_snippet(
        "import jax, jax.numpy as jnp, numpy as np;"
        "f = lambda v: jax.pure_callback("
        "lambda a: np.asarray(a) * 2,"
        "jax.ShapeDtypeStruct((2,), np.float32), v);"
        "print('cb-ok', jax.jit(f)(jnp.ones(2, jnp.float32))[0])",
        timeout=240,
    )
    if rc is None:
        return False, "callback probe hung"
    if rc == 0 and "cb-ok" in out:
        return True, "pure_callback under jit"
    return False, (err or out)[-200:]


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m mpi4jax_tpu.runtime.diag")
    ap.add_argument("--device", action="store_true",
                    help="also probe the accelerator (claim/compile/"
                         "callbacks); each probe is a subprocess with a "
                         "timeout")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--port", type=int, default=45910)
    args = ap.parse_args(argv)

    checks = [
        ("native_build", check_native_build),
        ("ffi_fast_path", check_ffi),
        ("coll_algo_engine", check_coll_algo_engine),
        ("observability", lambda: check_observability(args.port + 13)),
        ("static_verify", check_static_verify),
        ("schedule_plan", lambda: check_schedule_plan(args.port + 19)),
        ("topology", lambda: check_topology(args.port + 37)),
        ("transport_loopback", lambda: check_transport_loopback(args.port)),
        ("failure_detection",
         lambda: check_failure_detection(args.port + 7)),
        ("self_healing", lambda: check_self_healing(args.port + 53)),
        ("elasticity", lambda: check_elasticity(args.port + 29)),
        ("serving", lambda: check_serving(args.port + 43)),
        ("live_retune", lambda: check_live_retune(args.port + 61)),
    ]
    if args.device:
        checks += [
            ("device_claim", check_device_claim),
            ("device_compile", check_device_compile),
            ("host_callbacks", check_host_callbacks),
        ]

    results = []
    failed = 0
    for name, fn in checks:
        t0 = time.perf_counter()
        try:
            ok, detail = fn()
        except Exception as err:
            ok, detail = False, f"{type(err).__name__}: {err}"[:200]
        dt = time.perf_counter() - t0
        failed += 0 if ok else 1
        results.append({"check": name, "ok": bool(ok),
                        "detail": str(detail), "seconds": round(dt, 1)})
        if not args.json:
            mark = "PASS" if ok else "FAIL"
            print(f"{mark}  {name:<20} {detail}  ({dt:.1f}s)", flush=True)
    if args.json:
        print(json.dumps({"results": results, "failed": failed}))
    return failed


if __name__ == "__main__":
    sys.exit(main())
