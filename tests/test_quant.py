"""Quantized collective wire formats (qring/qrd): the native codec
against its documented numpy reference (bit-identical — the accuracy
harness and the docs lean on the reference being the REAL format), the
schedule simulators' error bounds, the tune-layer gating, and the
observability wire_bytes/compression plumbing.

Runs under CPU-only tier-1: the native half drives a transport-only
build of tpucomm.cc through ctypes on a size-1 comm (no sockets), the
rest is pure Python loaded through the package or standalone.
"""

import ctypes
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_file(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_quantized():
    try:
        from mpi4jax_tpu.ops import quantized

        return quantized
    except ImportError:
        # the module's jax imports are lazy; the numpy reference and
        # simulators work standalone
        import types

        pkg = types.ModuleType("m4j_q_pkg")
        pkg.__path__ = [str(REPO / "mpi4jax_tpu")]
        sys.modules.setdefault("m4j_q_pkg", pkg)
        return _load_file("m4j_quantized_test",
                          REPO / "mpi4jax_tpu/ops/quantized.py")


def _load_tune():
    try:
        from mpi4jax_tpu import tune

        return tune
    except ImportError:
        return _load_file("m4j_tune_quant_test",
                          REPO / "mpi4jax_tpu/tune/__init__.py")


q = _load_quantized()
tune = _load_tune()


# ---------------- native codec vs the numpy reference ----------------


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    cxx = os.environ.get("CXX", "g++")
    if shutil.which(cxx) is None:
        pytest.skip(f"no C++ compiler ({cxx}) available")
    so = tmp_path_factory.mktemp("quant_native") / "libtpucomm_quant.so"
    src = REPO / "native" / "tpucomm.cc"
    res = subprocess.run(
        [cxx, "-O2", "-std=c++17", "-fPIC", "-Wall", "-pthread", "-shared",
         "-o", str(so), str(src), "-lrt"],
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, f"native build failed:\n{res.stderr[-2000:]}"
    lib = ctypes.CDLL(str(so))
    lib.tpucomm_quant_packed_bytes.restype = ctypes.c_int64
    lib.tpucomm_quant_packed_bytes.argtypes = [ctypes.c_int64]
    lib.tpucomm_quant_pack.restype = ctypes.c_int
    lib.tpucomm_quant_unpack.restype = ctypes.c_int
    return lib


def _p(a):
    return a.ctypes.data_as(ctypes.c_void_p)


def _native_pack(lib, x, dtype_code):
    out = np.zeros(int(lib.tpucomm_quant_packed_bytes(x.size)), np.int8)
    rc = lib.tpucomm_quant_pack(_p(x), ctypes.c_int64(x.size), dtype_code,
                                _p(out))
    assert rc == 0
    return out


def test_packed_bytes_formula_and_block_sync(native_lib):
    """The wire layout is ceil(n/QUANT_BLOCK) f32 scales + n int8 codes;
    the native kQuantBlock and the Python QUANT_BLOCK must agree (the
    packed size at one-past-a-block boundary detects any drift)."""
    for n in (0, 1, q.QUANT_BLOCK - 1, q.QUANT_BLOCK, q.QUANT_BLOCK + 1,
              7 * q.QUANT_BLOCK + 13, 1 << 20):
        nb = (n + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK
        assert native_lib.tpucomm_quant_packed_bytes(n) == (
            4 * nb + n if n > 0 else 0), n


@pytest.mark.parametrize("n", [1, 5, 255, 256, 257, 1000, 4096, 100_001])
def test_native_pack_bit_identical_to_reference(native_lib, n):
    rng = np.random.RandomState(n)
    for x in (
        (rng.randn(n) * rng.choice([1e-4, 1.0, 1e4])).astype(np.float32),
        np.zeros(n, np.float32),                       # all-zero blocks
        np.full(n, -3.25, np.float32),                 # constant
    ):
        packed = _native_pack(native_lib, x, 11)       # TPU_F32
        scales, codes = q.quant_pack_ref(x)
        ref = np.concatenate([scales.view(np.int8), codes])
        np.testing.assert_array_equal(packed, ref)
        # the whole-frame reference (what the ICI leg ships on the
        # leader tier) is the same bytes — three codecs, one format
        np.testing.assert_array_equal(q.quant_pack_wire_ref(x), ref)
        # unpack round-trips exactly the reference's dequantization
        back = np.empty(n, np.float32)
        rc = native_lib.tpucomm_quant_unpack(_p(packed), ctypes.c_int64(n),
                                             11, _p(back))
        assert rc == 0
        np.testing.assert_array_equal(back, q.quant_unpack_ref(scales,
                                                               codes))


def test_native_pack_error_bound(native_lib):
    """|dequant - x| <= blockwise absmax/254 (half a quantization step)."""
    rng = np.random.RandomState(0)
    x = (rng.randn(10_000) * 7).astype(np.float32)
    packed = _native_pack(native_lib, x, 11)
    back = np.empty(x.size, np.float32)
    assert native_lib.tpucomm_quant_unpack(_p(packed),
                                           ctypes.c_int64(x.size), 11,
                                           _p(back)) == 0
    for b in range((x.size + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK):
        blk = slice(b * q.QUANT_BLOCK, min(x.size, (b + 1) * q.QUANT_BLOCK))
        bound = np.max(np.abs(x[blk])) / 127.0 * 0.5 + 1e-9
        assert np.max(np.abs(back[blk] - x[blk])) <= bound, b


def test_native_pack_rejects_integer_dtypes(native_lib):
    x = np.arange(16, dtype=np.int32)
    out = np.zeros(64, np.int8)
    assert native_lib.tpucomm_quant_pack(_p(x), ctypes.c_int64(16), 3,
                                         _p(out)) != 0  # TPU_I32


def test_native_pack_bf16(native_lib):
    """bf16 payloads convert through f32 exactly like the reference."""
    rng = np.random.RandomState(2)
    f = (rng.randn(777) * 3).astype(np.float32)
    bits = f.view(np.uint32)
    bf = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16).astype(np.uint16)
    packed = _native_pack(native_lib, bf, 10)          # TPU_BF16
    f_from_bf = (bf.astype(np.uint32) << 16).view(np.float32)
    scales, codes = q.quant_pack_ref(f_from_bf)
    np.testing.assert_array_equal(
        packed, np.concatenate([scales.view(np.int8), codes]))


def test_wire_ref_matches_reference_layout():
    # pure numpy, no native build needed: the frame is scale bytes then
    # codes, nothing else (the ICI leg's _unpack_fold depends on it)
    rng = np.random.RandomState(3)
    for n in (1, q.QUANT_BLOCK - 1, q.QUANT_BLOCK, q.QUANT_BLOCK + 1, 1000):
        x = (rng.randn(n) * 5).astype(np.float32)
        scales, codes = q.quant_pack_ref(x)
        nb = (n + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK
        wire = q.quant_pack_wire_ref(x)
        assert wire.shape == (4 * nb + n,) and wire.dtype == np.int8
        np.testing.assert_array_equal(
            wire, np.concatenate([scales.view(np.int8), codes]))


@pytest.mark.parametrize("n", [1, 255, 256, 257, 1000, 4096])
def test_pallas_pack_bit_identical_to_reference(n):
    """The cross-ISA contract extended to the THIRD codec: the Pallas
    in-kernel pack (interpret mode here; the leader leg of the ICI data
    plane on a slice) emits byte-identical wire frames to
    ``quant_pack_ref``/``tpucomm_quant_pack``."""
    import jax.numpy as jnp

    from mpi4jax_tpu.ops import pallas_collectives as pc

    rng = np.random.RandomState(n)
    for x in (
        (rng.randn(n) * rng.choice([1e-4, 1.0, 1e4])).astype(np.float32),
        np.zeros(n, np.float32),
        np.full(n, -3.25, np.float32),
    ):
        wire = np.asarray(pc.quant_pack_pallas(jnp.asarray(x),
                                               interpret=True))
        np.testing.assert_array_equal(wire, q.quant_pack_wire_ref(x))


# ---------------- schedule simulators (accuracy-harness backbone) -----


@pytest.mark.parametrize("size", [2, 3, 4, 5, 8])
@pytest.mark.parametrize("sim", ["qring", "qrd"])
def test_simulators_track_exact_sum(size, sim):
    rng = np.random.RandomState(size)
    n = 3 * q.QUANT_BLOCK + 17
    parts = [(rng.randn(n) * (r + 1)).astype(np.float32)
             for r in range(size)]
    exact = np.sum(np.stack(parts), axis=0, dtype=np.float64)
    fn = q.simulate_qring_sum if sim == "qring" else q.simulate_qrd_sum
    got = fn(parts)
    denom = max(np.max(np.abs(exact)), 1e-6)
    assert np.max(np.abs(got - exact)) / denom < 3e-2, sim


def test_simulators_are_deterministic():
    rng = np.random.RandomState(9)
    parts = [rng.randn(1000).astype(np.float32) for _ in range(3)]
    assert np.array_equal(q.simulate_qring_sum(parts),
                          q.simulate_qring_sum(parts))
    assert np.array_equal(q.simulate_qrd_sum(parts),
                          q.simulate_qrd_sum(parts))


def test_simulator_size_one_is_identity():
    x = np.arange(7, dtype=np.float32)
    np.testing.assert_array_equal(q.simulate_qring_sum([x]), x)
    np.testing.assert_array_equal(q.simulate_qrd_sum([x]), x)


# ---------------- tune-layer gating ----------------


def test_quantized_algorithm_maps_to_twin():
    # defaults: tree below 64KB -> qrd, ring above -> qring
    assert tune.quantized_algorithm(1024) == "qrd"
    assert tune.quantized_algorithm(16 << 20) == "qring"
    assert tune.QUANT_TWIN["tree"] == "qrd"
    assert tune.EXACT_TWIN["qring"] == "ring"


def test_qalgos_rejected_for_allgather():
    with pytest.raises(ValueError, match="allreduce-only"):
        tune.set_algorithm("allgather", "qring")
    with pytest.raises(ValueError, match="allreduce-only"):
        tune._validate_table({"allgather": [(0, "qrd")]})
    # allreduce rows are legal and win the merge
    tune.set_algorithm("allreduce", "qring", min_bytes=1 << 20)
    try:
        assert tune.get_algorithm("allreduce", 4 << 20) == "qring"
        assert tune.quantized_algorithm(4 << 20) == "qring"
    finally:
        tune.clear_overrides()


def test_env_bare_quantized_name_governs_allreduce_only(monkeypatch):
    monkeypatch.setenv("MPI4JAX_TPU_COLL_ALGO", "qring")
    table = tune.decision_table()
    assert table["allreduce"] == [(0, "qring")]
    # allgather keeps its normal selection (no quantized schedule)
    assert all(a not in tune.QUANT_ALGOS for _, a in table["allgather"])


def test_wire_fractions_and_promotion_gates(monkeypatch, tmp_path):
    import json

    def ev(algo, nbytes, dur_us, wait_us):
        return {"name": "Allreduce", "src": "native", "ts_us": 0.0,
                "dur_us": dur_us, "wait_us": wait_us, "dispatch_us": 0.0,
                "bytes": nbytes, "peer": -1, "tag": 0, "algo": algo}

    # wire-bound ring at 1 MB, wait-bound ring at 128 KB
    events = ([ev("ring", 1 << 20, 1000.0, 10.0)] * 3
              + [ev("ring", 128 << 10, 1000.0, 900.0)] * 3
              + [ev("rd", 1 << 20, 5000.0, 10.0)] * 3
              + [ev("rd", 128 << 10, 5000.0, 10.0)] * 3)
    fr = tune.wire_fractions_from_events(events)
    assert fr["allreduce"][1 << 20]["ring"] > 0.9
    assert fr["allreduce"][128 << 10]["ring"] < 0.2

    try:
        from mpi4jax_tpu import obs
    except ImportError:
        obs = _load_file(
            "m4j_obs_quant_test",
            REPO / "mpi4jax_tpu/obs/__init__.py")
    base = str(tmp_path / "rec.json")
    # write a part so cache_from_trace's loader path is exercised
    import types  # noqa: F401

    part = {"version": 1, "rank": 0, "size": 2, "clock_offset_us": 0.0,
            "dropped": {}, "events": events}
    p0 = f"{base}.rank0.json"
    with open(p0, "w") as f:
        json.dump(part, f)
    cache = str(tmp_path / "cache.json")
    tune.cache_from_trace([p0], cache_path_override=cache)
    table = json.load(open(cache))["table"]["allreduce"]
    # 128 KB: wait-bound -> stays exact; 1 MB: wire-bound -> promoted
    assert table == [[0, "ring"], [1 << 20, "qring"]]

    # deny gate: no promotion when int8 wire formats are vetoed
    monkeypatch.setenv("MPI4JAX_TPU_COLL_QUANT", "deny")
    cache2 = str(tmp_path / "cache2.json")
    tune.cache_from_trace([p0], cache_path_override=cache2)
    table2 = json.load(open(cache2))["table"]["allreduce"]
    assert all(a not in tune.QUANT_ALGOS for _, a in table2)


# ---------------- obs wire_bytes / compression plumbing ----------------


def _load_obs():
    try:
        from mpi4jax_tpu import obs

        return obs
    except ImportError:
        return _load_file("m4j_obs_quant_test2",
                          REPO / "mpi4jax_tpu/obs/__init__.py")


def test_stats_compression_column_only_when_it_differs():
    obs = _load_obs()
    exact = {"name": "Allreduce", "src": "native", "ts_us": 0.0,
             "dur_us": 100.0, "wait_us": 0.0, "dispatch_us": 0.0,
             "bytes": 4096, "peer": -1, "tag": 0, "algo": "ring"}
    quant = dict(exact, algo="qring", wire_bytes=1088, ts_us=200.0)
    stats = obs.summarize([exact, quant])
    rows = {r["algo"]: r for r in stats["per_op"]}
    assert "compression" not in rows["ring"]
    assert rows["qring"]["wire_bytes"] == 1088
    assert rows["qring"]["compression"] == pytest.approx(4096 / 1088,
                                                         rel=1e-3)
    # eff_GBps stays LOGICAL bytes over wall time for both rows
    assert rows["qring"]["eff_GBps"] == rows["ring"]["eff_GBps"]
    # the rendered table gains the column only because a quantized row
    # is present
    table = obs.render_table(stats)
    assert "compression" in table
    table_exact = obs.render_table(obs.summarize([exact]))
    assert "compression" not in table_exact


def test_trace_and_chrome_roundtrip_carry_wire_bytes(tmp_path):
    obs = _load_obs()
    quant = {"name": "Allreduce", "src": "native", "ts_us": 10.0,
             "dur_us": 50.0, "wait_us": 5.0, "dispatch_us": 0.0,
             "bytes": 4096, "wire_bytes": 1088, "peer": -1, "tag": 0,
             "algo": "qring"}
    trace = obs.merge_parts([{"rank": 0, "size": 2, "events": [quant],
                              "dropped": {}}])
    assert obs.validate_chrome_trace(trace) == []
    span = next(e for e in trace["traceEvents"]
                if e.get("ph") == "X" and e.get("cat") == "native")
    assert span["args"]["wire_bytes"] == 1088
    assert span["args"]["bytes"] == 4096
    # load_events maps the chrome span back to a canonical event
    import json

    path = tmp_path / "merged.json"
    path.write_text(json.dumps(trace))
    events, _ = obs.load_events(str(path))
    assert events[0]["wire_bytes"] == 1088


def test_native_drain_defaults_wire_bytes_to_logical(native_lib):
    """Exact ops drain with wire_bytes == bytes (schema compatibility:
    every consumer may default the field)."""
    nat = _load_file("m4j_obs_native_quant_test",
                     REPO / "mpi4jax_tpu/obs/_native.py")
    assert nat.available(native_lib)
    lib = native_lib
    lib.tpucomm_init.restype = ctypes.c_int64
    lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_char_p]
    h = lib.tpucomm_init(0, 1, 47423, b"")
    assert h > 0
    try:
        nat.enable(lib, 64)
        x = np.arange(64.0, dtype=np.float32)
        out = np.empty_like(x)
        rc = lib.tpucomm_allreduce(ctypes.c_int64(h), _p(x), _p(out),
                                   ctypes.c_int64(64), 11, 0)
        assert rc == 0
        events = nat.drain(lib)
        nat.disable(lib)
        assert events and all(e["wire_bytes"] == e["bytes"]
                              for e in events)
    finally:
        lib.tpucomm_finalize(ctypes.c_int64(h))


# ---------------- packed one-leg scale transport (Python schedule) ----


def test_pack_scales_bitcast_roundtrip():
    jax = pytest.importorskip("jax")
    if not hasattr(q, "_pack_scales"):
        pytest.skip("standalone quantized module")
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    codes = jnp.asarray(rng.randint(-127, 128, (3, 40)), jnp.int8)
    scales = jnp.asarray(rng.rand(3).astype(np.float32) * 1e-3)
    packed = q._pack_scales(codes, scales)
    assert packed.shape == (3, 44) and packed.dtype == jnp.int8
    q2, s2 = q._unpack_scales(packed)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(codes))
    # the bitcast preserves the EXACT scale bits — this is what makes
    # the one-leg schedule bit-compatible with the old two-leg one
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(scales))


# ------- one-leg packed schedule ≡ historic two-leg schedule ----------
#
# The satellite contract: packing the scales into the payload halves the
# round count with BIT-COMPATIBLE results.  A little oracle runs the
# per-rank schedule for every virtual rank, resolving each collective
# leg when all ranks have posted to it — no mesh, no transport, works on
# any jax.


class _Stop(Exception):
    pass


def _run_world(xs, body):
    """Execute ``body(x_r, size, alltoall, allgather)`` for every rank
    of a virtual world, resolving collective legs in program order."""
    n = len(xs)
    resolved = []  # [(kind, [out_r, ...])]
    while True:
        posted = [None] * n
        kinds = [None] * n
        outs = [None] * n
        stopped = False
        for r in range(n):
            counter = {"i": 0}

            def leg(kind, arr, r=r):
                i = counter["i"]
                counter["i"] += 1
                if i < len(resolved):
                    rkind, router = resolved[i]
                    assert rkind == kind, "ranks diverged on leg order"
                    return router[r]
                posted[r] = np.asarray(arr)
                kinds[r] = kind
                raise _Stop()

            try:
                outs[r] = body(
                    xs[r], n,
                    lambda rows, r=r: leg("alltoall", rows, r),
                    lambda row, r=r: leg("allgather", row, r))
            except _Stop:
                stopped = True
        if not stopped:
            return outs
        assert all(k == kinds[0] for k in kinds), kinds
        if kinds[0] == "alltoall":
            router = [np.stack([posted[j][r] for j in range(n)])
                      for r in range(n)]
        else:
            router = [np.stack(posted)] * n
        resolved.append((kinds[0], router))


def _two_leg_schedule(x, size, alltoall, allgather):
    """The historic schedule: separate alltoall/allgather legs for the
    scales (four collective legs total)."""
    import jax.numpy as jnp

    orig_dtype = x.dtype
    flat, pad = q._pad_to(x, size)
    chunks = flat.reshape(size, -1)
    codes, scale = q._quantize(chunks)
    q_t = alltoall(codes)
    s_t = alltoall(scale.reshape(size, 1))
    partial = q_t.astype(jnp.float32) * s_t
    mine = jnp.sum(partial, axis=0)
    q2, s2 = q._quantize(mine[None])
    q_all = allgather(q2[0])
    s_all = allgather(s2[0])
    full = (q_all.astype(jnp.float32) * s_all[:, None]).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape).astype(orig_dtype)


def test_packed_schedule_bit_compatible_with_two_leg():
    pytest.importorskip("jax")
    if not hasattr(q, "_quantized_schedule"):
        pytest.skip("standalone quantized module")
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    n = 3
    xs = [jnp.asarray((rng.randn(257) * (r + 1)).astype(np.float32))
          for r in range(n)]
    packed_out = _run_world(xs, q._quantized_schedule)
    two_leg_out = _run_world(xs, _two_leg_schedule)
    for r in range(n):
        np.testing.assert_array_equal(np.asarray(packed_out[r]),
                                      np.asarray(two_leg_out[r]))
    # and both approximate the exact sum within the documented bound
    exact = np.sum(np.stack([np.asarray(x) for x in xs]), axis=0,
                   dtype=np.float64)
    denom = max(np.max(np.abs(exact)), 1e-6)
    assert np.max(np.abs(np.asarray(packed_out[0]) - exact)) / denom < 3e-2
