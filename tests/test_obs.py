"""Observability subsystem (mpi4jax_tpu/obs): recorder ring semantics,
numpy-compatible percentiles, clock-offset merge ordering, the Chrome
trace schema, the profile CLI, the tuner's --from-trace backend, and —
against the real native transport on a size-1 loopback comm (no
sockets) — the event ring's overflow accounting and the test-enforced
guarantee that a disabled recorder performs NO ring writes.

Everything here runs under CPU-only tier-1: the pure-Python half is
loaded standalone (the package __init__ gates on the jax version; the
obs package is documented stdlib-importable), and the native half
drives a transport-only build of tpucomm.cc through ctypes directly.
"""

import ctypes
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_pkg(name, init_path, search_dir):
    spec = importlib.util.spec_from_file_location(
        name, init_path, submodule_search_locations=[str(search_dir)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_obs():
    try:
        from mpi4jax_tpu import obs

        return obs
    except ImportError:
        return _load_pkg("m4j_obs_test", REPO / "mpi4jax_tpu/obs/__init__.py",
                         REPO / "mpi4jax_tpu/obs")


def _load_file(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


obs = _load_obs()


def _ev(name, ts_us, dur_us=5.0, wait_us=1.0, nbytes=64, peer=-1, tag=0,
        algo=None, src="native"):
    return {"name": name, "src": src, "ts_us": float(ts_us),
            "dur_us": float(dur_us), "wait_us": float(wait_us),
            "bytes": nbytes, "peer": peer, "tag": tag, "algo": algo}


# ---------------- recorder ring ----------------


def test_ring_overflow_keeps_newest_with_exact_drop_count():
    r = obs.Recorder(16)
    for i in range(41):
        r.append({"i": i})
    kept = [e["i"] for e in r.snapshot()]
    assert kept == list(range(25, 41))  # newest 16, oldest first
    assert r.dropped == 25  # exact, not approximate


def test_ring_no_overflow_reports_zero_drops():
    r = obs.Recorder(16)
    for i in range(7):
        r.append({"i": i})
    assert [e["i"] for e in r.snapshot()] == list(range(7))
    assert r.dropped == 0


# ---------------- percentile math ----------------


def test_percentiles_match_numpy_on_fixed_corpus():
    rng = np.random.RandomState(7)
    corpus = list(rng.gamma(2.0, 50.0, size=211))  # latency-shaped
    for q in (0, 12.5, 50, 90, 95, 99, 99.9, 100):
        assert obs.percentile(corpus, q) == pytest.approx(
            float(np.percentile(corpus, q)), abs=1e-9), q
    # degenerate corpora
    assert obs.percentile([], 50) == 0.0
    assert obs.percentile([3.5], 99) == 3.5


def test_stats_aggregates_per_op_peer_algo():
    events = [
        _ev("Allreduce", 0, dur_us=100, wait_us=40, nbytes=1024, algo="ring"),
        _ev("Allreduce", 200, dur_us=300, wait_us=60, nbytes=1024,
            algo="ring"),
        _ev("Send", 400, dur_us=10, wait_us=0, nbytes=64, peer=1, tag=7),
    ]
    stats = obs.summarize(events, dropped={"native": 3})
    rows = {(r["op"], r["algo"]): r for r in stats["per_op"]}
    ar = rows[("Allreduce", "ring")]
    assert ar["count"] == 2
    assert ar["bytes"] == 2048
    assert ar["p50_us"] == pytest.approx(200.0)
    assert ar["wait_frac"] == pytest.approx(0.25)  # 100us wait / 400us
    assert ar["eff_GBps"] == pytest.approx(2048 / 400e-6 / 1e9, rel=1e-3)
    assert rows[("Send", "-")]["peer"] == 1
    assert stats["dropped"] == {"native": 3}


def test_stats_keeps_native_and_ops_views_of_one_call_separate():
    """The native ring and the ops-layer span record the SAME call from
    two vantage points — they must aggregate as separate rows, never
    double-count (src is part of the grouping key)."""
    events = [
        _ev("Send", 100, dur_us=10, wait_us=2, nbytes=64, peer=1,
            src="native"),
        _ev("Send", 99, dur_us=30, wait_us=0, nbytes=64, peer=1,
            src="ops"),
    ]
    stats = obs.summarize(events)
    rows = {r["src"]: r for r in stats["per_op"]}
    assert set(rows) == {"native", "ops"}
    assert rows["native"]["count"] == 1 and rows["ops"]["count"] == 1
    assert rows["native"]["bytes"] == 64  # not 128: no double-count
    assert rows["native"]["wait_frac"] == pytest.approx(0.2)


# ---------------- clock-offset merge ----------------


def test_clock_offset_merge_orders_two_rank_sequence():
    """Rank 1's local clock runs 5 ms ahead; the recorded offsets must
    put its events back into true order in the merged timeline."""
    rec = obs._recorder
    # rank 0: true clock, no offset
    rec.start(lib=None, rank=0, size=2, clock_offset_s=0.0)
    rec.record_span("Send", 1.000100, 10e-6, peer=1, nbytes=64, tag=7)
    rec.record_span("Barrier", 1.000300, 5e-6)
    part0 = {"rank": 0, "size": 2, "dropped": rec.dropped(),
             "events": rec.events()}
    # rank 1: its unix clock reads 5 ms ahead of true; the alignment
    # handshake estimated -5 ms for it
    rec.start(lib=None, rank=1, size=2, clock_offset_s=-0.005)
    rec.record_span("Recv", 1.005150, 10e-6, peer=0, nbytes=64, tag=7)
    rec.record_span("Barrier", 1.005320, 5e-6)
    part1 = {"rank": 1, "size": 2, "dropped": rec.dropped(),
             "events": rec.events()}
    rec.stop()

    merged = obs.merge_parts([part1, part0])
    assert obs.validate_chrome_trace(merged) == []
    spans = [(e["name"], e["pid"]) for e in merged["traceEvents"]
             if e["ph"] == "X" and e.get("cat") != "phase"]
    assert spans == [("Send", 0), ("Recv", 1), ("Barrier", 0),
                     ("Barrier", 1)], spans
    # without the offset the recv (local 1.005150) would sort after
    # EVERY rank-0 event — prove the alignment actually moved it
    recv = next(e for e in merged["traceEvents"]
                if e["ph"] == "X" and e["name"] == "Recv")
    assert recv["ts"] == pytest.approx(1.000150 * 1e6, abs=1.0)


def test_chrome_trace_export_and_validation():
    events = [_ev("Allreduce", 100, dur_us=50, wait_us=20, nbytes=4096,
                  algo="rd"),
              _ev("Send", 200, dur_us=8, wait_us=0, peer=2, tag=5,
                  src="ops")]
    trace = obs.merge_parts([{"rank": 0, "size": 1, "events": events,
                              "dropped": {"native": 0}}])
    assert obs.validate_chrome_trace(trace) == []
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    ar = next(e for e in spans if e["name"] == "Allreduce")
    assert ar["args"]["bytes"] == 4096
    assert ar["args"]["algo"] == "rd"
    assert ar["args"]["wait_us"] == pytest.approx(20.0)
    assert ar["tid"] == 0  # native transport thread
    # the wait/wire phase split renders as nested child slices
    names = {e["name"] for e in spans}
    assert {"wait", "wire"} <= names
    wait = next(e for e in spans if e["name"] == "wait")
    assert wait["dur"] == pytest.approx(20.0)
    # ops-layer spans land on their own thread row, no phase children
    send = next(e for e in spans if e["name"] == "Send")
    assert send["tid"] == 1
    # validator actually rejects malformed traces
    assert obs.validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    assert obs.validate_chrome_trace([1, 2])
    assert obs.validate_chrome_trace(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 0, "tid": 0,
                          "ts": 1.0, "dur": -4.0}]})


def test_serving_phase_label_roundtrips_chrome_to_canonical(tmp_path):
    """The serving plane's ``phase`` field (prefill/decode/kv_xfer) is
    additive: labeled spans carry it through stats grouping, the Chrome
    export, and the chrome->canonical loader; unlabeled events keep the
    exact pre-serving schema (no phase key anywhere)."""
    labeled = _ev("serve.decode", 100, dur_us=40, wait_us=0, nbytes=256,
                  src="ops")
    labeled["phase"] = "decode"
    plain = _ev("Allreduce", 200, dur_us=50, wait_us=10, nbytes=4096,
                algo="rd")
    # stats: phase splits the group key and lands on the row — only
    # for labeled spans
    two_phases = dict(labeled, phase="prefill")
    stats = obs.summarize([labeled, plain, two_phases])
    rows = {r.get("phase", "-"): r for r in stats["per_op"]
            if r["op"] == "serve.decode"}
    assert set(rows) == {"decode", "prefill"}
    flat = next(r for r in stats["per_op"] if r["op"] == "Allreduce")
    assert "phase" not in flat
    # chrome export carries it in args; the loader restores it
    trace = obs.merge_parts([{"rank": 0, "size": 1,
                              "events": [labeled, plain]}])
    assert obs.validate_chrome_trace(trace) == []
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(trace))
    events, _ = obs.load_events(str(path))
    by_name = {e["name"]: e for e in events}
    assert by_name["serve.decode"]["phase"] == "decode"
    assert "phase" not in by_name["Allreduce"]
    # part-file round trip preserves it too (parts store canonical form)
    base = str(tmp_path / "part.json")
    obs.write_part(base, rank=0, size=1, events=[labeled])
    loaded, _ = obs.load_events(obs.part_paths(base)[0])
    assert loaded[0]["phase"] == "decode"


# ---------------- dump files + profile CLI ----------------


def _write_two_rank_parts(base):
    events0 = [_ev("Allreduce", 100 + 300 * i, dur_us=100 + i, nbytes=1024,
                   algo="tree") for i in range(4)]
    events0 += [_ev("Allreduce", 2000 + 300 * i, dur_us=40 + i, nbytes=1024,
                    algo="rd") for i in range(4)]
    events0 += [_ev("Allreduce", 4000 + 9000 * i, dur_us=8000 + i,
                    nbytes=1 << 20, algo="tree") for i in range(3)]
    events0 += [_ev("Allreduce", 40000 + 9000 * i, dur_us=2500 + i,
                    nbytes=1 << 20, algo="ring") for i in range(3)]
    events0 += [_ev("Allgather", 80000, dur_us=60, nbytes=4096, algo="ring")]
    obs.write_part(base, rank=0, size=3, events=events0,
                   dropped={"native": 0, "ops": 0})
    obs.write_part(base, rank=1, size=3, events=events0,
                   dropped={"native": 2, "ops": 0})
    return obs.part_paths(base)


def test_load_events_rejects_future_part_version(tmp_path):
    path = tmp_path / "future.rank0.json"
    path.write_text(json.dumps({"version": 99, "rank": 0, "size": 2,
                                "events": [], "dropped": {}}))
    with pytest.raises(ValueError, match="version"):
        obs.load_part(str(path))
    # the fallback loader must not quietly read a future format with
    # v1 semantics either (profile report's error path relies on this)
    with pytest.raises(ValueError, match="version"):
        obs.load_events(str(path))


def test_part_dump_roundtrip_and_rank_globbing(tmp_path):
    base = str(tmp_path / "out.json")
    parts = _write_two_rank_parts(base)
    assert [obs.load_part(p)["rank"] for p in parts] == [0, 1]
    part = obs.load_part(parts[1])
    assert part["size"] == 3 and part["dropped"]["native"] == 2
    events, world = obs.load_events(parts[0])
    assert world == 3 and len(events) == 15


def test_profile_cli_report_and_merge(tmp_path, capsys):
    profile = _load_file("m4j_profile_test", REPO / "mpi4jax_tpu/profile.py")
    base = str(tmp_path / "out.json")
    parts = _write_two_rank_parts(base)
    assert profile.main(["merge", "--out", base, *parts]) == 0
    merged = json.load(open(base))
    assert obs.validate_chrome_trace(merged) == []
    assert merged["otherData"]["world_size"] == 3
    # report renders the per-op/per-algo table from the same recordings
    assert profile.main(["report", *parts]) == 0
    out = capsys.readouterr().out
    assert "Allreduce" in out and "ring" in out and "p99_us" in out
    assert "2 dropped" in out
    # report also reads the merged trace, and --json emits obs.stats
    assert profile.main(["report", base, "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["schema"] == obs.STATS_SCHEMA
    assert any(r["op"] == "Allgather" for r in stats["per_op"])
    # bad input fails loudly, not silently
    assert profile.main(["report", str(tmp_path / "missing.json")]) == 2


# ---------------- tuner feedback (--from-trace) ----------------


def _load_tune():
    try:
        from mpi4jax_tpu import tune

        return tune
    except ImportError:
        return _load_file("m4j_tune_obs_test",
                          REPO / "mpi4jax_tpu/tune/__init__.py")


def test_from_trace_derives_loadable_algorithm_cache(tmp_path):
    tune = _load_tune()
    base = str(tmp_path / "out.json")
    parts = _write_two_rank_parts(base)
    cache = str(tmp_path / "tune_cache.json")
    written = tune.cache_from_trace(parts, cache_path_override=cache)
    assert written == cache
    data = json.load(open(cache))
    assert data["world_size"] == 3
    assert data["transport"] == "tcp:from-trace"
    # the best MEDIAN observed algorithm wins per size bucket: rd at
    # 1 KB; at 1 MB ring wins AND its recorded wire share dominates
    # (dur >> wait), so the row is promoted to the quantized twin —
    # the wire is the bottleneck there, shrinking frames is the lever
    assert data["table"]["allreduce"] == [[0, "rd"], [1 << 20, "qring"]]
    assert data["table"]["allgather"] == [[0, "ring"]]
    assert any(m["source"] == "trace" for m in data["measurements"])
    promo = [m for m in data["measurements"]
             if m.get("source") == "trace:quant-promotion"]
    assert promo and promo[0]["promoted_from"] == "ring"
    assert promo[0]["wire_frac"] >= tune.QUANT_PROMOTE_WIRE_FRAC
    # exactly what bridge.comm_init loads at communicator creation
    loaded = tune.load_cache(3, path=cache)
    assert loaded["allreduce"] == [(0, "rd"), (1 << 20, "qring")]
    # the exact-only escape hatch (tune --from-trace --no-quantize)
    cache2 = str(tmp_path / "tune_cache_exact.json")
    tune.cache_from_trace(parts, cache_path_override=cache2,
                          quantize=False)
    data2 = json.load(open(cache2))
    assert data2["table"]["allreduce"] == [[0, "rd"], [1 << 20, "ring"]]


def test_from_trace_rejects_recordings_without_tcp_signal(tmp_path):
    tune = _load_tune()
    base = str(tmp_path / "shm.json")
    # an arena-served run: every collective is labeled shm — no TCP
    # algorithm evidence, must refuse rather than write a noise cache
    obs.write_part(base, rank=0, size=2,
                   events=[_ev("Allreduce", 0, nbytes=1024, algo="shm")],
                   dropped={})
    with pytest.raises(ValueError, match="no TCP-path collective"):
        tune.cache_from_trace(obs.part_paths(base))


def test_bench_record_is_field_compatible():
    rec = obs.bench_record(op="allreduce", nbytes=1 << 20, seconds=0.002,
                           ranks=4, tier="world", algo="ring", reps=10)
    # the canonical keys every benchmark artifact and report shares
    assert rec["op"] == "allreduce" and rec["bytes"] == 1 << 20
    assert rec["seconds"] == 0.002 and rec["us"] == pytest.approx(2000.0)
    assert rec["eff_GBps_per_chip"] == pytest.approx(
        1.5 * (1 << 20) / 0.002 / 1e9, rel=1e-3)
    assert rec["ranks"] == 4 and rec["algo"] == "ring" and rec["reps"] == 10
    solo = obs.bench_record(op="memcpy", nbytes=100, seconds=1.0)
    assert solo["eff_GBps_per_chip"] == pytest.approx(100 / 1e9)


# ---------------- native event ring (real transport, no sockets) -----


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    """Transport-only build of native/tpucomm.cc, driven via ctypes on a
    size-1 comm — the self-delivery path needs no sockets, so this runs
    under CPU-only tier-1 in any container with a C++ toolchain."""
    cxx = os.environ.get("CXX", "g++")
    if shutil.which(cxx) is None:
        pytest.skip(f"no C++ compiler ({cxx}) available")
    so = tmp_path_factory.mktemp("obs_native") / "libtpucomm_obs.so"
    src = REPO / "native" / "tpucomm.cc"
    res = subprocess.run(
        [cxx, "-O1", "-std=c++17", "-fPIC", "-Wall", "-pthread", "-shared",
         "-o", str(so), str(src), "-lrt"],
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, f"native build failed:\n{res.stderr[-2000:]}"
    lib = ctypes.CDLL(str(so))
    lib.tpucomm_init.restype = ctypes.c_int64
    lib.tpucomm_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_char_p]
    h = lib.tpucomm_init(0, 1, 47299, b"")
    assert h > 0, "size-1 comm init failed"
    yield lib, h
    lib.tpucomm_finalize(ctypes.c_int64(h))


def _native_mod():
    try:
        from mpi4jax_tpu.obs import _native

        return _native
    except ImportError:
        return _load_file("m4j_obs_native_test",
                          REPO / "mpi4jax_tpu/obs/_native.py")


def _self_send_recv(lib, h, tag):
    buf = np.arange(8.0)
    out = np.empty_like(buf)
    p = lambda a: a.ctypes.data_as(ctypes.c_void_p)  # noqa: E731
    rc = lib.tpucomm_send(ctypes.c_int64(h), p(buf),
                          ctypes.c_int64(buf.nbytes), 0, tag)
    assert rc == 0
    rc = lib.tpucomm_recv(ctypes.c_int64(h), p(out),
                          ctypes.c_int64(out.nbytes), 0, tag)
    assert rc == 0
    np.testing.assert_array_equal(out, buf)


def test_native_disabled_fast_path_writes_nothing(native_lib):
    """THE zero-cost contract: with recording off, transport ops perform
    no event-ring writes at all (test-enforced)."""
    lib, h = native_lib
    nat = _native_mod()
    assert nat.available(lib)
    nat.disable(lib)
    for tag in range(20, 25):
        _self_send_recv(lib, h, tag)
    buf = np.arange(8.0)
    out = np.empty_like(buf)
    lib.tpucomm_allreduce(ctypes.c_int64(h),
                          buf.ctypes.data_as(ctypes.c_void_p),
                          out.ctypes.data_as(ctypes.c_void_p),
                          ctypes.c_int64(8), 12, 0)
    held, dropped = nat.counts(lib)
    assert held == 0 and dropped == 0
    assert nat.drain(lib) == []


def test_native_ring_records_ops_with_fields(native_lib):
    lib, h = native_lib
    nat = _native_mod()
    nat.enable(lib, 64)
    _self_send_recv(lib, h, 42)
    buf = np.arange(8.0)
    out = np.empty_like(buf)
    rc = lib.tpucomm_allreduce(ctypes.c_int64(h),
                               buf.ctypes.data_as(ctypes.c_void_p),
                               out.ctypes.data_as(ctypes.c_void_p),
                               ctypes.c_int64(8), 12, 0)  # f64 SUM
    assert rc == 0
    events = nat.drain(lib)
    nat.disable(lib)
    names = [e["name"] for e in events]
    assert names == ["Send", "Recv", "Allreduce"]
    send = events[0]
    assert send["peer"] == 0 and send["tag"] == 42 and send["bytes"] == 64
    assert 0 <= send["wait_s"] <= send["dur_s"]
    ar = events[2]
    assert ar["bytes"] == 64 and ar["peer"] == -1
    assert all(e["t"] <= n["t"] for e, n in zip(events, events[1:]))


def test_native_events_carry_dispatch_phase(native_lib):
    """Every drained event reports queue_s (the post -> native-start
    dispatch delay); a detached self-send (queued on the progress
    engine) records a positive one, and the phases always fit inside
    the op: queue + wait <= dur."""
    lib, h = native_lib
    nat = _native_mod()
    nat.enable(lib, 64)
    for tag in range(60, 64):
        _self_send_recv(lib, h, tag)
    events = nat.drain(lib)
    nat.disable(lib)
    assert events and all("queue_s" in e for e in events)
    for e in events:
        assert 0.0 <= e["queue_s"] <= e["dur_s"] + 1e-12, e
        assert e["queue_s"] + e["wait_s"] <= e["dur_s"] + 1e-9, e
    sends = [e for e in events if e["name"] == "Send"]
    assert any(e["queue_s"] > 0.0 for e in sends), (
        "no queued (detached) send recorded a dispatch delay")


def test_stats_and_trace_carry_dispatch_split():
    """dispatch_us flows from canonical events into obs.stats rows
    (dispatch_frac) and the Chrome trace (args + a nested dispatch
    phase slice ahead of wait/wire)."""
    ev = _ev("Send", 100, dur_us=50, wait_us=10, peer=1)
    ev["dispatch_us"] = 15.0
    stats = obs.summarize([ev])
    row = stats["per_op"][0]
    assert row["dispatch_frac"] == pytest.approx(0.3)
    assert row["wait_frac"] == pytest.approx(0.2)
    trace = obs.merge_parts([{"rank": 0, "size": 1, "events": [ev],
                              "dropped": {}}])
    assert obs.validate_chrome_trace(trace) == []
    spans = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert spans["Send"]["args"]["dispatch_us"] == pytest.approx(15.0)
    assert spans["dispatch"]["dur"] == pytest.approx(15.0)
    assert spans["wait"]["ts"] == pytest.approx(spans["dispatch"]["ts"]
                                                + 15.0)
    assert spans["wire"]["dur"] == pytest.approx(25.0)


def test_native_ring_overflow_keeps_newest_exact_drops(native_lib):
    lib, h = native_lib
    nat = _native_mod()
    nat.enable(lib, 16)
    total = 30  # 15 send+recv pairs
    for i in range(total // 2):
        _self_send_recv(lib, h, 1000 + i)
    held, dropped = nat.counts(lib)
    assert held == 16
    assert dropped == total - 16  # exact drop accounting
    events = nat.drain(lib)
    assert len(events) == 16
    # the kept events are the NEWEST 16, oldest-first
    tags = [e["tag"] for e in events]
    assert tags == [1000 + (total - 16 + i) // 2 for i in range(16)]
    # drain clears held events but the drop counter survives
    held2, dropped2 = nat.counts(lib)
    assert held2 == 0 and dropped2 == total - 16
    nat.disable(lib)


def test_native_partial_drain_counts_undelivered_as_dropped(native_lib):
    """A drain whose buffer is smaller than the held count (events can
    arrive between the count probe and the drain) must COUNT what it
    discards — the exact-drop-accounting contract."""
    lib, h = native_lib
    nat = _native_mod()
    nat.enable(lib, 32)
    for i in range(5):
        _self_send_recv(lib, h, 300 + i)  # 10 events held
    buf = (nat.TpuObsEvent * 4)()
    got = lib.tpucomm_obs_drain(buf, ctypes.c_int64(4))
    assert got == 4
    # the 4 delivered are the NEWEST, oldest-first
    assert [buf[i].tag for i in range(4)] == [303, 303, 304, 304]
    held, dropped = nat.counts(lib)
    assert held == 0
    assert dropped == 6  # the 6 undelivered events were counted
    nat.disable(lib)


def test_native_disable_after_enable_stops_recording(native_lib):
    lib, h = native_lib
    nat = _native_mod()
    nat.enable(lib, 16)
    _self_send_recv(lib, h, 7)
    nat.disable(lib)
    _self_send_recv(lib, h, 8)
    held, dropped = nat.counts(lib)
    assert held == 0 and dropped == 0


# ------- end-to-end: launcher --trace over the real transport --------
#
# The launcher runs as a plain FILE and the ranks import the runtime
# through a parent-package shim that skips mpi4jax_tpu/__init__.py, so
# this full multi-process path — comm init, clock-alignment handshake,
# native recording, per-rank dump at exit, launcher merge — runs under
# CPU-only tier-1 even where the package's jax-version gate blocks the
# normal import (the ops layer is not involved at bridge level).

_RANK_PROG = r"""
import os, sys, types
REPO = %r
sys.path.insert(0, REPO)
pkg = types.ModuleType("mpi4jax_tpu")
pkg.__path__ = [os.path.join(REPO, "mpi4jax_tpu")]
sys.modules["mpi4jax_tpu"] = pkg
import numpy as np
from mpi4jax_tpu.runtime import bridge, transport

c = transport.get_world_comm()
h = c.handle  # comm init: transport mesh + obs install (TRACE is set)
r, n = c.rank(), c.size()
out = bridge.allreduce(h, np.arange(1024.0), 0)  # SUM
assert abs(float(out[1]) - n) < 1e-9, out[1]
got = bridge.sendrecv(h, np.full(8, float(r)), (8,), np.float64,
                      (r - 1) %% n, (r + 1) %% n, 5)
assert float(got[0]) == float((r - 1) %% n), got
big = bridge.allreduce(h, np.ones(1 << 18), 0)  # 2 MB: ring territory
assert abs(float(big[0]) - n) < 1e-9
bridge.barrier(h)
print("bridge_trace OK", flush=True)
"""


@pytest.mark.parametrize("np_", [3])
def test_launch_trace_end_to_end_bridge_level(tmp_path, np_):
    repo = str(REPO)
    # prebuild the native lib once so the ranks don't compile 3x
    pre = subprocess.run(
        [sys.executable, "-c",
         "import sys, types, os; sys.path.insert(0, %r);"
         "pkg = types.ModuleType('mpi4jax_tpu');"
         "pkg.__path__ = [os.path.join(%r, 'mpi4jax_tpu')];"
         "sys.modules['mpi4jax_tpu'] = pkg;"
         "from mpi4jax_tpu.runtime import bridge; bridge.get_lib();"
         "print('prebuilt')" % (repo, repo)],
        capture_output=True, text=True, timeout=300,
    )
    assert pre.returncode == 0, pre.stderr[-2000:]

    prog = tmp_path / "bridge_trace_prog.py"
    prog.write_text(_RANK_PROG % repo)
    out = tmp_path / "trace.json"
    # a stale part from an earlier, wider run at the same path must not
    # leak into this run's merge (the launcher clears them pre-spawn)
    stale = tmp_path / "trace.json.rank7.json"
    stale.write_text(json.dumps({"version": 1, "rank": 7, "size": 8,
                                 "events": [], "dropped": {}}))
    env = dict(os.environ)
    env["MPI4JAX_TPU_DISABLE_SHM"] = "1"  # record real TCP algorithms
    res = subprocess.run(
        [sys.executable, str(REPO / "mpi4jax_tpu/runtime/launch.py"),
         "-n", str(np_), "--port", "26610", "--trace", str(out),
         str(prog)],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo,
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("bridge_trace OK") == np_
    assert "[obs] recording written to" in res.stderr
    assert f"merged {np_}/{np_} rank recording(s)" in res.stderr, \
        res.stderr[-2000:]

    assert not stale.exists(), "stale pre-run part survived the launcher"
    parts = obs.part_paths(str(out))
    assert len(parts) == np_
    merged = json.loads(out.read_text())
    assert obs.validate_chrome_trace(merged) == []
    assert merged["otherData"]["world_size"] == np_
    spans = [e for e in merged["traceEvents"]
             if e["ph"] == "X" and e.get("cat") != "phase"]
    assert {e["pid"] for e in spans} == set(range(np_))  # EVERY rank
    ar = [e for e in spans if e["name"] == "Allreduce"]
    assert len(ar) >= 2 * np_  # small + big per rank
    assert all(e["args"]["bytes"] > 0 for e in ar)
    assert any(e["args"].get("algo") in ("ring", "rd", "tree")
               for e in ar), [e["args"] for e in ar[:4]]
    sr = [e for e in spans if e["name"] == "Sendrecv"]
    assert any(e["args"]["peer"] >= 0 for e in sr)
    # cross-rank alignment: every rank recorded a clock offset field
    for p in parts:
        assert "clock_offset_us" in obs.load_part(p)
    # wait/transfer split present in the merged timeline
    assert any(e.get("cat") == "phase" and e["name"] == "wait"
               for e in merged["traceEvents"])

    # the recorded run feeds the tuner: a loadable cache comes out
    tune = _load_tune()
    cache = str(tmp_path / "cache.json")
    tune.cache_from_trace(parts, cache_path_override=cache)
    data = json.load(open(cache))
    assert data["world_size"] == np_
    assert all(e[1] in ("ring", "rd", "tree", "qring", "qrd")
               for op in data["table"] for e in data["table"][op])
