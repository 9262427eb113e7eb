"""World-tier integration: run the per-rank programs under the launcher.

The reference runs its suite twice (pytest / mpirun -np 2 pytest,
docs/developers.rst there); here the multi-process half is driven from
pytest via the bundled launcher, at np=2 and np=4.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAMS = os.path.join(REPO, "tests", "world_programs")

# Test ports sit below Linux's ephemeral range (32768-60999): a rank
# listening there can collide with another world's outgoing connection.
_port = [24100]


def run_launcher(program, np_, timeout=180, env_extra=None, extra_args=(),
                 prog_args=(), prog_dir=None):
    _port[0] += np_ + 3  # unique ports per invocation
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # ranks don't need virtual devices
    env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [
            sys.executable, "-m", "mpi4jax_tpu.runtime.launch",
            "-n", str(np_), "--port", str(_port[0]), *extra_args,
            os.path.join(prog_dir or PROGRAMS, program), *prog_args,
        ],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("np_", [2, 4])
def test_basic_ops(np_):
    res = run_launcher("basic_ops.py", np_)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("basic_ops OK") == np_


def test_neighbor_exchange():
    # one-op bidirectional ring/chain exchange at np=3 — the smallest
    # ring where pairwise bidirectional scheduling deadlocks
    res = run_launcher("neighbor_ops.py", 3)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("neighbor_ops OK") == 3


def test_shm_chunked_pieces():
    # 1 MB slots against 4-6 MB payloads: every collective exercises its
    # multi-piece loop (incl. scatter/alltoall divided-slot budgets)
    res = run_launcher(
        "shm_chunked.py", 2, timeout=300,
        # pin the arena ON: the whole-suite tcp axis (DISABLE_SHM=1 in
        # CI env) must not turn the shm tests into trivial TCP reruns
        env_extra={"MPI4JAX_TPU_SHM_MB": "1", "MPI4JAX_TPU_DISABLE_SHM": ""},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("shm_chunked OK") == 2


def test_shm_ring_stub_path():
    # 4 KB rings force every payload over 1 KB (ring/4) through the
    # stub-in-ring + TCP-payload path — ordering spine and large-message
    # degradation both exercised by the full op battery
    res = run_launcher(
        "full_ops.py", 2, timeout=300,
        env_extra={"MPI4JAX_TPU_SHM_RING_KB": "4",
                   "MPI4JAX_TPU_DISABLE_SHM": ""},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 2


def test_shm_p2p_disabled_axis():
    # p2p kill switch: collectives stay on the arena, point-to-point
    # falls back to TCP — numerics identical
    res = run_launcher(
        "full_ops.py", 2, timeout=300,
        env_extra={"MPI4JAX_TPU_DISABLE_SHM_P2P": "1",
                   "MPI4JAX_TPU_DISABLE_SHM": ""},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 2


def test_shm_disabled_tcp_path():
    # collectives fall back to the framed TCP schedules under the shm
    # kill switch — numerics must be identical (CI axis for the arena)
    res = run_launcher(
        "full_ops.py", 2, timeout=300,
        env_extra={"MPI4JAX_TPU_DISABLE_SHM": "1"},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 2


@pytest.mark.parametrize("np_,shm", [(4, "on"), (4, "off"), (3, "off")])
def test_coll_algo_equivalence(np_, shm):
    # cross-algorithm equivalence (ring/rd/tree x {f32,i32,bf16} x
    # {SUM,MAX} vs the default path), under the arena and under
    # DISABLE_SHM=1; np=3 exercises the non-power-of-two rd fold
    env = {"MPI4JAX_TPU_DISABLE_SHM": "1" if shm == "off" else ""}
    res = run_launcher("coll_algo_ops.py", np_, timeout=300, env_extra=env)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("coll_algo_ops OK") == np_


def test_coll_algo_forced_ring_axis():
    # the forced-`ring` suite axis (mirror of the DISABLE_SHM=1 axis):
    # the full op battery must hold with every allreduce/allgather
    # forced onto the ring schedules over TCP
    res = run_launcher(
        "full_ops.py", 4, timeout=300,
        env_extra={"MPI4JAX_TPU_COLL_ALGO": "ring",
                   "MPI4JAX_TPU_DISABLE_SHM": "1"},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 4


def test_tune_cli_smoke(tmp_path):
    # the offline autotuner end to end: the CLI sweeps algorithms at
    # np=4, writes a well-formed cache, and a SUBSEQUENT run loads and
    # honors it (algo_report prints the engine's live picks, and debug
    # tracing names the algorithm on the wire)
    import json

    cache = tmp_path / "tune_4.json"
    _port[0] += 9
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MPI4JAX_TPU_COLL_ALGO", None)
    res = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.tune", "--np", "4",
         "--port", str(_port[0]), "--sizes", "1024,262144",
         "--repeats", "3", "--cache", str(cache)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert cache.exists(), res.stdout

    data = json.loads(cache.read_text())
    assert data["version"] == 2 and data["world_size"] == 4
    for op in ("allreduce", "allgather"):
        entries = data["table"][op]
        assert entries and entries[0][0] == 0
        assert all(e[1] in ("ring", "rd", "tree", "qring", "qrd")
                   for e in entries)
    assert data["measurements"], "tuner wrote no measurements"

    # round-trip through the loader, then honor-check on a live job
    from mpi4jax_tpu import tune

    try:
        table = tune.load_cache(4, path=str(cache))
    finally:
        # don't leak this cache into the pytest process's own engine state
        tune._cache_table = None
        tune._cache_origin = None
    expected = {}
    for nbytes in (1024, 262144):
        algo = "auto"
        for mb, name in table["allreduce"]:
            if nbytes >= mb:
                algo = name
        expected[nbytes] = algo
    res = run_launcher(
        "algo_report.py", 4, timeout=180,
        env_extra={"MPI4JAX_TPU_TUNE_CACHE": str(cache),
                   "MPI4JAX_TPU_DISABLE_SHM": "1",
                   "MPI4JAX_TPU_DEBUG": "1",
                   "ALGO_REPORT_SIZES": "1024,262144"},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("algo_report OK") == 4
    for nbytes, algo in expected.items():
        assert res.stdout.count(f"allreduce@{nbytes}={algo}") == 4, (
            res.stdout
        )
    assert res.stdout.count("sources=defaults+cache:") == 4
    # the native trace line names the algorithm that ran
    assert "algo " + expected[262144] in res.stderr, res.stderr[-2000:]


def test_foreign_launcher_env_adoption():
    # an mpirun-shaped environment (OMPI_COMM_WORLD_RANK/SIZE) with no
    # MPI4JAX_TPU_* vars must be adopted as the world job description —
    # the drop-in path for `mpirun -n 2 python prog.py` users
    # (reference README.rst:73-77)
    _port[0] += 7
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPI4JAX_TPU_COORD"] = f"127.0.0.1:{_port[0]}"
    procs = []
    for rank in range(2):
        e = dict(env)
        e["OMPI_COMM_WORLD_RANK"] = str(rank)
        e["OMPI_COMM_WORLD_SIZE"] = "2"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(PROGRAMS, "basic_ops.py")],
            env=e, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=180) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err + out
        assert "basic_ops OK" in out


@pytest.mark.parametrize("np_", [2, 4])
def test_mpi4py_comm_adoption(np_, tmp_path):
    # WorldComm.from_mpi (VERDICT r4 #6): plain processes holding
    # (simulated) mpi4py comms hand them over; bootstrap rides mpi4py,
    # data rides the native transport.  Covers COMM_WORLD, a
    # Split-derived subgroup, and composition with the framework's own
    # split.  Reference bar: any MPI.Comm as op param (utils.py:80-127).
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MPI4JAX_TPU_COORD", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["FAKE_MPI_DIR"] = str(tmp_path)
    env["FAKE_MPI_SIZE"] = str(np_)
    procs = []
    for rank in range(np_):
        e = dict(env)
        e["FAKE_MPI_RANK"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(PROGRAMS, "mpi_adopt.py")],
            env=e, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err + out
        assert "mpi_adopt OK" in out


def test_foreign_launcher_jobid_port_derivation():
    # two concurrent mpirun jobs on one host must not collide on the
    # rendezvous port (ADVICE r4): with no MPI4JAX_TPU_COORD set, the
    # default derives from the launcher's job-unique token — same jobid
    # -> same port (ranks rendezvous), different jobid -> different port
    import mpi4jax_tpu.runtime.transport as tr

    def coord_for(jobid):
        saved = dict(os.environ)
        for var in ("OMPI_MCA_ess_base_jobid", "PMIX_NAMESPACE",
                    "SLURM_JOB_ID", "PMI_JOBID", "PBS_JOBID", "LSB_JOBID",
                    "MPI4JAX_TPU_COORD"):
            os.environ.pop(var, None)
        if jobid is not None:
            os.environ["OMPI_MCA_ess_base_jobid"] = jobid
        try:
            return tr._default_coord()
        finally:
            os.environ.clear()
            os.environ.update(saved)

    assert coord_for("12345") == coord_for("12345")
    assert coord_for("12345") != coord_for("12346")
    assert coord_for(None) == "127.0.0.1:49817"

    # end to end: both ranks derive the same port from the jobid alone
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MPI4JAX_TPU_COORD", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["OMPI_MCA_ess_base_jobid"] = str(os.getpid())
    procs = []
    for rank in range(2):
        e = dict(env)
        e["OMPI_COMM_WORLD_RANK"] = str(rank)
        e["OMPI_COMM_WORLD_SIZE"] = "2"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(PROGRAMS, "basic_ops.py")],
            env=e, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        ))
    outs = [p.communicate(timeout=180) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err + out
        assert "basic_ops OK" in out


@pytest.mark.parametrize("np_", [2, 4])
def test_full_ops(np_):
    # the mesh tier's identity battery (dtype sweep, double transpose,
    # vmap, autodiff) executed as a world program — the analog of the
    # reference running its whole suite again under mpirun -np 2
    # (mpi-tests.yml:74-90 there)
    res = run_launcher("full_ops.py", np_, timeout=300)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == np_


def test_multihost_hosts_list():
    # non-loopback host table: rank 1 listens on the 127.0.0.2 alias and
    # rank 0 dials it there (the pod/DCN layout exercised via the local
    # alias range; previously the hosts plumbing had no caller — weak #5)
    res = run_launcher(
        "basic_ops.py", 2, extra_args=("--hosts", "127.0.0.1,127.0.0.2")
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("basic_ops OK") == 2


def test_tpu_ranks_each_get_one_chip(tmp_path):
    # --platform tpu with -n > 1: every rank sees only its own chip, as
    # a single-chip slice with its own libtpu port (the program never
    # imports jax, so this runs without a chip)
    prog = tmp_path / "chip_env.py"
    prog.write_text(
        "import os\n"
        "e = os.environ\n"
        "print('chip_env', e['MPI4JAX_TPU_RANK'], e['JAX_PLATFORMS'],"
        " e['TPU_VISIBLE_CHIPS'], e['TPU_PROCESS_BOUNDS'],"
        " e['TPU_PROCESS_PORT'], flush=True)\n")
    res = run_launcher("chip_env.py", 3, prog_dir=str(tmp_path),
                       extra_args=("--platform", "tpu"))
    assert res.returncode == 0, res.stderr + res.stdout
    lines = sorted(ln.split()[1:] for ln in res.stdout.splitlines()
                   if ln.startswith("chip_env"))
    assert lines == [[str(r), "tpu", str(r), "1,1,1", str(8476 + r)]
                     for r in range(3)]


@pytest.mark.parametrize("platforms,np_", [("tpu", 1), ("cpu", 4),
                                           ("cpu,tpu", 1)])
def test_tpu_rank_env_leaves_single_process_alone(platforms, np_):
    from mpi4jax_tpu.runtime.launch import tpu_rank_env

    assert tpu_rank_env(platforms, 0, np_) == {}


def test_hosts_list_length_mismatch():
    res = run_launcher(
        "basic_ops.py", 2, extra_args=("--hosts", "127.0.0.1")
    )
    assert res.returncode != 0
    assert "2 ranks" in res.stderr


@pytest.mark.parametrize("ffi", ["on", "off"])
def test_ffi_fast_path(ffi):
    # native custom calls used when available; callback fallback under the
    # kill switch — identical numerics either way.  The "on" case clears
    # the var explicitly so the test holds under a CI job that forces
    # callbacks mode globally ("" parses as false in utils/config.py).
    env = ({"MPI4JAX_TPU_DISABLE_FFI": "1"} if ffi == "off"
           else {"MPI4JAX_TPU_DISABLE_FFI": ""})
    res = run_launcher("ffi_path.py", 2, env_extra=env)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count(f"ffi_path OK (ffi={ffi})") == 2


def test_vmap_ops():
    res = run_launcher("vmap_ops.py", 2)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("vmap_ops OK") == 2


def test_ordering():
    res = run_launcher("ordering.py", 2)
    assert res.returncode == 0, res.stderr + res.stdout


@pytest.mark.parametrize("np_,grid,size", [
    (1, (1, 1), (64, 128)), (2, (1, 2), (64, 128)),
    (4, (2, 2), (64, 128)), (6, (2, 3), (66, 126)),
])
def test_sw_world_matches_mesh_solver(np_, grid, size):
    # the world-tier per-rank solver (explicit sendrecv halos over the
    # native transport — the reference's mpirun shape) must reproduce
    # the mesh-tier SPMD solver bit-for-nearly-bit; covers the
    # self-wrap (np=1), two-rank-ring (gx=2 periodic), and the >= 3
    # periodic ring whose naive pairwise schedule deadlocked (the
    # uniform-shift fix)
    res = run_launcher(
        "sw_world_rank.py", np_, timeout=300,
        prog_dir=os.path.join(REPO, "benchmarks"),
        prog_args=("--grid", str(grid[0]), str(grid[1]),
                   "--size", str(size[0]), str(size[1]),
                   "--days", "0.02", "--check"),
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert "sw_world CHECK OK" in res.stdout


@pytest.mark.parametrize("mode", ["fresh_token", "no_token"])
def test_broken_token_chain_fails_at_trace_time(mode):
    # chain guard (VERDICT r4 #8): a deliberately broken chain in
    # explicit-token mode dies at TRACE time under strict mode, never
    # reaching the transport (beats the reference, which can only
    # document the footgun — docs/sharp-bits.rst:6-34 there)
    res = run_launcher(
        "broken_chain.py", 2, timeout=120,
        env_extra={"MPI4JAX_TPU_STRICT_TOKENS": "1", "BROKEN_MODE": mode},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("CAUGHT AT TRACE TIME") == 2
    assert "UNREACHABLE" not in res.stdout


def test_mesh_world_composition():
    # tier composition: np=2 world ranks, each owning a 4-virtual-device
    # mesh — mesh psum inside shard_map + world ops in the same jitted
    # step, plus the asymmetric-chain torture (SURVEY §7 hard part 4)
    res = run_launcher(
        "mesh_world.py", 2, timeout=300,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("mesh_world OK") == 2


def test_subcomm_ops():
    # split/dup sub-communicators on a 2x2 rank grid (reference analog:
    # arbitrary mpi4py comms, comm.py:4-11 + sharp-bits there)
    res = run_launcher("subcomm_ops.py", 4)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("subcomm_ops OK") == 4


def test_status_ops():
    # status introspection on recv/sendrecv (reference
    # test_sendrecv.py:29-61): eager, jit, ANY_TAG, split tags, short
    # messages
    res = run_launcher("status_ops.py", 2)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("status_ops OK") == 2


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_fuzz_ops(seed):
    # randomized matched-op program, replayed against numpy — exercises
    # framing, eager/writer concurrency, self-queue, and wildcards in
    # combination (the generative big sibling of the ordering tortures)
    res = run_launcher("fuzz_ops.py", 2,
                       env_extra={"FUZZ_SEED": str(seed)})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("fuzz_ops OK") == 2


@pytest.mark.parametrize("seed", [3, 11])
def test_fuzz_ops_ring_boundary(seed):
    # same generative program against 4 KB p2p rings: payloads flip
    # between inline frames and stub+TCP constantly (inline cutoff
    # ring/4 = 1 KB sits inside the fuzz size range), and ring wrap
    # happens every few messages — the r5 rings' nastiest regime
    res = run_launcher("fuzz_ops.py", 2,
                       env_extra={"FUZZ_SEED": str(seed), "FUZZ_OPS": "80",
                                  "MPI4JAX_TPU_SHM_RING_KB": "4",
                                  "MPI4JAX_TPU_DISABLE_SHM": ""})
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("fuzz_ops OK") == 2


def test_wildcard_recv():
    # ANY_SOURCE receives at np=4, incl. mixed wildcard/directed ordering
    # (the reference's default recv source, recv.py:45 there)
    res = run_launcher("wildcard_recv.py", 4)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("wildcard_recv OK") == 4


def test_autodiff():
    res = run_launcher("autodiff.py", 2)
    assert res.returncode == 0, res.stderr + res.stdout


def test_abort_fail_fast():
    res = run_launcher("abort.py", 2, timeout=120)
    assert res.returncode != 0
    assert "UNREACHABLE" not in res.stdout
    assert "returned error code" in res.stderr


@pytest.mark.parametrize("mode", ["opcode", "reduce_op", "dtype"])
def test_shm_schedule_mismatch_aborts(mode):
    # the arena's per-op opword cross-check: ranks disagreeing on which
    # collective comes next — or on its dtype or reduce op at equal byte
    # counts (ADVICE r4 low) — must abort with a diagnostic naming both
    # opwords, not hang in a barrier or reduce divergently in silence
    res = run_launcher("shm_schedule_mismatch.py", 2, timeout=120,
                       env_extra={"MISMATCH_MODE": mode,
                                  "MPI4JAX_TPU_DISABLE_SHM": ""})
    assert res.returncode != 0
    assert res.stdout.count("warmup ok") == 2
    assert "UNREACHABLE" not in res.stdout
    assert ("schedule mismatch" in res.stderr
            or "returned error code" in res.stderr), res.stderr[-800:]


def test_tag_mismatch_aborts():
    res = run_launcher("tag_mismatch.py", 2, timeout=120)
    assert res.returncode != 0
    assert "UNREACHABLE\n" not in res.stdout
    assert "order violation" in res.stderr or "returned error code" in res.stderr


def test_flush_exit_no_deadlock():
    # reference regression: pending async comm at teardown must not hang
    res = run_launcher("flush_exit.py", 2, timeout=120)
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("dispatched, exiting") == 2


def test_debug_log_format():
    res = run_launcher(
        "ordering.py", 2, env_extra={"MPI4JAX_TPU_DEBUG": "1"}
    )
    assert res.returncode == 0, res.stderr + res.stdout
    # reference format: "r<rank> | <id8> | <Op> ..." with timing on exit
    import re

    lines = [l for l in res.stderr.splitlines() if re.match(r"^r\d+ \| ", l)]
    assert any("Send" in l for l in lines), res.stderr[:2000]
    assert any(
        re.search(r"done with code 0 \(\d+\.\d+ s\)", l) for l in lines
    )


def test_trace_records_and_merges_perfetto_timeline(tmp_path):
    """The observability acceptance path end to end: `launch --trace`
    on a 3-rank full-ops program produces one merged Perfetto-loadable
    trace with per-op spans from EVERY rank (bytes, peer/algorithm,
    wait/transfer phases); `profile report` renders the table from the
    same recordings; `tune --from-trace` derives a loadable algorithm
    cache from them."""
    import json

    from mpi4jax_tpu import obs, tune

    out = tmp_path / "trace.json"
    res = run_launcher(
        "full_ops.py", 3, timeout=600,
        extra_args=("--trace", str(out)),
        # TCP path: shm-arena events carry algo=shm, which is honest but
        # useless to the tuner; the acceptance run records real algorithms.
        # Host-callback route (the one TPU ranks take): the ops-layer
        # spans are recorded there, and the cpu FFI custom calls skip it
        env_extra={"MPI4JAX_TPU_DISABLE_SHM": "1",
                   "MPI4JAX_TPU_DISABLE_FFI": "1"},
    )
    assert res.returncode == 0, res.stderr + res.stdout
    assert res.stdout.count("full_ops OK") == 3
    assert "merged 3/3 rank recording(s)" in res.stderr, res.stderr[-2000:]

    parts = obs.part_paths(str(out))
    assert len(parts) == 3, parts
    merged = json.loads(out.read_text())
    assert obs.validate_chrome_trace(merged) == []
    spans = [e for e in merged["traceEvents"]
             if e["ph"] == "X" and e.get("cat") != "phase"]
    assert {e["pid"] for e in spans} == {0, 1, 2}  # every rank present
    native_ar = [e for e in spans
                 if e["name"] == "Allreduce" and e["cat"] == "native"]
    assert native_ar, "no native allreduce spans recorded"
    assert all(e["args"]["bytes"] > 0 for e in native_ar)
    assert any(e["args"].get("algo") in ("ring", "rd", "tree")
               for e in native_ar), native_ar[:3]
    # full_ops' point-to-point traffic is its sendrecv ring
    sends = [e for e in spans
             if e["name"] == "Sendrecv" and e["cat"] == "native"]
    assert any(e["args"]["peer"] >= 0 for e in sends)
    # the ops layer contributes labeled spans on its own thread row
    assert any(e["cat"] == "ops" and e["args"]["bytes"] > 0 for e in spans)
    # the wait/transfer split renders as nested phase slices
    phase_names = {e["name"] for e in merged["traceEvents"]
                   if e.get("cat") == "phase"}
    assert "wait" in phase_names

    # profile report renders the per-op/per-algo table from the dumps
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", REPO)
    env["JAX_PLATFORMS"] = "cpu"
    rep = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.profile", "report", *parts],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert rep.returncode == 0, rep.stderr
    assert "Allreduce" in rep.stdout and "wait_frac" in rep.stdout

    # tune --from-trace: recorded real-run timings -> loadable cache
    cache = tmp_path / "cache_from_trace.json"
    tn = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.tune",
         "--from-trace", f"{out}.rank*.json", "--cache", str(cache)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert tn.returncode == 0, tn.stderr + tn.stdout
    data = json.loads(cache.read_text())
    assert data["world_size"] == 3
    # full_ops records a quantized allreduce too: its qrd rows are
    # selectable names, gated at run time by MPI4JAX_TPU_COLL_QUANT
    assert all(e[1] in tune.TRACE_ALGOS
               for op in data["table"] for e in data["table"][op])
    try:
        table = tune.load_cache(3, path=str(cache))  # what comm_init loads
        assert table
    finally:
        tune._cache_table = None
        tune._cache_origin = None
