"""Topology subsystem unit tests: fake-host parsing, Topology shape /
fingerprint / leg split, the numpy schedule simulators, the
topology-keyed tune cache, the config knobs, and the obs tier split —
all process-local (no sockets, no launcher; the live-world coverage is
tests/world/test_topology.py)."""

import json
import os
import pathlib
import sys
import types

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_pkg_modules():
    """topo / tune / obs._stats / utils.config without the package
    __init__ (its jax gate blocks old-jax containers; every module
    here is jax-free by design).  The fallback loads them under an
    ALIAS root instead of registering a bare ``mpi4jax_tpu`` in
    sys.modules — a leaked synthetic package would make other test
    modules' import-gate probes succeed spuriously in-process."""
    try:
        from mpi4jax_tpu import topo, tune
        from mpi4jax_tpu.obs import _stats
        from mpi4jax_tpu.utils import config

        return topo, tune, _stats, config
    except ImportError:
        import importlib

        alias = "m4j_topo_tests_pkg"
        if alias not in sys.modules:
            pkg = types.ModuleType(alias)
            pkg.__path__ = [str(REPO / "mpi4jax_tpu")]
            sys.modules[alias] = pkg
        topo = importlib.import_module(alias + ".topo")
        tune = importlib.import_module(alias + ".tune")
        _stats = importlib.import_module(alias + ".obs._stats")
        config = importlib.import_module(alias + ".utils.config")
        return topo, tune, _stats, config


topo, tune, _stats, config = _load_pkg_modules()


# ---------------- parse_fake_hosts ----------------

def test_parse_fake_hosts_groups_and_bare_tokens():
    labels = topo.parse_fake_hosts("r0,r1|r2,r3", 4)
    assert labels == ["fake-host-0", "fake-host-0",
                      "fake-host-1", "fake-host-1"]
    assert topo.parse_fake_hosts("0 , 1 | 2", 3) == [
        "fake-host-0", "fake-host-0", "fake-host-1"]


def test_parse_fake_hosts_unlisted_and_out_of_range():
    # unlisted ranks keep their real host (None); a spec written for a
    # larger world stays valid on a shrunk one (out-of-range ignored)
    assert topo.parse_fake_hosts("r0|r2", 4) == [
        "fake-host-0", None, "fake-host-1", None]
    assert topo.parse_fake_hosts("r0,r1|r2", 2) == [
        "fake-host-0", "fake-host-0"]


def test_parse_fake_hosts_rejects_garbage_and_duplicates():
    assert topo.parse_fake_hosts("", 4) is None
    assert topo.parse_fake_hosts(None, 4) is None
    with pytest.raises(ValueError):
        topo.parse_fake_hosts("r0,banana", 4)
    with pytest.raises(ValueError):
        topo.parse_fake_hosts("r0|r0", 4)


# ---------------- Topology ----------------

def _fp(host, fake=None, tpu=0):
    return {"v": 1, "host": host, "boot_id": "b", "fake": fake,
            "tpu_chips": tpu}


def test_topology_islands_leaders_and_ordering():
    t = topo.Topology([_fp("a"), _fp("a"), _fp("b"), _fp("b"), _fp("a")])
    assert t.islands == [[0, 1, 4], [2, 3]]
    assert t.island_of == [0, 0, 1, 1, 0]
    assert t.leaders == [0, 2]
    assert t.multi and t.n_islands == 2
    assert t.leader(4) == 0 and t.leader(3) == 2
    # dense island ids ordered by leader rank (the native invariant)
    assert t.leaders == sorted(t.leaders)


def test_topology_fake_overrides_real_host():
    t = topo.Topology([_fp("same", "fake-host-0"), _fp("same", "fake-host-0"),
                       _fp("same", "fake-host-1")])
    assert t.islands == [[0, 1], [2]]


def test_topology_link_classes_and_tiers():
    t = topo.Topology([_fp("a", tpu=4), _fp("a", tpu=4),
                       _fp("b"), _fp("b")])
    assert t.tiers == ["ici", "ici", "shm", "shm"]
    assert t.link(0, 0) == "self"
    assert t.link(0, 1) == "ici"
    assert t.link(2, 3) == "shm"
    assert t.link(1, 2) == "tcp"


def test_topology_fingerprint_keys_on_shape_not_names():
    a = topo.Topology([_fp("hostA"), _fp("hostA"), _fp("hostB"), _fp("hostB")])
    b = topo.Topology([_fp("other1"), _fp("other1"),
                       _fp("other2"), _fp("other2")])
    c = topo.Topology([_fp("x"), _fp("x"), _fp("x"), _fp("y")])  # 3+1
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()
    assert len(a.fingerprint()) == 12
    flat = topo.Topology([_fp("x")] * 4)
    assert not flat.multi
    assert flat.fingerprint() != a.fingerprint()


def test_topology_leg_bytes_and_render():
    t = topo.Topology([_fp("a")] * 4 + [_fp("b")] * 4)
    legs = t.leg_bytes("hring", 1000)
    # intra: 2 * nbytes * (k-1) per island; inter: 2 * (L-1) * nbytes
    assert legs == {"intra": 2 * 1000 * 6, "inter": 2 * 1000}
    # htree's leader leg is recursive doubling: every butterfly
    # participant ships the FULL payload per round (+ the fold pair)
    assert t.leg_bytes("htree", 1000)["inter"] == 2 * 1000  # L=2: 2*1
    t4 = topo.Topology([_fp(h) for h in "aabbccdd" for _ in (0,)][:8])
    assert t4.n_islands == 4
    assert t4.leg_bytes("htree", 1000)["inter"] == 4 * 2 * 1000  # 4*log2(4)
    t3 = topo.Topology([_fp("a"), _fp("b"), _fp("c")])
    # L=3: pof2=2 (2*1 rounds... 2*log2(2)=2) + fold pair 2 -> 4
    assert t3.leg_bytes("htree", 1000)["inter"] == 4 * 1000
    flatlegs = t.leg_bytes("ring", 1000)
    assert flatlegs["intra"] == 0 and flatlegs["inter"] == 2 * 7 * 1000
    out = t.render()
    assert "island0[r0 r1 r2 r3" in out and "inter=tcp" in out
    d = t.describe()
    assert d["islands"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert d["fingerprint"] == t.fingerprint()


# ---------------- schedule simulators ----------------

def test_flat_simulators_match_numpy_on_exact_ints():
    rng = np.random.RandomState(0)
    for n in (1, 2, 3, 4, 5, 8):
        vals = [rng.randint(-50, 50, 97).astype(np.float32)
                for _ in range(n)]
        want = np.sum(np.stack(vals), axis=0)
        assert np.array_equal(topo.simulate_ring_sum(vals), want), n
        assert np.array_equal(topo.simulate_rd_sum(vals), want), n


def test_hier_simulators_are_close_and_deterministic():
    rng = np.random.RandomState(1)
    vals = [rng.randn(513).astype(np.float32) for _ in range(6)]
    islands = [[0, 1, 2, 3], [4, 5]]
    for fn in (topo.simulate_hring_sum, topo.simulate_htree_sum):
        got = fn(vals, islands)
        want = np.sum(np.stack(vals).astype(np.float64), axis=0)
        assert np.allclose(got, want, rtol=1e-4, atol=1e-4)
        # deterministic: same inputs, same bits
        assert np.array_equal(got, fn(vals, islands))


def test_hier_simulator_single_island_is_member_fold():
    vals = [np.float32([1e8]), np.float32([1.0]), np.float32([-1e8])]
    # sequential member-order fold: (1e8 + 1) - 1e8 == 0 in f32
    got = topo.simulate_hring_sum(vals, [[0, 1, 2]])
    assert got[0] == np.float32(np.float32(1e8 + 1.0) - 1e8)


def test_hier_simulator_intra_ring_is_the_ring_association():
    # intra="ring" (the ICI-leg data plane) folds each island with the
    # ring reduce-scatter association, NOT the sequential member fold
    rng = np.random.RandomState(7)
    vals = [rng.randn(513).astype(np.float32) for _ in range(5)]
    islands = [[0, 1, 2], [3, 4]]
    got = topo.simulate_hring_sum(vals, islands, intra="ring")
    # phase 1 of the schedule == per-island simulate_ring_sum
    isl0 = topo.simulate_ring_sum([vals[r] for r in islands[0]])
    isl1 = topo.simulate_ring_sum([vals[r] for r in islands[1]])
    want = topo.simulate_ring_sum([isl0, isl1])
    assert np.array_equal(got, want)
    assert np.allclose(got, np.sum(np.stack(vals), axis=0),
                       rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        topo.simulate_hring_sum(vals, islands, intra="banana")


def test_simulate_ici_q_sum_bound_and_determinism():
    rng = np.random.RandomState(11)
    vals = [rng.randn(700).astype(np.float32) * 3 for _ in range(6)]
    islands = [[0, 1, 2, 3], [4, 5]]
    got = topo.simulate_ici_q_sum(vals, islands)
    exact = np.sum(np.stack(vals).astype(np.float64), axis=0)
    denom = max(float(np.max(np.abs(exact))), 1e-6)
    err = float(np.max(np.abs(got.astype(np.float64) - exact))) / denom
    assert err < 5e-2, err  # the documented int8 wire bound
    assert got.dtype == np.float32
    assert np.array_equal(got, topo.simulate_ici_q_sum(vals, islands))


# ---------------- topology-keyed tune cache ----------------

def test_cache_path_topology_suffix(monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_TUNE_CACHE", raising=False)
    monkeypatch.setenv("XDG_CACHE_HOME", "/tmp/xdgtest")
    assert tune.cache_path(8).endswith("tune_8.json")
    assert tune.cache_path(8, "abc123").endswith("tune_8_abc123.json")


def test_save_load_cache_topology_stamp(tmp_path, monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_TUNE_CACHE", raising=False)
    p = tmp_path / "tune_4_deadbeef.json"
    table = {"allreduce": [(0, "tree"), (65536, "hring")]}
    tune.save_cache(4, table, path=str(p), topo_fingerprint="deadbeef")
    data = json.loads(p.read_text())
    assert data["topology"] == "deadbeef"
    try:
        loaded = tune.load_cache(4, path=str(p), topo_fingerprint="deadbeef")
        assert loaded["allreduce"][1] == (65536, "hring")
        with pytest.raises(ValueError):
            tune.load_cache(4, path=str(p), topo_fingerprint="00000000")
    finally:
        tune._cache_table = None
        tune._cache_origin = None
        tune._cache_loaded_for = None


def test_install_topology_flips_defaults_and_legacy_fallback(
        tmp_path, monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_TUNE_CACHE", raising=False)
    monkeypatch.delenv("MPI4JAX_TPU_COLL_ALGO", raising=False)
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    t_multi = topo.Topology([_fp("a"), _fp("a"), _fp("b"), _fp("b")])
    t_flat = topo.Topology([_fp("a")] * 4)
    try:
        tune.install(4, topology=t_multi)
        assert tune.get_algorithm("allreduce", 16 << 20) == "hring"
        assert tune.get_algorithm("allreduce", 1024) == "tree"
        assert "defaults:topology" in tune.sources()
        # a flat rediscovery (elastic shrink emptied an island)
        # restores the flat defaults
        tune.install(4, topology=t_flat)
        assert tune.get_algorithm("allreduce", 16 << 20) == "ring"
        # legacy fallback: only an un-keyed tune_4.json on disk — a
        # multi-island install still loads it
        legacy = {"version": 1, "world_size": 4, "table":
                  {"allreduce": [[0, "rd"]]}, "measurements": []}
        os.makedirs(tmp_path / "mpi4jax_tpu", exist_ok=True)
        (tmp_path / "mpi4jax_tpu" / "tune_4.json").write_text(
            json.dumps(legacy))
        tune.install(4, topology=t_multi)
        assert tune.get_algorithm("allreduce", 16 << 20) == "rd"
        assert "tune_4.json" in (tune._cache_origin or "")
        # ...but a topology-KEYED cache wins over the legacy one
        keyed = dict(legacy)
        keyed["table"] = {"allreduce": [[0, "htree"]]}
        keyed["topology"] = t_multi.fingerprint()
        (tmp_path / "mpi4jax_tpu" /
         f"tune_4_{t_multi.fingerprint()}.json").write_text(
            json.dumps(keyed))
        tune._cache_table = None
        tune._cache_loaded_for = None
        tune.install(4, topology=t_multi)
        assert tune.get_algorithm("allreduce", 16 << 20) == "htree"
    finally:
        tune._cache_table = None
        tune._cache_origin = None
        tune._cache_loaded_for = None
        tune._topo_multi = False


def test_check_algo_accepts_hier_names():
    assert tune._check_algo("hring") == "hring"
    assert tune._check_algo("htree", "allgather") == "htree"
    assert tune.ALGO_CODES["hring"] == 7 and tune.ALGO_CODES["htree"] == 8
    assert tune.HIER_ALGOS == {"hring", "htree"}
    with pytest.raises(ValueError):
        tune._check_algo("hband")


def test_hier_leg_events_carry_no_tuning_signal():
    # a hierarchical collective's per-leg event is labeled with the LEG
    # algorithm (e.g. ring on the leader tier) but times only that leg:
    # the tuner must ignore it, and use the whole-op record instead
    leg = {"name": "Allreduce", "src": "native", "algo": "ring",
           "bytes": 1 << 20, "dur_us": 10.0, "tier": "inter"}
    whole = {"name": "Allreduce", "src": "native", "algo": "hring",
             "bytes": 1 << 20, "dur_us": 50.0}
    m = tune.measurements_from_events([leg, whole])
    assert "ring" not in m.get("allreduce", {}).get(1 << 20, {})
    assert m["allreduce"][1 << 20]["hring"] == pytest.approx(50e-6)


# ---------------- config knobs ----------------

def test_topo_and_hier_knob_parsers(monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_TOPO", raising=False)
    monkeypatch.delenv("MPI4JAX_TPU_HIER", raising=False)
    assert config.topo_mode() == "auto"
    assert config.hier_mode() == "allow"
    monkeypatch.setenv("MPI4JAX_TPU_TOPO", "off")
    assert config.topo_mode() == "off"
    monkeypatch.setenv("MPI4JAX_TPU_HIER", "force")
    assert config.hier_mode() == "force"
    monkeypatch.setenv("MPI4JAX_TPU_TOPO", "maybe")
    with pytest.raises(ValueError):
        config.topo_mode()
    monkeypatch.setenv("MPI4JAX_TPU_HIER", "sometimes")
    with pytest.raises(ValueError):
        config.hier_mode()
    monkeypatch.setenv("MPI4JAX_TPU_FAKE_HOSTS", "r0|r1")
    assert config.fake_hosts_spec() == "r0|r1"


def test_ici_leg_knob_parser(monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_ICI_LEG", raising=False)
    assert config.ici_leg_mode() == "auto"
    for v in ("auto", "off", "force"):
        monkeypatch.setenv("MPI4JAX_TPU_ICI_LEG", v)
        assert config.ici_leg_mode() == v
    monkeypatch.setenv("MPI4JAX_TPU_ICI_LEG", "on")  # typo: abort loudly
    with pytest.raises(ValueError, match="MPI4JAX_TPU_ICI_LEG"):
        config.ici_leg_mode()
    monkeypatch.delenv("MPI4JAX_TPU_ICI_LEG", raising=False)
    assert config.knob_env()["MPI4JAX_TPU_ICI_LEG"] == "auto"


# ---------------- the ICI data-plane leg (process-local) ----------------


def _ici_leg_mod():
    import importlib

    return importlib.import_module(topo.__name__ + "._ici_leg")


def test_ici_leg_eligibility_gating(monkeypatch):
    leg = _ici_leg_mod()
    monkeypatch.delenv("MPI4JAX_TPU_HIER", raising=False)
    monkeypatch.delenv("MPI4JAX_TPU_PLAN", raising=False)
    t_ici = topo.Topology([_fp("a", tpu=4), _fp("a", tpu=4),
                           _fp("b", tpu=4), _fp("b", tpu=4)])
    t_shm = topo.Topology([_fp("a"), _fp("a"), _fp("b"), _fp("b")])
    t_flat = topo.Topology([_fp("a", tpu=4)] * 4)
    # auto: every multi-member island must be fully ici-tier
    assert leg.eligible(t_ici, mode="auto")
    assert not leg.eligible(t_shm, mode="auto")
    # force skips ONLY the tier check (the off-TPU tier-1 axis)
    assert leg.eligible(t_shm, mode="force")
    # off / no topology / flat world: never
    assert not leg.eligible(t_ici, mode="off")
    assert not leg.eligible(None, mode="force")
    assert not leg.eligible(t_flat, mode="force")
    # hier deny must keep degrading to the flat twins
    monkeypatch.setenv("MPI4JAX_TPU_HIER", "deny")
    assert not leg.eligible(t_ici, mode="force")
    monkeypatch.delenv("MPI4JAX_TPU_HIER", raising=False)
    # plan execution owns the schedule: the leg steps aside
    monkeypatch.setenv("MPI4JAX_TPU_PLAN", "/tmp/plan.json")
    assert not leg.eligible(t_ici, mode="force")


def test_ici_leg_status_and_backend(monkeypatch):
    leg = _ici_leg_mod()
    monkeypatch.setenv("MPI4JAX_TPU_ICI_LEG", "force")
    st = topo.ici_leg_status()
    assert st["mode"] == "force"
    assert st["backend"] in ("pallas", "numpy")
    assert st["backend"] == leg.ici_leg_backend()
    assert st["active"] is False  # no handle given
    monkeypatch.delenv("MPI4JAX_TPU_ICI_LEG", raising=False)
    assert topo.ici_leg_status()["mode"] == "auto"


def test_joint_ici_combos_need_the_leg():
    _joint = tune._submodule("_joint")
    base = dict(multi_island=True, quant_mode="allow", hier_mode="allow")
    # without the leg (the 3-kwarg legacy call shape): +ici excluded
    legless = _joint.eligible_combos("allreduce", **base)
    assert not any("ici" in c for c in legless)
    with_leg = _joint.eligible_combos("allreduce", ici_leg=True, **base)
    for c in ("hring+ici", "htree+ici", "hring+q+ici", "htree+q+ici"):
        assert c in with_leg
    # quant deny drops the +q+ici composites but keeps the exact +ici
    qdeny = _joint.eligible_combos("allreduce", ici_leg=True,
                                   multi_island=True, quant_mode="deny",
                                   hier_mode="allow")
    assert "hring+ici" in qdeny and "hring+q+ici" not in qdeny
    assert _joint.combo_algo("hring+q+ici") == "hring"
    assert _joint.combo_gates("htree+q+ici") == {
        "MPI4JAX_TPU_COLL_QUANT": "force",
        "MPI4JAX_TPU_ICI_LEG": "force"}
    assert _joint.combo_gates("hring+ici") == {
        "MPI4JAX_TPU_ICI_LEG": "force"}


# ---------------- obs: tier split ----------------

def test_stats_split_intra_vs_inter_bytes():
    events = [
        # whole-op record: NO tier (never double-counted)
        {"name": "Allreduce", "src": "native", "ts_us": 0.0,
         "dur_us": 100.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 1000, "peer": -1, "tag": 0, "algo": "hring"},
        {"name": "Reduce", "src": "native", "ts_us": 1.0, "dur_us": 30.0,
         "wait_us": 0.0, "dispatch_us": 0.0, "bytes": 1000, "peer": 0,
         "tag": 0, "algo": "shm", "tier": "intra"},
        {"name": "Allreduce", "src": "native", "ts_us": 2.0,
         "dur_us": 50.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 1000, "peer": -1, "tag": 0, "algo": "ring",
         "tier": "inter"},
        {"name": "Bcast", "src": "native", "ts_us": 3.0, "dur_us": 20.0,
         "wait_us": 0.0, "dispatch_us": 0.0, "bytes": 1000, "peer": 0,
         "tag": 0, "algo": "shm", "tier": "intra"},
    ]
    stats = _stats.summarize(events)
    assert stats["tier_bytes"] == {"intra": 2000, "inter": 1000}
    tiers = {(r["op"], r.get("tier")) for r in stats["per_op"]}
    assert ("Allreduce", None) in tiers or ("Allreduce", "inter") in tiers
    # the whole-op hring row and the inter-leg ring row never merge
    hring_rows = [r for r in stats["per_op"] if r["algo"] == "hring"]
    assert hring_rows and "tier" not in hring_rows[0]
    inter_rows = [r for r in stats["per_op"] if r.get("tier") == "inter"]
    assert inter_rows and inter_rows[0]["algo"] == "ring"
    # rendering includes the tier column only when legs are present
    table = _stats.render_table(stats)
    assert "tier" in table.splitlines()[0]


def test_stats_without_tier_events_schema_unchanged():
    events = [{"name": "Send", "src": "native", "ts_us": 0.0,
               "dur_us": 5.0, "wait_us": 0.0, "dispatch_us": 0.0,
               "bytes": 64, "peer": 1, "tag": 7, "algo": None}]
    stats = _stats.summarize(events)
    assert "tier_bytes" not in stats
    assert all("tier" not in r for r in stats["per_op"])
    assert "tier" not in _stats.render_table(stats).splitlines()[0]


# ---------------- analysis invariance (needs jax) ----------------

def test_hier_algo_keeps_plain_allreduce_schedule_signature():
    """Hierarchical routing is INVISIBLE to the static verifier: a
    forced hring allreduce extracts the same per-rank schedule (and
    cache key) as the plain one, so every golden plan and verified
    corpus stays byte-identical."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import mpi4jax_tpu as m4j
    from mpi4jax_tpu import analysis

    def plain(x, comm):
        return m4j.allreduce(x, op=m4j.SUM, comm=comm)

    def hier(x, comm):
        return m4j.allreduce(x, op=m4j.SUM, comm=comm, algo="hring")

    rp = analysis.check(plain, jnp.ones((4,), jnp.float32), world_size=4)
    rh = analysis.check(hier, jnp.ones((4,), jnp.float32), world_size=4)
    assert rp.ok and rh.ok
    assert rp.schedules == rh.schedules
    assert rp.cache_key == rh.cache_key


# ---------------- the alltoall family (MoE expert exchange) ----------------

def test_check_algo_accepts_alltoall_family():
    assert tune._check_algo("qalltoall", "alltoall") == "qalltoall"
    assert tune._check_algo("halltoall", "alltoall") == "halltoall"
    assert tune._check_algo("hqalltoall", "alltoall") == "hqalltoall"
    assert tune.ALGO_CODES["qalltoall"] == 9
    assert tune.ALGO_CODES["halltoall"] == 10
    assert tune.ALGO_CODES["hqalltoall"] == 11
    assert tune.A2A_ALGOS == {"qalltoall", "halltoall", "hqalltoall"}
    assert tune.A2A_QUANT == {"qalltoall", "hqalltoall"}
    assert tune.A2A_HIER == {"halltoall", "hqalltoall"}
    # the degrade chain: one gate axis at a time
    assert tune.HIER_FLAT_TWIN["halltoall"] == "ring"
    assert tune.HIER_FLAT_TWIN["hqalltoall"] == "qalltoall"
    # family names are alltoall-only; the allreduce twins stay theirs
    with pytest.raises(ValueError):
        tune._check_algo("qalltoall", "allreduce")
    with pytest.raises(ValueError):
        tune._check_algo("qring", "alltoall")


def test_alltoall_simulators_permutation_and_quant_bound():
    rng = np.random.RandomState(3)
    n = 5
    base = (rng.randn(n, n, 97) * 4).astype(np.float32)
    inputs = [base[r] for r in range(n)]
    want = [base[:, r] for r in range(n)]  # alltoall IS this transpose
    # halltoall is a pure permutation: bit-identical to the flat exchange
    got_h = topo.simulate_halltoall(inputs)
    assert all(np.array_equal(g, w) for g, w in zip(got_h, want))
    # qalltoall: own chunk exact, off-rank chunks int8-bounded
    got_q = topo.simulate_qalltoall(inputs)
    for r in range(n):
        assert np.array_equal(got_q[r][r], want[r][r])
        err = np.max(np.abs(got_q[r] - want[r]))
        assert 0 < err < np.max(np.abs(base)) / 127.0 + 1e-6
    # hqalltoall on a 3+2 split: intra chunks exact, cross bounded,
    # and deterministic
    islands = [[0, 1, 2], [3, 4]]
    got_hq = topo.simulate_hqalltoall(inputs, islands)
    again = topo.simulate_hqalltoall(inputs, islands)
    for r in range(n):
        assert np.array_equal(got_hq[r], again[r])
        my = islands[0] if r in islands[0] else islands[1]
        for s in range(n):
            if s in my:
                assert np.array_equal(got_hq[r][s], want[r][s]), (r, s)
            else:
                assert not np.array_equal(got_hq[r][s], want[r][s])
                assert np.max(np.abs(got_hq[r][s] - want[r][s])) < (
                    np.max(np.abs(base)) / 127.0 + 1e-6)
    # single island degenerates to the exact permutation
    one = topo.simulate_hqalltoall(inputs, [[0, 1, 2, 3, 4]])
    assert all(np.array_equal(g, w) for g, w in zip(one, want))


def test_leg_bytes_alltoall_family_geometry():
    t = topo.Topology([_fp("a")] * 4 + [_fp("b")] * 4)
    n, chunk = 8, 1000
    nbytes = n * chunk
    flat = t.leg_bytes("alltoall", nbytes)
    assert flat == {"intra": 0, "inter": n * (n - 1) * chunk}
    # halltoall: direct intra chunks + cross-chunk staging hops stay
    # intra; only the cross blocks cross the leader tier
    h = t.leg_bytes("halltoall", nbytes)
    assert h["intra"] == (2 * 4 * 3 * chunk        # direct, both islands
                          + 2 * (3 * 4 + 4 * 3) * chunk)  # staging
    assert h["inter"] == 2 * 4 * 4 * chunk
    # hqalltoall: same geometry, leader blocks through the codec
    hq = t.leg_bytes("hqalltoall", nbytes)
    assert hq["intra"] == h["intra"]
    assert hq["inter"] == 2 * topo._quant_wire_bytes(4 * 4 * chunk)
    assert hq["inter"] < h["inter"]
    # flat quantized: every off-rank chunk is a codec frame
    q = t.leg_bytes("qalltoall", nbytes)
    assert q == {"intra": 0,
                 "inter": n * (n - 1) * topo._quant_wire_bytes(chunk)}
    # codec arithmetic matches the native formula 4*ceil(count/256)+count
    assert topo._quant_wire_bytes(1024) == 256 + 4 * 1
    assert topo._quant_wire_bytes(1028) == 257 + 4 * 2
    # single island: everything is intra
    tf = topo.Topology([_fp("a")] * 4)
    assert tf.leg_bytes("qalltoall", 4000)["inter"] == 0
    assert tf.leg_bytes("alltoall", 4000)["inter"] == 0


def test_alltoall_leg_events_carry_no_tuning_signal():
    # hierarchical alltoall's per-leg events (intra shm leg, inter ring/
    # qalltoall leg) are labeled with the LEG algorithm and a tier: the
    # tuner must read only the tier-less whole-op record
    legs = [
        {"name": "Alltoall", "src": "native", "algo": "shm",
         "bytes": 4096, "dur_us": 5.0, "tier": "intra"},
        {"name": "Alltoall", "src": "native", "algo": "qalltoall",
         "bytes": 8192, "wire_bytes": 2176, "dur_us": 20.0,
         "tier": "inter"},
    ]
    whole = {"name": "Alltoall", "src": "native", "algo": "hqalltoall",
             "bytes": 1 << 15, "dur_us": 60.0}
    m = tune.measurements_from_events(legs + [whole])
    a2a = m.get("alltoall", {})
    assert all("shm" not in by_algo for by_algo in a2a.values())
    assert all("qalltoall" not in by_algo for by_algo in a2a.values())
    assert a2a[1 << 15]["hqalltoall"] == pytest.approx(60e-6)


def test_stats_alltoall_quant_rows_carry_wire_bytes():
    events = [
        # flat qalltoall whole-op record: packed wire, no tier
        {"name": "Alltoall", "src": "native", "ts_us": 0.0,
         "dur_us": 40.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 8192, "wire_bytes": 2176, "peer": -1, "tag": 0,
         "algo": "qalltoall"},
        # hqalltoall legs: tier split, quantized leader leg
        {"name": "Alltoall", "src": "native", "ts_us": 1.0,
         "dur_us": 10.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 4096, "peer": -1, "tag": 0, "algo": "shm",
         "tier": "intra"},
        {"name": "Alltoall", "src": "native", "ts_us": 2.0,
         "dur_us": 30.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 8192, "wire_bytes": 2176, "peer": -1, "tag": 0,
         "algo": "qalltoall", "tier": "inter"},
        {"name": "Alltoall", "src": "native", "ts_us": 3.0,
         "dur_us": 60.0, "wait_us": 0.0, "dispatch_us": 0.0,
         "bytes": 1 << 15, "peer": -1, "tag": 0, "algo": "hqalltoall"},
    ]
    stats = _stats.summarize(events)
    assert stats["tier_bytes"] == {"intra": 4096, "inter": 8192}
    rows = {(r["algo"], r.get("tier")): r for r in stats["per_op"]}
    flatq = rows[("qalltoall", None)]
    assert flatq["wire_bytes"] == 2176
    assert flatq["compression"] == pytest.approx(8192 / 2176, rel=1e-3)
    # the whole-op hqalltoall row is exact-payload (its compression
    # lives on the leader-leg row), and never merges with its legs
    assert "wire_bytes" not in rows[("hqalltoall", None)]
    assert rows[("qalltoall", "inter")]["wire_bytes"] == 2176
