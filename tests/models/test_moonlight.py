"""Moonlight (DeepSeek-V3 architecture) prefill against its plain reference.

A tiny shape that keeps every mechanism: latent attention with a rope
part, 1 dense and 2 MoE layers, top-6 of 16 routed experts with a
nonzero selection bias, a shared expert, sequences of 64, on a 4-device
CPU mesh (4 experts per rank).  The reference is the benchmark's own,
``perf/references/moonlight.py``, loaded by path; it imports nothing of
the package.  The model runs in float32 here, so program and reference
agree to rounding; the chip's bfloat16 comparison is calibrated in
``PERF.md``.
"""

import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi4jax_tpu.models import moonlight
from mpi4jax_tpu.parallel import moe
from mpi4jax_tpu.parallel.moe import (receive_capacities, receive_rungs,
                                      route_topk_sigmoid)

REPO = pathlib.Path(__file__).resolve().parents[2]
SEED = 11
TOL = 2e-5

HF = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
          first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=32,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          intermediate_size=96, moe_intermediate_size=32,
          n_routed_experts=16, num_experts_per_tok=6, n_shared_experts=1,
          routed_scaling_factor=2.446, rms_norm_eps=1e-5, rope_theta=50000.0,
          dtype="float32", weight_seed=SEED)


def _load_reference():
    name = "m4j_moonlight_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, REPO / "perf" / "references" / "moonlight.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _tokens(seed=0, batch=4, t=64):
    return np.random.RandomState(seed).randint(
        0, HF["vocab_size"], (batch, t)).astype(np.int32)


def _run(hf, tokens, ranks=4, bias=None):
    mesh = Mesh(np.array(jax.devices()[:ranks]), ("ep",))
    model = moonlight.Moonlight(moonlight.MoonlightConfig.from_hf(hf), mesh)
    params = model.init_params(hf["weight_seed"])
    if bias is not None:
        params["moe"]["router_bias"] = jnp.broadcast_to(
            jnp.asarray(bias), params["moe"]["router_bias"].shape)
    hidden, logits, counts = model.prefill_fn()(params, jnp.asarray(tokens))
    return ({"hidden": np.asarray(hidden), "logits": np.asarray(logits)},
            np.asarray(counts))


_four_ranks = {}


def _run_four(tokens):
    """The default four-rank run of the first token batch, computed once."""
    if "out" not in _four_ranks:
        _four_ranks["out"] = _run(HF, tokens)
    return _four_ranks["out"]


def _reference(ref, hf, tokens, bias=None, monkeypatch=None):
    if bias is not None:
        weight = ref._weight

        def biased(seed, name, shape, dtype, *index):
            if name == "router_bias":
                return jnp.asarray(bias, jnp.float32)
            return weight(seed, name, shape, dtype, *index)

        monkeypatch.setattr(ref, "_weight", biased)
    hidden, logits = ref.forward(hf, tokens)
    return {"hidden": hidden, "logits": logits}


def _assert_close(ref, got, want):
    r = ref.readings(got, want)
    assert r["hidden_rel"] < TOL and r["logits_rel"] < TOL, r


def test_weights_match_the_reference_recipe(ref):
    """The program's on-device weights are the reference's, tensor by
    tensor, experts included (each rank generates its own), to float32
    rounding: the two programs may fuse the draw's arithmetic
    differently."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    model = moonlight.Moonlight(moonlight.MoonlightConfig.from_hf(HF), mesh)
    params = model.init_params(SEED)
    for layer_in_stack, layer in ((0, 1), (1, 2)):
        for e in (0, 7, 15):
            np.testing.assert_allclose(
                np.asarray(params["moe"]["experts_down"][layer_in_stack, e]),
                np.asarray(ref.weight(SEED, "experts_down", (32, 64),
                                      jnp.float32, layer, e)), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["moe"]["router_bias"][layer_in_stack]),
            np.asarray(ref.weight(SEED, "router_bias", (16,), jnp.float32,
                                  layer)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(params["dense"]["wq"][0]),
        np.asarray(ref.weight(SEED, "wq", (64, 4 * 24), jnp.float32, 0)),
        rtol=1e-6)


def test_prefill_matches_reference(ref):
    tokens = _tokens()
    got, counts = _run_four(tokens)
    _assert_close(ref, got, _reference(ref, HF, tokens))
    # every routed pair of every MoE layer is counted once: nothing drops
    assert counts.shape == (4, 2, 16)
    np.testing.assert_array_equal(counts.sum(axis=(0, 2)), [4 * 64 * 6] * 2)


def test_skewed_router_drops_nothing(ref, monkeypatch):
    """A selection bias that sends all six rows of every token to rank
    2's experts (32 experts, 8 per rank): rank 2 receives the worst-case
    buffer, and the answer is still the reference's."""
    hf = dict(HF, n_routed_experts=32)
    bias = np.zeros(32, np.float32)
    bias[16:24] = 10.0
    tokens = _tokens(1)
    got, counts = _run(hf, tokens, bias=bias)
    per_rank = counts.sum(axis=(0, 1)).reshape(4, 8).sum(axis=1)
    np.testing.assert_array_equal(per_rank, [0, 0, 2 * 4 * 64 * 6, 0])
    # both layers took the last rung of the ladder, the worst case
    ladder = receive_capacities(64, 6, 4)
    assert len(ladder) > 1
    np.testing.assert_array_equal(receive_rungs(counts, 6),
                                  [len(ladder) - 1] * 2)
    _assert_close(ref, got, _reference(ref, hf, tokens, bias, monkeypatch))


def test_smaller_rung_is_bit_identical_to_the_worst_case(monkeypatch):
    """Unbiased routing takes a rung below the worst case in every layer,
    and the answer is bit for bit that of the layer whose ladder is the
    worst case alone: the same rows, in the same order and tiling."""
    tokens = _tokens()
    got, counts = _run_four(tokens)
    rungs = receive_rungs(counts, 6)
    assert (rungs < len(receive_capacities(64, 6, 4)) - 1).all(), rungs
    monkeypatch.setattr(moe, "receive_capacities",
                        lambda t, top_k, size: (size * t * top_k,))
    worst, worst_counts = _run(HF, tokens)
    np.testing.assert_array_equal(worst_counts, counts)
    for name in ("hidden", "logits"):
        np.testing.assert_array_equal(got[name], worst[name])


def test_receive_rungs_agree_with_the_rungs_taken(monkeypatch):
    """``receive_rungs`` on the prefill's counts gives, for each layer,
    the rung that every rank's layer chose."""
    rung = moe.receive_rung

    def recorded(every, capacities):
        r = rung(every, capacities)
        jax.debug.callback(
            lambda e, i: taken.append((np.asarray(e).copy(), int(i))),
            every, r)
        return r

    monkeypatch.setattr(moe, "receive_rung", recorded)
    hf = dict(HF, n_routed_experts=32)
    bias = np.zeros(32, np.float32)
    bias[16:24] = 10.0  # the second token batch's layers: all to rank 2
    runs = []
    for tokens, b in ((_tokens(), None), (_tokens(1), bias)):
        taken = []
        _, counts = _run(hf, tokens, bias=b)
        jax.effects_barrier()
        runs.append((counts, taken))
    monkeypatch.undo()
    rungs = {}
    for counts, taken in runs:
        assert len(taken) == 4 * 2  # every rank, both layers
        for layer, r in enumerate(receive_rungs(counts, 6)):
            every = counts[:, layer].reshape(4, 4, 8)
            chose = [i for e, i in taken if np.array_equal(e, every)]
            assert chose == [r] * 4, (layer, chose, r)
            rungs[r] = rungs.get(r, 0) + 1
    # both a rung below the worst case and the worst case were taken
    last = len(receive_capacities(64, 6, 4)) - 1
    assert min(rungs) < last and max(rungs) == last, rungs


def test_selection_by_biased_score_weighting_by_score(ref, monkeypatch):
    """A bias large enough to change which experts win: the experts are
    the top-6 of ``s + bias``, their weights the normalised ``s``."""
    rng = np.random.RandomState(4)
    bias = rng.uniform(-0.3, 0.3, 16).astype(np.float32)
    h = jnp.asarray(rng.randn(256, 64).astype(np.float32))
    w_gate = jnp.asarray(rng.randn(64, 16).astype(np.float32) * 0.05)
    idx, w = route_topk_sigmoid(h, w_gate, jnp.asarray(bias), 6, 2.446)
    s = 1 / (1 + np.exp(-np.asarray(h, np.float64) @ np.asarray(w_gate)))
    want_idx = np.argsort(-(s + bias), axis=1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(want_idx, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(1, keepdims=True) * 2.446,
        rtol=1e-5)
    unbiased = np.sort(np.argsort(-s, axis=1)[:, :6], 1)
    assert (unbiased != np.sort(want_idx, 1)).any(axis=1).mean() > 0.3

    tokens = _tokens(2)
    got, _ = _run(HF, tokens, bias=bias)
    _assert_close(ref, got, _reference(ref, HF, tokens, bias, monkeypatch))


def test_one_rank_holding_every_expert_matches_four(ref):
    """The shares of four ranks add up to the whole layer: one rank that
    holds all 16 experts gives the four-rank answer."""
    tokens = _tokens()
    four, _ = _run_four(tokens)
    one, counts = _run(HF, tokens, ranks=1)
    assert counts.shape == (1, 2, 16)
    _assert_close(ref, four, one)
