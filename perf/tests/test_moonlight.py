"""The Moonlight cell on the CPU at a tiny shape: a whole run through the
harness (trace 0) is correct against the reference, the counters count
every routed row once, and the per-layer readers read a hand-made trace
as their docstrings say."""

import time
from types import SimpleNamespace

import jax
import pytest

from perf import harness
from perf.systems import moonlight
from perf.tracefile import Chip, Trace
from perf.work import moonlight as work

TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=16,
            n_shared_experts=1, dtype="float32")
READERS = ("moe.gmm_ms_per_step", "moe.gmm_roofline",
           "moe.exchange_ms_per_step", "moe.exchange_exposed_ms_per_step",
           "moe.route_ms_per_step", "attn.mla_ms_per_step",
           "attn.flash_roofline", "step.mfu")


def _spec():
    spec = harness.resolve("moonlight_ep4.prefill")
    spec["config"] = dict(spec["config"], **TINY)
    spec["traffic"] = dict(spec["traffic"], prompt_tokens=64)
    return spec


def test_run_is_correct_and_counts_every_routed_row():
    spec = _spec()
    result = harness.run(spec, 2**33 + 7, 1.0, 0,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:4])
    assert result["correct"], result["check"]
    assert result["compiles_in_window"] == 0

    cell = moonlight.Cell(spec["config"], spec["traffic"],
                          jax.devices()[:4], 3)
    cell.generate()
    cell.warm_up()
    for slot in range(3):
        jax.block_until_ready(cell.call("prefill", cell.initial(slot)))
    c = cell.counters()
    assert c["steps"] == 3 and c["tokens"] == 4 * 64
    # top-6 rows of every token in each of the 2 MoE layers, per step
    assert sum(c["moe.rows"]) == 6 * 4 * 64 * 2
    assert c["shape"]["experts_per_chip"] == 4


def _trace(ids):
    ops = [(0.000, 0.004, "custom-call:tpu_custom_call", "moe_gmm"),
           (0.004, 0.005, "fusion", "moe_gmm"),
           (0.005, 0.007, "ragged-all-to-all", "moe_dispatch"),
           (0.0065, 0.0070, "fusion", "moe_route"),
           (0.007, 0.008, "all-reduce", "moe_dispatch"),
           (0.008, 0.009, "custom-call:tpu_custom_call", "mla_attention"),
           (0.009, 0.010, "fusion", "mla_attention")]
    chips = {i: Chip(list(ops), []) for i in ids}
    return Trace(chips, (0.0, 0.010), [], scoped=True)


def test_readers_on_a_hand_made_trace():
    shape = dict(hidden=64, heads=4, qk_head=24, v_head=16, moe_width=32,
                 experts_per_chip=4, moe_layers=2, layers=3, seq_len=64,
                 seqs_per_chip=1, chips=4, vocab=512, itemsize=2,
                 active_params=1000)
    counters = {"moe.rows": [100.0, 200.0, 300.0, 400.0],
                "device_ids": [0, 1, 2, 3], "shape": shape}
    run = SimpleNamespace(trace=_trace(range(4)), counters=counters,
                          window=SimpleNamespace(seconds=0.5, steps=10),
                          device_kind="TPU v5 lite")
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in READERS}
    assert got["moe.gmm_ms_per_step"] == pytest.approx(0.4)
    assert got["moe.route_ms_per_step"] == pytest.approx(0.05)
    assert got["attn.mla_ms_per_step"] == pytest.approx(0.2)
    # the ragged exchange and the counts' all-reduce, 3 ms in all; the
    # route fusion overlaps 0.5 ms of it
    assert got["moe.exchange_ms_per_step"] == pytest.approx(0.3)
    assert got["moe.exchange_exposed_ms_per_step"] == pytest.approx(0.25)
    # every chip spent the same, so the first is read: its 100 rows
    flops, bytes_ = work.gmm(100.0, shape)
    want = max(flops / 197e12, bytes_ / 819e9) / 0.4e-3 * 100
    assert got["moe.gmm_roofline"] == pytest.approx(want)
    flops, bytes_ = work.flash(shape)
    want = max(flops / 197e12, bytes_ / 819e9) / 0.1e-3 * 100
    assert got["attn.flash_roofline"] == pytest.approx(want)
    want = work.model_flops(shape) / (0.05 * 4 * 197e12) * 100
    assert got["step.mfu"] == pytest.approx(want)


def test_readers_find_nothing_in_an_unscoped_trace():
    """A program that names none of the scopes (the parent's) reads
    nothing, and raises nothing."""
    trace = _trace(range(4))
    for chip in trace.chips.values():
        chip.ops = [(s, e, op, "unscoped") for s, e, op, _ in chip.ops]
    run = SimpleNamespace(trace=trace, counters={},
                          window=SimpleNamespace(seconds=0.5, steps=10),
                          device_kind="TPU v5 lite")
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(run) is None
