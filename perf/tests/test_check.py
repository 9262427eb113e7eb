"""The check that decides ``correct``, driven through a whole run of the
harness on the CPU at a small size, with the look for a chip skipped.

The program as it stands passes, in every cell.  The control (the plain
reference put in the program's place and computed in bfloat16, the
precision below the configuration's float32) and each fault that a
shallow-water cell can have fail: a step that returns its state
unchanged, the exchange between chips left out, an answer altered where
it is produced, and, between walls in x, a program that wraps x around.
(A solve has no batch, so "half of the batch left out" has no
counterpart.)
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from perf import harness
from perf.references import shallow_water as reference
from perf.systems import shallow_water as system

SMALL = {"ny": 24, "nx": 48, "model_days": 0.002}  # 9 steps: 1 + 2 x 4
ONE, FOUR, WALLED = ("sw_ref.periodic", "sw_ref_2x2.periodic",
                     "sw_ref_walled.periodic")


def _spec(workload):
    spec = harness.resolve(workload)
    gy, gx = spec["config"]["grid"]
    spec["config"] = dict(spec["config"], ny=SMALL["ny"] * gy,
                          nx=SMALL["nx"] * gx,
                          model_days=SMALL["model_days"])
    spec["traffic"] = dict(spec["traffic"], steps_per_call=4)
    return spec


def _run(workload, seed=2**31 + 11, trace=0):
    return harness.run(_spec(workload), seed, 0.0, trace,
                       t_start=time.perf_counter(), devices=jax.devices())


@pytest.fixture
def pallas(monkeypatch):
    """The fused kernel, interpreted: what impl="auto" picks on a TPU."""
    from mpi4jax_tpu.models.shallow_water import ShallowWater

    step_fn = ShallowWater.step_fn
    monkeypatch.setattr(
        ShallowWater, "step_fn",
        lambda self, n, first=False: step_fn(self, n, first=first,
                                             impl="pallas"))


def _assert_fails(result):
    assert result["correct"] is False
    assert result["failed"] == 1
    assert any(r["value"] is None or r["value"] > r["limit"]
               for r in result["check"].values())


def test_fused_kernel_passes(pallas):
    result = _run(ONE)
    assert result["correct"], result["check"]
    assert result["attempted"] == 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert list(result)[-1] == "check"


def test_window_counts_whole_solves():
    spec = _spec(ONE)
    cell = system.Cell(spec["config"], spec["traffic"], jax.devices(), 5)
    cell.generate()
    window = harness.drive(cell, solves=3)
    assert (window.solves, window.calls, window.steps) == (3, 9, 27)
    assert sorted(window.kept) == [0, 1]
    assert len(window.solve_seconds) == 3


@pytest.mark.parametrize("workload", [ONE, FOUR, WALLED])
def test_xla_step_passes(workload):
    result = _run(workload)
    assert result["correct"], result["check"]
    for r in result["check"].values():
        assert r["value"] < r["limit"] / 10


@pytest.mark.parametrize("workload", [ONE, WALLED])
def test_control_fails(monkeypatch, workload):
    """The reference in bfloat16 in the program's place."""
    def call(self, kind, state):
        s = tuple(f.astype(jnp.bfloat16) for f in state)
        n = dict(self.calls)[kind]
        s = reference.step(s, self.config, first=kind == "bootstrap")
        for _ in range(n - 1):
            s = reference.step(s, self.config, first=False)
        return type(state)(*(f.astype(jnp.float32) for f in s))

    monkeypatch.setattr(system.Cell, "call", call)
    _assert_fails(_run(workload))


@pytest.mark.parametrize("workload", [ONE, FOUR, WALLED])
def test_state_left_unchanged_fails(monkeypatch, workload):
    monkeypatch.setattr(system.Cell, "call", lambda self, kind, s: s)
    _assert_fails(_run(workload))


def test_periodic_answer_judged_against_walls_fails(monkeypatch):
    """The walled cell's program run periodic in x: the jet passes
    through the east wall where the reference stops it."""
    from mpi4jax_tpu.models import shallow_water

    params = shallow_water.SWParams
    monkeypatch.setattr(shallow_water, "SWParams",
                        lambda **kw: params(**dict(kw, periodic_x=True)))
    _assert_fails(_run(WALLED))


def test_exchange_between_chips_left_out_fails(monkeypatch):
    from mpi4jax_tpu.parallel import halo

    exchange = halo._axis_exchange

    def local_only(f, dim, axis_name, width, periodic):
        if lax.axis_size(axis_name) > 1:
            return f
        return exchange(f, dim, axis_name, width, periodic)

    monkeypatch.setattr(halo, "_axis_exchange", local_only)
    _assert_fails(_run(FOUR))


@pytest.mark.parametrize("workload", [ONE, FOUR, WALLED])
def test_answer_altered_where_produced_fails(monkeypatch, workload):
    call = system.Cell.call

    def altered(self, kind, state):
        out = call(self, kind, state)
        return out._replace(h=out.h.at[5, 7].add(1.0))

    monkeypatch.setattr(system.Cell, "call", altered)
    _assert_fails(_run(workload))
