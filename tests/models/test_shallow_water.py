"""Shallow-water solver: stability, conservation, and — the strongest
correctness check — bitwise-comparable results between a 1-device and an
8-device decomposition of the same problem."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

import jax
import mpi4jax_tpu as m4j
from mpi4jax_tpu.models.shallow_water import SCOPES, ShallowWater, SWParams
from mpi4jax_tpu.parallel.grid import ProcessGrid

NY, NX = 24, 48
PARAMS = SWParams(dx=5e3, dy=5e3)


def run_model(grid_shape, n_steps):
    n = int(np.prod(grid_shape))
    grid = ProcessGrid(grid_shape, devices=jax.devices()[:n])
    model = ShallowWater(grid, (NY, NX), PARAMS)
    state = model.init()
    state = model.step_fn(n_steps, first=True)(state)
    return model, state


def test_finite_and_nontrivial():
    model, state = run_model((2, 4), 10)
    h = model.interior(state.h)
    assert np.all(np.isfinite(h))
    assert h.std() > 0  # jet + perturbation evolve


def test_mass_conservation():
    model, state0 = run_model((2, 4), 0)
    m0 = model.total_mass(state0)
    state = model.step_fn(20, first=True)(state0)
    m1 = model.total_mass(state)
    assert abs(m1 - m0) / abs(m0) < 1e-5


def test_decomposition_invariance():
    # 1 device vs 8 devices must produce the same trajectory
    model1, s1 = run_model((1, 1), 10)
    model8, s8 = run_model((2, 4), 10)
    h1 = model1.interior(s1.h)
    h8 = model8.interior(s8.h)
    np.testing.assert_allclose(h1, h8, rtol=2e-5, atol=2e-5)
    u1 = model1.interior(s1.u)
    u8 = model8.interior(s8.u)
    np.testing.assert_allclose(u1, u8, rtol=2e-4, atol=2e-4)


def test_longer_run_stable():
    model, state = run_model((2, 4), 100)
    h = model.interior(state.h)
    assert np.all(np.isfinite(h))
    # surface stays within physically plausible bounds around DEPTH=100
    assert 50 < h.mean() < 150


@pytest.mark.parametrize("first,program", [(True, "sw_bootstrap"),
                                           (False, "sw_advance")])
def test_compiled_step_names_its_scopes(first, program):
    """The step's instructions carry ``sw_step`` in their op_name, those
    of its halo exchange ``sw_step/halo_exchange``, and the program is
    named for its call: what a profiler trace reads them by."""
    grid = ProcessGrid((2, 2), devices=jax.devices()[:4])
    model = ShallowWater(grid, (NY, NX), PARAMS)
    fn = model.step_fn(3, first=first)
    text = fn.lower(model.init()).compile().as_text()
    assert text.startswith(f"HloModule jit_{program}")
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert all(n.startswith(f"jit({program})/") for n in names
               if "/" in n)
    steps = [n for n in names if "/sw_step/" in n]
    halo = [n for n in steps if "/sw_step/halo_exchange/" in n]
    assert halo and len(halo) < len(steps)
    assert any(n.endswith("/halo_exchange/ppermute") for n in halo)
    assert any(n.endswith("/halo_exchange/dynamic_update_slice")
               for n in halo)
    # of the solver's declared names, the XLA step carries these two
    assert {p for n in names for p in n.split("/") if p in SCOPES} == {
        "sw_step", "halo_exchange"}
