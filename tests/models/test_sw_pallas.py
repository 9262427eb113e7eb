"""Fused Pallas shallow-water step vs the XLA slice-stencil step.

Same stencils, same boundary-mask ordering — results must agree to f32
reassociation tolerance, bootstrap (Euler) step included.  Runs the
kernel under the Pallas TPU interpreter on the CPU mesh.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models.shallow_water import SCOPES, ShallowWater, SWParams
from mpi4jax_tpu.parallel.grid import ProcessGrid


def _model(ny=32, nx=64):
    grid = ProcessGrid((1, 1), devices=jax.devices()[:1])
    return ShallowWater(grid, (ny, nx), SWParams(dx=5e3, dy=5e3))


def _advance(model, impl, n_steps, **kw):
    state = model.init()
    state = model.step_fn(1, first=True, impl=impl, **kw)(state)
    if n_steps > 1:
        state = model.step_fn(n_steps - 1, first=False, impl=impl, **kw)(
            state)
    return state


@pytest.mark.parametrize("n_steps", [1, 12])
def test_fused_step_matches_xla(n_steps):
    model = _model()
    ref = _advance(model, "xla", n_steps)
    got = _advance(model, "pallas", n_steps)
    for name, a, b in zip(ref._fields, got, ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
            err_msg=f"field {name} after {n_steps} steps",
        )


def test_fused_step_tile_edge_cases():
    """Domain heights that are not multiples of the row tile, and domains
    smaller than one window, still match."""
    for ny, nx in [(16, 32), (22, 32), (48, 32)]:
        model = _model(ny, nx)
        ref = _advance(model, "xla", 3)
        got = _advance(model, "pallas", 3)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
                err_msg=f"domain ({ny},{nx})",
            )


@pytest.mark.parametrize("tile_rows,fuse", [(16, 1), (16, 2), (32, 2),
                                            (24, 3)])
def test_fused_step_multi_tile(tile_rows, fuse):
    """Force ntiles >= 2 so the clamped interior halo index maps and the
    cross-tile halo consistency under temporal blocking actually run (the
    tuned defaults pad the small CI domains into a single tile)."""
    model = _model(ny=70, nx=32)  # nyp=72 -> >= 3 tiles at T=16/32
    ref = _advance(model, "xla", 7)
    got = _advance(model, "pallas", 7, tile_rows=tile_rows, fuse=fuse)
    for name, a, b in zip(ref._fields, got, ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
            err_msg=f"field {name} tile_rows={tile_rows} fuse={fuse}",
        )


@pytest.mark.parametrize("first,n_steps", [
    (False, 2), (False, 3), (False, 7), (False, 10), (False, 25),
    (True, 25)])
def test_fused_loop_matches_kernel_calls(first, n_steps):
    """The time loop runs its fused calls two per iteration: an even, an
    odd and a zero count of whole fused calls, with and without single
    steps after them, give bit for bit what the same kernel calls give
    made one by one."""
    from mpi4jax_tpu.models import _sw_pallas

    model = _model()
    state = model.init()
    got = model.step_fn(n_steps, first=first, impl="pallas")(state)

    tile_rows, fuse = 120, 3  # step_fn's defaults
    shape = state.h.shape
    T_eff = _sw_pallas._tiling(shape[0], tile_rows, fuse)[0]
    s = _sw_pallas.pad_rows(state, tile_rows=tile_rows, fuse=fuse)

    def call(st, is_first, tr, fz):
        return _sw_pallas.fused_step(
            st, model.params, first=is_first, logical_shape=shape,
            tile_rows=tr, fuse=fz)

    remaining = n_steps
    if first:
        s = call(s, True, T_eff, 1)
        remaining -= 1
    whole, rest = divmod(remaining, fuse)
    for _ in range(whole):
        s = call(s, False, tile_rows, fuse)
    for _ in range(rest):
        s = call(s, False, T_eff, 1)
    want = _sw_pallas.unpad_rows(s, shape)
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"field {name} after {n_steps} steps, first={first}")


def test_fused_step_conserves_mass():
    model = _model()
    s0 = model.init()
    s1 = model.step_fn(1, first=True, impl="pallas")(s0)
    s1 = model.step_fn(20, first=False, impl="pallas")(s1)
    m0 = float(jnp.sum(model.interior(s0.h)))
    m1 = float(jnp.sum(model.interior(s1.h)))
    assert abs(m1 - m0) / abs(m0) < 1e-5


def test_pallas_impl_rejects_decomposed_grid():
    grid = ProcessGrid((2, 4))
    model = ShallowWater(grid, (16, 32), SWParams(dx=5e3, dy=5e3))
    with pytest.raises(ValueError, match="1x1 grid"):
        model.step_fn(1, impl="pallas")


def test_auto_impl_surfaces_kernel_compile_error(monkeypatch):
    """impl="auto" on a TPU target picks the fused kernel, and a kernel
    that fails to build raises: no fall-back to another tile or to the
    XLA step hides it."""
    from mpi4jax_tpu.models import _sw_pallas
    from mpi4jax_tpu.ops import flash

    def broken(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(flash, "target_platform", lambda: "tpu")
    monkeypatch.setattr(_sw_pallas, "fused_step", broken)
    model = _model()
    state = model.init()
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        model.step_fn(4, first=True, impl="auto")(state)


def test_fused_path_names_its_scopes_and_kernel():
    """The interpreted fused path: the padding to the kernel's block and
    the cut back carry ``sw_pad_rows`` and ``sw_unpad_rows`` in their
    op_name, and the kernel's instructions its name, ``sw_fused_step``."""
    model = _model()  # 34 x 66 padded to the kernel's 128-lane block
    text = model.step_fn(4, impl="pallas").lower(
        model.init()).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert any("/sw_pad_rows/" in n for n in names)
    assert any("/sw_unpad_rows/" in n for n in names)
    assert any("/sw_fused_step/" in n for n in names)
    assert not any("/sw_step/" in n for n in names)
    # of the solver's declared names, the fused path carries these three
    assert {p for n in names for p in n.split("/") if p in SCOPES} == {
        "sw_pad_rows", "sw_unpad_rows", "sw_fused_step"}
