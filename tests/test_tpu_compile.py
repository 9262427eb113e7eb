"""The main path's Pallas kernels, compiled by Mosaic for a described v5e.

No chip is needed: the TPU compiler compiles for a chip that is described
and not attached (``jax.experimental.topologies``).  Each test compiles
with ``interpret=False`` at the real width and asserts that the kernel is
in the program (``tpu_custom_call``), so what interpret mode cannot see
(tile alignment, VMEM budgets, a kernel that cannot be partitioned) fails
here at no chip time.  Nothing runs: results and times come from
``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import: one
process at a time may load libtpu, and under pytest-xdist every worker
imports this file.  Keep these tests in this one file.

The shallow-water kernel compiles at ``(tile_rows, fuse) = (64, 1)``
(a few seconds); the default ``(120, 3)`` takes about 97 s here, too slow
for tier-1, and is compiled on the chip by ``chip_smoke.py``.  Its time loop
(two fused calls per iteration, so that XLA copies no loop carry) is
checked here at a small domain, and at the flagship domain by the one
test that compiles for an attached chip and skips elsewhere.
"""

import os
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("x",))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_sw_fused_step_compiles(one_chip):
    from mpi4jax_tpu.models import _sw_pallas
    from mpi4jax_tpu.models.shallow_water import SWParams, SWState

    tile_rows, fuse = 64, 1
    shape = (1802, 3602)  # 1800x3600 interior plus the 1-cell halo
    nyp_pad = _sw_pallas._tiling(shape[0], tile_rows, fuse)[2]
    padded = jax.ShapeDtypeStruct(
        (nyp_pad, _sw_pallas._col_pad(shape[1])), jnp.float32,
        sharding=one_chip)
    step = jax.jit(partial(
        _sw_pallas.fused_step, params=SWParams(dx=5e3, dy=5e3), first=False,
        interpret=False, tile_rows=tile_rows, logical_shape=shape,
        fuse=fuse))
    _assert_kernel(step.lower(SWState(*(padded,) * 6)).compile())


def _while_bodies(hlo):
    """``{name: instruction lines}`` of each while loop body in HLO text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            name, comps[m.group(1)] = m.group(1), []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return {b: comps[b] for b in re.findall(r"\bbody=%?([\w.\-]+)", hlo)}


def _assert_loop_carries_no_copies(devices, ny, nx):
    """Compile a 25-step call of the fused time loop (8 whole fused calls,
    the default ``tile_rows=120, fuse=3``) for ``devices[0]``: no while
    body copies a padded field (the loop carry of the kernel's outputs),
    and each body holds two ``sw_fused_step`` calls."""
    from mpi4jax_tpu.models import _sw_pallas
    from mpi4jax_tpu.models.shallow_water import (ShallowWater, SWParams,
                                                  SWState)
    from mpi4jax_tpu.parallel.grid import ProcessGrid

    grid = ProcessGrid((1, 1), devices=devices[:1])
    model = ShallowWater(grid, (ny, nx), SWParams(dx=5e3, dy=5e3))
    sharding = NamedSharding(grid.mesh, P(*grid.axes))
    state = SWState(*(jax.ShapeDtypeStruct(
        model.stacked_shape, jnp.float32, sharding=sharding),) * 6)
    with jax.set_mesh(grid.mesh):
        hlo = model.step_fn(25, impl="pallas").lower(state).compile(
            ).as_text()
    padded = "f32[{},{}]".format(_sw_pallas._tiling(ny + 2, 120, 3)[2],
                                 _sw_pallas._col_pad(nx + 2))
    bodies = _while_bodies(hlo)
    assert bodies
    for name, lines in bodies.items():
        copies = [ln for ln in lines if re.search(
            r"= " + re.escape(padded) + r"\S* copy(-start)?\(", ln)]
        kernels = [ln for ln in lines
                   if " custom-call(" in ln and "sw_fused_step" in ln]
        assert not copies, f"{name}: {copies}"
        assert len(kernels) == 2, f"{name}: {len(kernels)} kernel calls"


def test_sw_fused_loop_carries_no_copies(topo):
    """A small domain, so that the default tiling compiles in seconds."""
    _assert_loop_carries_no_copies(topo.devices, 240, 512)


def test_sw_fused_loop_carries_no_copies_on_chip():
    """The flagship domain, whose fields live in HBM, on the attached chip:
    ``python -m pytest --noconftest tests/test_tpu_compile.py -k on_chip``
    on a TPU host (the suite's conftest holds JAX to the CPU)."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiles for the attached TPU")
    _assert_loop_carries_no_copies(jax.devices(), 1800, 3600)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles(topo, grad):
    from mpi4jax_tpu.ops.flash import ring_flash_attention

    mesh = Mesh(np.array(topo.devices[:1]), ("sp",))
    fa = jax.shard_map(
        partial(ring_flash_attention, axis="sp", causal=True,
                interpret=False),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    spec = jax.ShapeDtypeStruct((4, 4096, 16, 128), jnp.bfloat16,
                                sharding=NamedSharding(mesh, P()))
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(fa(q, k, v).astype(
            jnp.float32)), argnums=(0, 1, 2))
    else:
        fn = fa
    _assert_kernel(jax.jit(fn).lower(spec, spec, spec).compile())


@pytest.mark.parametrize("kernel", ["ring_shift", "fused_ring_allreduce_sum"])
def test_ici_kernel_compiles_on_four_chips(four_chips, kernel):
    from mpi4jax_tpu.ops import pallas_collectives as pc

    if kernel == "ring_shift":
        def body(v):
            return pc.ring_shift(v, "x", 1, interpret=False)
        shape = (4 * 64, 128)
    else:
        def body(v):
            return pc.fused_ring_allreduce_sum(
                v.reshape(-1), "x", interpret=False)[None]
        shape = (4, 4 * 4096 + 37)
    fn = jax.jit(jax.shard_map(body, mesh=four_chips, in_specs=P("x"),
                               out_specs=P("x"), check_vma=False))
    spec = jax.ShapeDtypeStruct(shape, jnp.float32,
                                sharding=NamedSharding(four_chips, P("x")))
    _assert_kernel(fn.lower(spec).compile())


def _computations(hlo):
    """``{name: body lines}`` of every computation in an HLO module's
    text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return comps


def test_expert_parallel_moe_compiles_on_four_chips(four_chips):
    """The dropless EP4 MoE layer at a small width: one ``conditional``
    over the receive capacities, each branch a dispatch and a combine
    ``ragged-all-to-all`` (no padded all-to-all of expert rows) around
    the experts' megablox ``gmm`` Mosaic kernels, the last branch at the
    worst case."""
    from mpi4jax_tpu.parallel.mesh import MeshComm
    from mpi4jax_tpu.parallel.moe import (expert_parallel_ffn,
                                          receive_capacities,
                                          route_topk_sigmoid)

    t, d, f, experts = 512, 256, 256, 16

    def layer(x, w_gate, bias, w_gate_up, w_down):
        idx, w = route_topk_sigmoid(x, w_gate, bias, 6, 2.446)
        return expert_parallel_ffn(x, idx, w, w_gate_up, w_down,
                                   n_experts=experts,
                                   comm=MeshComm("x", mesh=four_chips))[0]

    fn = jax.jit(jax.shard_map(
        layer, mesh=four_chips, in_specs=(P("x"), P(), P(), P("x"), P("x")),
        out_specs=P("x"), check_vma=False))

    def spec(shape, dtype, p):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(four_chips, p))

    hlo = fn.lower(spec((4 * t, d), jnp.bfloat16, P("x")),
                   spec((d, experts), jnp.bfloat16, P()),
                   spec((experts,), jnp.float32, P()),
                   spec((experts, d, 2 * f), jnp.bfloat16, P("x")),
                   spec((experts, f, d), jnp.bfloat16, P("x"))
                   ).compile().as_text()
    capacities = receive_capacities(t, 6, 4)
    assert len(capacities) == 4 and capacities[-1] == 4 * t * 6
    (cond,) = re.findall(r".* conditional\(.*", hlo)
    branches = re.search(r"branch_computations=\{([^}]*)\}", cond).group(1)
    comps = _computations(hlo)
    dispatched = []
    for name in branches.replace("%", "").split(", "):
        body = comps[name]
        exchanges = [ln for ln in body if " ragged-all-to-all(" in ln]
        assert len(exchanges) == 2, exchanges
        (dispatch,) = [ln for ln in exchanges if "moe_dispatch" in ln]
        assert [ln for ln in exchanges if "moe_combine" in ln]
        dispatched.append(int(re.search(r"= bf16\[(\d+),", dispatch)[1]))
        gmm = [ln for ln in body
               if "tpu_custom_call" in ln and "moe_gmm" in ln]
        assert len(gmm) == 8, len(gmm)  # gate-up and down, per source rank
    assert dispatched == list(capacities)
    ragged = re.findall(r" ragged-all-to-all\(", hlo)
    assert len(ragged) == 2 * len(capacities)
    # the only all-to-alls left move one offset per rank
    for line in re.findall(r".* all-to-all\(.*", hlo):
        assert re.search(r"= s32\[4(,1)*\]", line), line
