"""Pallas RDMA ring collectives vs XLA builtin collectives.

Mirrors the reference's identity-based per-op testing style
(/root/reference/tests/collective_ops/test_allreduce.py:13-32) but checks the
DMA path against the XLA collective path — both run on the 8-device CPU mesh,
the DMA kernels under Pallas TPU interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from mpi4jax_tpu.ops import pallas_collectives as pc
from mpi4jax_tpu.ops._mesh_impl import ring_perm

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 devices"
)


def _mesh(n=4):
    return jax.make_mesh((n,), ("x",))


def _smap(fn, mesh, in_specs=P("x"), out_specs=P("x")):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("shift", [1, -1, 2])
def test_ring_shift_matches_ppermute(dtype, shift):
    mesh = _mesh()
    n = 4
    x = jnp.arange(n * 8 * 128).reshape(n * 8, 128).astype(dtype)
    got = _smap(lambda v: pc.ring_shift(v, "x", shift), mesh)(x)
    want = _smap(
        lambda v: lax.ppermute(v, "x", ring_perm(n, shift)), mesh
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_all_gather_matches_lax():
    mesh = _mesh()
    x = jnp.arange(4 * 6 * 32, dtype=jnp.float32).reshape(4 * 6, 32)
    got = _smap(
        lambda v: pc.all_gather(v, "x"), mesh, out_specs=P(None, "x")
    )(x)
    want = _smap(
        lambda v: lax.all_gather(v, "x", axis=0, tiled=False),
        mesh,
        out_specs=P(None, "x"),
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_reduce_scatter_matches_psum_chunk():
    mesh = _mesh()
    n = 4
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n * n * 3, 16), np.float32)

    def rs(v):
        return pc.reduce_scatter_sum(v, "x")

    got = _smap(rs, mesh)(x)

    def ref(v):
        full = lax.psum(v, "x")
        c = v.shape[0] // n
        return lax.dynamic_slice_in_dim(
            full, lax.axis_index("x") * c, c, axis=0
        )

    want = _smap(ref, mesh)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize(
    "shape",
    [(4 * 8, 32), (2048, 4), (7, 5), (3,), ()],
    ids=["butterfly", "ring-rs-ag", "odd", "tiny", "scalar"],
)
def test_allreduce_matches_psum(shape):
    mesh = _mesh()
    rng = np.random.RandomState(1)
    full = (4,) + shape
    x = jnp.asarray(rng.randn(*full), np.float32)
    got = _smap(lambda v: pc.allreduce_sum(v[0], "x")[None], mesh)(x)
    want = _smap(lambda v: lax.psum(v[0], "x")[None], mesh)(x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_allreduce_grad_matches_psum_grad():
    mesh = _mesh()
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4 * 4, 8), np.float32)
    w = jnp.asarray(rng.randn(4 * 4, 8), np.float32)

    def loss_pc(v, w):
        return jnp.sum(pc.allreduce_sum(v, "x") * w)

    def loss_ref(v, w):
        return jnp.sum(lax.psum(v, "x") * w)

    def gradder(loss):
        def f(v, w):
            g = jax.grad(loss)(v, w)
            return g

        return _smap(f, mesh, in_specs=(P("x"), P("x")))

    got = gradder(loss_pc)(x, w)
    want = gradder(loss_ref)(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_butterfly_allreduce_8dev():
    """log2(8)=3 XOR exchanges; payload small enough for the butterfly."""
    mesh = jax.make_mesh((8,), ("x",))
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(8 * 64), np.float32)
    assert 64 <= pc.BUTTERFLY_MAX_ELEMS
    got = _smap(lambda v: pc.allreduce_sum(v, "x"), mesh)(x)
    want = _smap(lambda v: lax.psum(v, "x"), mesh)(x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_ring_shift2_both_directions():
    mesh = _mesh()
    x = jnp.arange(4 * 8 * 16, dtype=jnp.float32).reshape(4 * 8, 16)

    def f(v):
        a, b = pc.ring_shift2(v, 2.0 * v, "x")
        return a + b

    got = _smap(f, mesh)(x)

    def ref(v):
        a = lax.ppermute(v, "x", ring_perm(4, 1))
        b = lax.ppermute(2.0 * v, "x", ring_perm(4, -1))
        return a + b

    want = _smap(ref, mesh)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bidirectional_allreduce_matches_psum():
    """Payloads over BIDIR_MIN_ELEMS take the split two-direction ring."""
    mesh = _mesh()
    rng = np.random.RandomState(9)
    assert 4 * 4096 >= pc.BIDIR_MIN_ELEMS
    x = jnp.asarray(rng.randn(4 * 4096 + 4 * 3), np.float32)  # odd: pads
    got = _smap(lambda v: pc.allreduce_sum(v, "x"), mesh)(x)
    want = _smap(lambda v: lax.psum(v, "x"), mesh)(x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("count", [4 * 4096, 4 * 4096 + 37, 513, 3],
                         ids=["aligned", "odd", "small-odd", "tiny"])
def test_fused_ring_allreduce_matches_numpy_twin(count):
    """The ICI data plane's bit-exactness contract: the fused
    double-buffered ring kernel folds with EXACTLY the numpy
    ``simulate_ring_sum`` association (which is also the off-pallas
    backend of ``topo/_ici_leg.py``) — every device, every byte."""
    from mpi4jax_tpu import topo

    mesh = _mesh()
    rng = np.random.RandomState(count)
    rows = rng.randn(4, count).astype(np.float32) * 3
    got = _smap(
        lambda v: pc.fused_ring_allreduce_sum(v.reshape(-1), "x")[None],
        mesh,
    )(jnp.asarray(rows))
    want = topo.simulate_ring_sum([rows[r] for r in range(4)])
    for r in range(4):
        np.testing.assert_array_equal(np.asarray(got)[r], want), r


def test_fused_ring_allreduce_grad_is_itself():
    # d(sum_r x_r)/dx = the same allreduce of the cotangents
    mesh = _mesh()
    rng = np.random.RandomState(21)
    x = jnp.asarray(rng.randn(4 * 600), np.float32)
    w = jnp.asarray(rng.randn(4 * 600), np.float32)

    def make(ar):
        def f(v, w):
            return jax.grad(lambda v: jnp.sum(ar(v) * w))(v)

        return _smap(f, mesh, in_specs=(P("x"), P("x")))

    got = make(lambda v: pc.fused_ring_allreduce_sum(v, "x"))(x, w)
    want = make(lambda v: lax.psum(v, "x"))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_large_allreduce_dispatches_to_fused_ring():
    # the dispatch arm: bandwidth-bound payloads on n > 2 ride the
    # fused kernel, so allreduce_sum must be bit-identical to it there
    # (the threshold is per shard: each of the 4 holds BIDIR_MIN_ELEMS+8)
    mesh = _mesh()
    rng = np.random.RandomState(23)
    x = jnp.asarray(rng.randn(4 * (pc.BIDIR_MIN_ELEMS + 8)), np.float32)
    via_dispatch = _smap(lambda v: pc.allreduce_sum(v, "x"), mesh)(x)
    direct = _smap(lambda v: pc.fused_ring_allreduce_sum(v, "x"), mesh)(x)
    np.testing.assert_array_equal(np.asarray(via_dispatch),
                                  np.asarray(direct))


def test_ring_shift2_grad():
    mesh = _mesh()
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(4 * 4, 8), np.float32)
    w1 = jnp.asarray(rng.randn(4 * 4, 8), np.float32)
    w2 = jnp.asarray(rng.randn(4 * 4, 8), np.float32)

    def make(step):
        def f(v, w1, w2):
            return jax.grad(
                lambda v: jnp.sum(sum(jnp.multiply(o, w)
                                      for o, w in zip(step(v), (w1, w2))))
            )(v)

        return _smap(f, mesh, in_specs=(P("x"), P("x"), P("x")))

    got = make(lambda v: pc.ring_shift2(v, 3.0 * v, "x"))(x, w1, w2)
    want = make(
        lambda v: (
            lax.ppermute(v, "x", ring_perm(4, 1)),
            lax.ppermute(3.0 * v, "x", ring_perm(4, -1)),
        )
    )(x, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("periodic", [True, False])
def test_halo_exchange_rdma_matches_ppermute(monkeypatch, periodic):
    from mpi4jax_tpu.parallel.grid import ProcessGrid
    from mpi4jax_tpu.parallel.halo import halo_exchange

    grid = ProcessGrid((2, 4))
    rng = np.random.RandomState(12)
    ny, nx = 2 * 6, 4 * 6
    a = jnp.asarray(rng.randn(ny, nx), np.float32)
    b = jnp.asarray(rng.randn(ny, nx), np.float32)

    def run():
        def f(a, b):
            return halo_exchange((a, b), grid, halo=1, periodic=periodic)

        return jax.jit(
            shard_map(
                f,
                mesh=grid.mesh,
                in_specs=(P(*grid.axes),) * 2,
                out_specs=(P(*grid.axes),) * 2,
            )
        )(a, b)

    base = run()
    monkeypatch.setenv("MPI4JAX_TPU_PALLAS_COLLECTIVES", "1")
    rdma = run()
    for g, w in zip(rdma, base):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_ring_shift_n_matches_sequential():
    mesh = _mesh()
    x = jnp.arange(4 * 4 * 8, dtype=jnp.float32).reshape(4 * 4, 8)

    def f(v):
        a, b, c = pc.ring_shift_n((v, 2.0 * v, v + 1.0), "x")
        return a + b + c

    got = _smap(f, mesh)(x)
    perm = ring_perm(4, 1)
    want = _smap(
        lambda v: sum(lax.ppermute(p, "x", perm)
                      for p in (v, 2.0 * v, v + 1.0)),
        mesh,
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_shift_n_grad_matches_ppermute():
    mesh = _mesh()
    rng = np.random.RandomState(14)
    x = jnp.asarray(rng.randn(4 * 4, 8), np.float32)
    w = jnp.asarray(rng.randn(4 * 4, 8), np.float32)

    def make(shifter):
        def f(v, w):
            def loss(v):
                a, b = shifter(v)
                return jnp.sum(a * w) + jnp.sum(b * (2.0 * w))

            return jax.grad(loss)(v)

        return _smap(f, mesh, in_specs=(P("x"), P("x")))

    perm = ring_perm(4, 1)
    got = make(lambda v: pc.ring_shift_n((v, v * v), "x"))(x, w)
    want = make(
        lambda v: (lax.ppermute(v, "x", perm),
                   lax.ppermute(v * v, "x", perm))
    )(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ring_attention_rdma_matches_xla(monkeypatch):
    """Ring attention's k/v rotation rides ring_shift_n under the flag and
    must agree with the ppermute ring bit-for-bit."""
    from mpi4jax_tpu.parallel.ring import ring_attention

    mesh = _mesh()
    rng = np.random.RandomState(13)
    b, t, h, d = 2, 4 * 8, 2, 16
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), np.float32)
               for _ in range(3))

    def run():
        return jax.jit(
            shard_map(
                lambda q, k, v: ring_attention(
                    q, k, v, axis="x", causal=True, impl="xla"
                ),
                mesh=mesh,
                in_specs=(P(None, "x"),) * 3,
                out_specs=P(None, "x"),
            )
        )(q, k, v)

    base = run()
    monkeypatch.setenv("MPI4JAX_TPU_PALLAS_COLLECTIVES", "1")
    rdma = run()
    np.testing.assert_array_equal(np.asarray(rdma), np.asarray(base))


@pytest.mark.parametrize("nmesh", [(4,), (2, 4)])
def test_alltoall_direct_matches_lax(nmesh):
    axes = ("a", "b")[: len(nmesh)]
    mesh = jax.make_mesh(nmesh, axes)
    axis = axes[-1]
    n = nmesh[-1]
    rng = np.random.RandomState(15)
    total = int(np.prod(nmesh))
    x = jnp.asarray(rng.randn(total * n * 3, 8), np.float32)
    spec = P(tuple(axes))

    got = jax.jit(
        shard_map(
            lambda v: pc.alltoall(v.reshape(n, -1, 8), axis).reshape(v.shape),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )(x)
    want = jax.jit(
        shard_map(
            lambda v: lax.all_to_all(
                v.reshape(n, -1, 8), axis, split_axis=0, concat_axis=0
            ).reshape(v.shape),
            mesh=mesh, in_specs=spec, out_specs=spec,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_alltoall_direct_complex():
    """The spectral FFT's slab transpose moves complex64 — byte-exact
    through the DMA path."""
    mesh = _mesh()
    rng = np.random.RandomState(17)
    x = jnp.asarray(
        rng.randn(4 * 4, 8) + 1j * rng.randn(4 * 4, 8), np.complex64
    )
    got = _smap(
        lambda v: pc.alltoall(v.reshape(4, -1, 8), "x").reshape(v.shape),
        mesh,
    )(x)
    want = _smap(
        lambda v: lax.all_to_all(
            v.reshape(4, -1, 8), "x", split_axis=0, concat_axis=0
        ).reshape(v.shape),
        mesh,
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spectral_fft_rdma_matches(monkeypatch):
    """End-to-end: the distributed FFT's alltoall transposes ride the
    direct RDMA kernel under the flag, same spectrum either way."""
    from mpi4jax_tpu.models import spectral

    n = 16
    rng = np.random.RandomState(18)
    u = jnp.asarray(rng.randn(n, n, n), np.float32)
    mesh = jax.make_mesh((4,), ("x",))

    def run():
        return jax.jit(
            shard_map(
                lambda v: spectral.ifft3(spectral.fft3(v, axis="x"),
                                         axis="x").real,
                mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            )
        )(u)

    base = run()
    monkeypatch.setenv("MPI4JAX_TPU_PALLAS_COLLECTIVES", "1")
    rdma = run()
    np.testing.assert_allclose(
        np.asarray(rdma), np.asarray(base), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(rdma), np.asarray(u),
                               rtol=1e-4, atol=1e-4)


def test_alltoall_direct_grad():
    mesh = _mesh()
    rng = np.random.RandomState(16)
    x = jnp.asarray(rng.randn(4 * 4, 6), np.float32)
    w = jnp.asarray(rng.randn(4 * 4, 6), np.float32)

    def make(op):
        def f(v, w):
            return jax.grad(
                lambda v: jnp.sum(op(v.reshape(4, -1, 6)) * w.reshape(4, -1, 6))
            )(v)

        return _smap(f, mesh, in_specs=(P("x"), P("x")))

    got = make(lambda v: pc.alltoall(v, "x"))(x, w)
    want = make(
        lambda v: lax.all_to_all(v, "x", split_axis=0, concat_axis=0)
    )(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ring_shift_of():
    assert pc.ring_shift_of(ring_perm(8, 1), 8) == 1
    assert pc.ring_shift_of(ring_perm(8, -1), 8) == 7
    assert pc.ring_shift_of(ring_perm(8, 3), 8) == 3
    assert pc.ring_shift_of([(0, 1)], 8) is None
    assert pc.ring_shift_of([(i, i) for i in range(8)], 8) is None
    # not a uniform shift
    assert pc.ring_shift_of([(0, 1), (1, 0), (2, 3), (3, 2)], 4) is None


def test_multidim_mesh_ring_shift():
    """On a 2-D mesh the DMA target must be the *global* logical id — the
    neighbor on the ring axis within this device's row/column."""
    mesh = jax.make_mesh((2, 4), ("a", "b"))
    x = jnp.arange(8 * 8 * 16, dtype=jnp.float32).reshape(8 * 8, 16)

    for axis in ("a", "b"):
        got = jax.jit(
            shard_map(
                lambda v: pc.ring_shift(v, axis),
                mesh=mesh,
                in_specs=P(("a", "b")),
                out_specs=P(("a", "b")),
            )
        )(x)
        n = mesh.shape[axis]
        want = jax.jit(
            shard_map(
                lambda v: lax.ppermute(v, axis, ring_perm(n, 1)),
                mesh=mesh,
                in_specs=P(("a", "b")),
                out_specs=P(("a", "b")),
            )
        )(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_multidim_mesh_allreduce_matches_psum():
    mesh = jax.make_mesh((2, 4), ("a", "b"))
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8 * 4, 8), np.float32)
    for axis in ("a", "b"):
        got = jax.jit(
            shard_map(
                lambda v: pc.allreduce_sum(v, axis),
                mesh=mesh,
                in_specs=P(("a", "b")),
                out_specs=P(("a", "b")),
            )
        )(x)
        want = jax.jit(
            shard_map(
                lambda v: lax.psum(v, axis),
                mesh=mesh,
                in_specs=P(("a", "b")),
                out_specs=P(("a", "b")),
            )
        )(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5
        )


def test_ring_shift_grad_is_inverse_shift():
    """Transpose flows the cotangent backward along the message edge —
    the reference sendrecv's source/dest swap (sendrecv.py:390-409)."""
    mesh = _mesh()
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4 * 4, 8), np.float32)
    w = jnp.asarray(rng.randn(4 * 4, 8), np.float32)

    def make(shifter):
        def f(v, w):
            return jax.grad(
                lambda v: jnp.sum(shifter(v) * w)
            )(v)

        return _smap(f, mesh, in_specs=(P("x"), P("x")))

    got = make(lambda v: pc.ring_shift(v, "x", 1))(x, w)
    want = make(lambda v: lax.ppermute(v, "x", ring_perm(4, 1)))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_all_gather_grad_matches_lax():
    mesh = _mesh()
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(4 * 4, 8), np.float32)

    def make(gatherer):
        def f(v):
            return jax.grad(lambda v: jnp.sum(gatherer(v) ** 2))(v)

        return _smap(f, mesh)

    got = make(lambda v: pc.all_gather(v, "x"))(x)
    want = make(
        lambda v: lax.all_gather(v, "x", axis=0, tiled=False)
    )(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_fwd_mode_raises():
    mesh = _mesh()
    x = jnp.ones((4 * 4, 8), np.float32)

    def f(v):
        return jax.jvp(
            lambda v: pc.ring_shift(v, "x", 1), (v,), (v,)
        )[1]

    with pytest.raises(TypeError):
        _smap(f, mesh)(x)


def test_mesh_tier_routing(monkeypatch):
    """With the flag set, the public mesh-tier ops ride the DMA path and
    still produce identical results."""
    monkeypatch.setenv("MPI4JAX_TPU_PALLAS_COLLECTIVES", "1")
    from mpi4jax_tpu.ops import _mesh_impl as m
    from mpi4jax_tpu.ops.reduce_ops import SUM

    mesh = _mesh()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4 * 8, 16), np.float32)

    got = _smap(lambda v: m.allreduce(v, SUM, "x"), mesh)(x)
    want = _smap(lambda v: lax.psum(v, "x"), mesh)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    got = _smap(
        lambda v: m.sendrecv(v, ring_perm(4, 1), "x"), mesh
    )(x)
    want = _smap(
        lambda v: lax.ppermute(v, "x", ring_perm(4, 1)), mesh
    )(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
