"""World tier ON THE TPU: every op inside ``jax.jit`` on device arrays.

A world job whose ranks run on the TPU (no JAX_PLATFORMS=cpu pin):
every world op moves device (HBM) buffers through the ordered host
callback into the native transport and back — the structural analog of
the reference's GPU bridge staging D2H → MPI → H2D
(mpi_xla_bridge_gpu.pyx:233-251 there).  Exercises every collective,
the p2p ops via MPI-style self-messaging, Status introspection, the
ordering of callbacks inside ``lax.scan`` and autodiff through them.

Run by chip_smoke.py and bench.py; also runnable by hand:
    python -m mpi4jax_tpu.runtime.launch -n 1 --platform tpu \
        tests/world_programs/tpu_world.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

import mpi4jax_tpu as m4j


def main():
    dev = jax.devices()[0]
    platform = dev.platform
    assert platform == "tpu", (
        f"this program must run on the TPU, got {platform}"
    )

    comm = m4j.get_default_comm()
    rank, size = comm.rank(), comm.size()

    x = jnp.arange(8, dtype=jnp.float32) + rank
    assert dev in x.devices(), (x.devices(), dev)

    # every collective in one jitted program on device-resident buffers
    @jax.jit
    def collectives(x):
        stacked = jnp.stack([x] * size)
        out = (
            m4j.allreduce(x, op=m4j.SUM, comm=comm),
            m4j.allreduce(x, op=m4j.MAX, comm=comm),
            m4j.allgather(x, comm=comm),
            m4j.alltoall(stacked, comm=comm),
            m4j.bcast(x, root=0, comm=comm),
            m4j.reduce(x, op=m4j.SUM, root=0, comm=comm),
            m4j.scan(x, op=m4j.SUM, comm=comm),
            m4j.gather(x, root=0, comm=comm),
            m4j.scatter(stacked, root=0, comm=comm),
        )
        m4j.barrier(comm=comm)
        return out

    ar_sum, ar_max, ag, a2a, bc, red, sc, g, mine = collectives(x)
    assert dev in ar_sum.devices(), "result must land back on the TPU"

    expect = np.arange(8) * size + sum(range(size))
    np.testing.assert_allclose(np.asarray(ar_sum), expect)
    np.testing.assert_allclose(np.asarray(ar_max), np.arange(8) + size - 1)
    assert ag.shape == (size, 8)
    assert a2a.shape == (size, 8)
    np.testing.assert_allclose(np.asarray(bc), np.arange(8))
    if rank == 0:
        np.testing.assert_allclose(np.asarray(red), expect)
        assert g.shape == (size, 8)
    np.testing.assert_allclose(
        np.asarray(sc), np.cumsum([np.arange(8) + r for r in range(rank + 1)],
                                  axis=0)[-1])
    np.testing.assert_allclose(np.asarray(mine), np.arange(8))  # root row

    # p2p + Status via self-messaging (reference allows self-sendrecv —
    # its exit-flush regression depends on it, test_common.py:91-114)
    st = m4j.Status()
    out = jax.jit(lambda v: m4j.sendrecv(
        v, source=rank, dest=rank, status=st, comm=comm))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
    assert st.Get_source() == rank and st.Get_count(np.float32) == 8, st

    st2 = m4j.Status()

    @jax.jit
    def send_then_recv(v):
        m4j.send(v * 2, dest=rank, tag=9, comm=comm)
        return m4j.recv(v, source=m4j.ANY_SOURCE, status=st2, comm=comm)

    out = send_then_recv(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2)
    assert st2.Get_source() == rank and st2.Get_tag() == 9, st2

    # ordered effects must serialize the callbacks inside lax.scan (the
    # reference's fori_loop halo pattern, shallow_water.py:415-420)
    def body(carry, _):
        carry = m4j.allreduce(carry, op=m4j.SUM, comm=comm) / size
        carry = m4j.sendrecv(carry, source=rank, dest=rank, comm=comm)
        return carry, ()

    looped, _ = jax.jit(
        lambda v: jax.lax.scan(body, v, None, length=4)
    )(jnp.ones((4,), jnp.float32))
    np.testing.assert_allclose(np.asarray(looped), 1.0, rtol=1e-6)

    # autodiff through the callback path
    grad = jax.jit(jax.grad(
        lambda v: m4j.allreduce(v, op=m4j.SUM, comm=comm).sum()
    ))(x)
    np.testing.assert_allclose(np.asarray(grad), 1.0)

    print(f"tpu_world OK (rank {rank} of {size}, platform {platform}, "
          f"{dev.device_kind}, ops in jit via ordered host callbacks)",
          flush=True)


if __name__ == "__main__":
    main()
