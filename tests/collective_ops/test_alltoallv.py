"""alltoallv (MPI_Alltoallv) on the CPU mesh against a numpy twin.

Off TPU the mesh tier runs the padded exchange (XLA:CPU has no
ragged-all-to-all); ``tests/test_tpu_compile.py`` compiles the ragged one
for a described v5e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m4j

N = 4
ROWS = 6
D = 3


@pytest.fixture(scope="module")
def mesh():
    return m4j.make_mesh(N)


def twin(xs, counts, out_rows):
    """Rank ``dst``'s output: the chunks of every rank, in rank order,
    each rank's chunks laid back to back in its input; ``counts[src,
    dst]`` rows from ``src`` to ``dst``."""
    outs = []
    for dst in range(N):
        out = np.zeros((out_rows, D), xs.dtype)
        at = 0
        for src in range(N):
            start = counts[src, :dst].sum()
            c = counts[src, dst]
            out[at:at + c] = xs[src, start:start + c]
            at += c
        outs.append(out)
    return np.stack(outs)


def exchange(mesh, xs, counts, out_rows):
    recv = counts.T.copy()  # recv[dst, src] = counts[src, dst]

    def body(x, send, got):
        return m4j.alltoallv(x[0], send[0], got[0], out_rows=out_rows)[None]

    out = jax.jit(m4j.spmd(body, mesh=mesh))(
        jnp.asarray(xs), jnp.asarray(counts, jnp.int32),
        jnp.asarray(recv, jnp.int32))
    return np.asarray(out)


def data(seed):
    return np.random.RandomState(seed).randn(N, ROWS, D).astype(np.float32)


@pytest.mark.parametrize("case", ["ragged_with_zeros", "all_to_one_peer"])
def test_alltoallv_matches_twin(mesh, case):
    rng = np.random.RandomState(1)
    if case == "ragged_with_zeros":
        counts = rng.randint(0, 3, (N, N))
        counts[0] = 0          # rank 0 sends nothing
        counts[:, 2] = 0       # rank 2 receives nothing
        counts[1, 1] = ROWS - counts[1].sum() + counts[1, 1]
    else:
        counts = np.zeros((N, N), int)
        counts[:, 3] = ROWS    # every row of every rank to rank 3
    assert (counts.sum(axis=1) <= ROWS).all()
    out_rows = N * ROWS
    xs = data(2)
    np.testing.assert_array_equal(exchange(mesh, xs, counts, out_rows),
                                  twin(xs, counts, out_rows))


def test_alltoallv_round_trip(mesh):
    """Sending the received chunks back with the counts transposed puts
    every row home, in place; rows never sent come back zero."""
    rng = np.random.RandomState(3)
    counts = rng.randint(0, 2, (N, N))
    out_rows = N * ROWS
    xs = data(4)

    def body(x, send, got):
        there = m4j.alltoallv(x[0], send[0], got[0], out_rows=out_rows)
        back = m4j.alltoallv(there, got[0], send[0], out_rows=ROWS)
        return there[None], back[None]

    there, back = jax.jit(m4j.spmd(body, mesh=mesh))(
        jnp.asarray(xs), jnp.asarray(counts, jnp.int32),
        jnp.asarray(counts.T.copy(), jnp.int32))
    np.testing.assert_array_equal(np.asarray(there),
                                  twin(xs, counts, out_rows))
    want = np.zeros_like(xs)
    for r in range(N):
        n = counts[r].sum()
        want[r, :n] = xs[r, :n]
    np.testing.assert_array_equal(np.asarray(back), want)


def test_alltoallv_displacements(mesh):
    """Explicit displacements: each rank sends its rows in reverse rank
    order and the receiver stores chunks from the top down."""
    counts = np.ones((N, N), int)
    send_displs = np.tile(np.arange(N)[::-1], (N, 1))
    recv_displs = np.tile(np.arange(N)[::-1], (N, 1))
    xs = data(5)

    def body(x, sd, rd):
        one = jnp.ones((N,), jnp.int32)
        return m4j.alltoallv(x[0], one, one, out_rows=N, send_displs=sd[0],
                             recv_displs=rd[0])[None]

    out = np.asarray(jax.jit(m4j.spmd(body, mesh=mesh))(
        jnp.asarray(xs), jnp.asarray(send_displs, jnp.int32),
        jnp.asarray(recv_displs, jnp.int32)))
    for dst in range(N):
        for src in range(N):
            np.testing.assert_array_equal(out[dst, N - 1 - src],
                                          xs[src, N - 1 - dst])


def test_alltoallv_bad_counts_shape(mesh):
    def body(x):
        c = jnp.ones((N + 1,), jnp.int32)
        return m4j.alltoallv(x[0], c, c, out_rows=4)[None]

    with pytest.raises(ValueError, match="one entry per rank"):
        m4j.spmd(body, mesh=mesh)(jnp.zeros((N, ROWS, D)))
