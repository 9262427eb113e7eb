"""alltoallv — exchange chunks of different lengths (MPI_Alltoallv).

Rank ``r`` sends rows ``send_displs[j] : send_displs[j] + send_counts[j]``
of ``x`` to rank ``j``; they land in rank ``j``'s output at rows
``recv_displs[r] : recv_displs[r] + recv_counts[r]``.  As in MPI, the
counts must agree: rank ``r``'s ``send_counts[j]`` is rank ``j``'s
``recv_counts[r]`` (exchange the counts first, e.g. with
:func:`~mpi4jax_tpu.ops.allgather`, where they are not known).

This is the exchange of a dropless expert-parallel dispatch: each rank
sends every peer exactly the rows routed there, where
:func:`~mpi4jax_tpu.ops.alltoall` would pad every chunk to the largest.
The mesh tier is one ``lax.ragged_all_to_all`` on TPU.  XLA:CPU has no
ragged all-to-all, so off TPU the same exchange runs as a padded
``lax.all_to_all`` (every chunk padded to ``x``'s rows) and a masked
placement: the same rows land in the same places.  The world tier has no
implementation yet.
"""

from __future__ import annotations

from ..utils import validation as _validation
from . import _dispatch, _mesh_impl


def alltoallv(x, send_counts, recv_counts, *, out_rows, send_displs=None,
              recv_displs=None, comm=None, token=None):
    """Exchange ragged chunks of ``x``'s rows with every rank.

    Args:
        x: array ``(rows, ...)``; the chunk for rank ``j`` is a block of
            its rows.
        send_counts: int32 ``(size,)``, rows sent to each rank.
        recv_counts: int32 ``(size,)``, rows received from each rank.
        out_rows: static number of rows of the output.
        send_displs: int32 ``(size,)``, first row of each chunk in ``x``
            (default: the chunks back to back, in rank order).
        recv_displs: int32 ``(size,)``, first output row of the chunk from
            each rank (default: back to back, in rank order).
        comm: communicator (default: ambient).
        token: optional ordering token; if given, returns ``(result,
            token)``.

    Returns:
        ``(out_rows, ...)`` array of ``x``'s dtype; rows that no chunk
        writes are zero.
    """
    import jax.numpy as jnp

    x = _validation.check_array("x", x)
    comm = _dispatch.resolve_comm(comm)
    if not _dispatch.is_mesh(comm):
        _validation.fail(
            "alltoallv is implemented on the mesh tier only (one "
            "ragged_all_to_all inside shard_map); the world-tier transport "
            "has no ragged exchange",
            op="alltoallv", comm=comm, x=x, exc=NotImplementedError)
    if x.ndim < 1:
        _validation.fail("alltoallv needs an array of rows",
                         op="alltoallv", comm=comm, x=x, exc=ValueError)

    def displs(counts, given):
        counts = jnp.asarray(counts, jnp.int32)
        if given is None:
            given = jnp.cumsum(counts) - counts
        return counts, jnp.asarray(given, jnp.int32)

    send_counts, send_displs = displs(send_counts, send_displs)
    recv_counts, recv_displs = displs(recv_counts, recv_displs)
    body = lambda v: _mesh_impl.alltoallv(
        v, send_counts, send_displs, recv_counts, recv_displs,
        int(out_rows), comm.axis)
    return _dispatch.maybe_tokenized(body, x, token)
