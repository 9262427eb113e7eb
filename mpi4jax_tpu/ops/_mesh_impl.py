"""Mesh/ICI-tier implementations of the 12 ops as XLA collectives.

This is the TPU-native core of the framework.  Where the reference lowers
every op to a host custom call into libmpi
(/root/reference/mpi4jax/_src/collective_ops/*.py → mpi_xla_bridge.pyx), here
each op *is* an XLA collective inside ``shard_map``: the compiler schedules it
onto ICI, fuses around it, and — because every rank runs the same SPMD
program — ordering and deadlock-freedom hold by construction (the property
the reference's token system exists to provide, docs/sharp-bits.rst there).

All functions below must be called inside ``shard_map`` (or ``spmd``) with
``axis`` bound.  ``rank`` is ``lax.axis_index(axis)`` (traced, uniform
program), ``size`` is ``lax.axis_size(axis)`` (static).

Collective mapping (reference op → XLA collective):

==============  =====================================================
allreduce       ``lax.psum/pmax/pmin``; generic ops all-gather+reduce
allgather       ``lax.all_gather(axis=0)``
alltoall        ``lax.all_to_all(split_axis=0, concat_axis=0)``
alltoallv       ``lax.ragged_all_to_all`` (padded ``all_to_all`` off TPU)
bcast           masked ``psum`` (only root contributes)
reduce          allreduce + select (non-root keeps its input)
scan            Hillis–Steele ladder of ``lax.ppermute`` (log2 steps)
scatter         ``lax.all_to_all`` + static root row
gather          allgather (result replicated — SPMD divergence, DESIGN.md)
sendrecv        ``lax.ppermute``
barrier         cross-rank psum dependency (SPMD programs need no barrier)
send/recv       rejected — meaningless as separate calls in one SPMD
                program; world tier provides exact reference semantics
==============  =====================================================
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import config as _config
from ..utils import dtypes as _dtypes
from .reduce_ops import ReduceOp, SUM


def _pallas_ring(axis):
    """True when the Pallas RDMA fast path should handle this collective:
    opt-in flag set, ``axis`` is a single named axis, and the global
    logical device id of a ring neighbor is computable (every mesh axis
    bound — see ``pallas_collectives.can_route``).  Under the flag the
    routed ops are reverse-mode differentiable only (fwd-mode raises,
    like the reference's sendrecv, sendrecv.py:150-155 there)."""
    if not _config.pallas_collectives_enabled():
        return False
    from . import pallas_collectives as _pc

    return _pc.can_route(axis)


def _rank(axis):
    return lax.axis_index(axis)


def _size(axis) -> int:
    return lax.axis_size(axis)


def _axes_tuple(axis):
    return (axis,) if isinstance(axis, str) else tuple(axis)


def as_varying(x, axis):
    """Promote ``x`` to be varying over ``axis`` (VMA bookkeeping).

    Under ``shard_map(..., check_vma=True)`` (the default), collectives
    require their operand's varying-axes set to include the collective
    axis; constants and replicated closures arrive invarying.  This makes
    every op accept either, so the ops work in user shard_maps regardless
    of the check mode.
    """
    from ..utils.jax_compat import vma_check_mode

    checked = vma_check_mode()
    if checked is None:
        # a wrong guess either corrupts transposed programs (pcast under
        # unchecked shard_map) or trips collective vma errors — fail loud
        raise RuntimeError(
            "cannot determine shard_map's check_vma mode (private jax API "
            "moved); update mpi4jax_tpu.utils.jax_compat.vma_check_mode"
        )
    if not checked:
        # unchecked shard_map: vma is untracked (always empty) and pcast's
        # transpose (a psum) would corrupt/abort transposed programs
        return x
    try:
        vma = jax.typeof(x).vma
    except (AttributeError, TypeError):
        return x
    missing = tuple(a for a in _axes_tuple(axis) if a not in vma)
    if missing:
        x = lax.pcast(x, missing, to="varying")
    return x


def _masked(x, keep):
    """x where keep (scalar traced bool) else zeros, preserving dtype."""
    return jnp.where(keep, x, jnp.zeros_like(x))


def allreduce(x, op: ReduceOp, axis):
    op.check_dtype(x.dtype)
    x = as_varying(x, axis)
    if op.lax_kind == "sum":
        if _pallas_ring(axis):
            from . import pallas_collectives as _pc

            # bandwidth-bound payloads dispatch to the fused
            # double-buffered ring kernel inside (one launch for all
            # hops; the same data plane the hierarchical schedules'
            # ICI intra leg rides — topo/_ici_leg.py)
            return _pc.allreduce_sum(x, axis)
        return lax.psum(x, axis)
    if op.lax_kind == "max":
        return lax.pmax(x, axis)
    if op.lax_kind == "min":
        return lax.pmin(x, axis)
    if op.custom:
        # user-defined: always the generic gather+reduce path — the
        # domain-based fast paths below are for the named builtins only
        stacked = lax.all_gather(x, axis, axis=0, tiled=False)
        return op.reduce(stacked).astype(x.dtype)
    if op.domain == "bool":
        # Logical ops ride the fused min/max collectives on a 0/1 view
        # (truthiness, so integer inputs behave like MPI's logical ops).
        bits = (x != 0).astype(jnp.uint8)
        if op.name == "LAND":
            out = lax.pmin(bits, axis)
        elif op.name == "LOR":
            out = lax.pmax(bits, axis)
        else:  # LXOR: parity of the count of true values
            out = (lax.psum(bits.astype(jnp.uint32), axis) % 2).astype(jnp.uint8)
        return out.astype(x.dtype)
    # PROD / bitwise: no fused XLA collective — gather then reduce locally.
    stacked = lax.all_gather(x, axis, axis=0, tiled=False)
    return op.reduce(stacked).astype(x.dtype)


def allgather(x, axis):
    x = as_varying(x, axis)
    if _pallas_ring(axis):
        from . import pallas_collectives as _pc

        return _pc.all_gather(x, axis)
    return lax.all_gather(x, axis, axis=0, tiled=False)


def alltoall(x, axis):
    size = _size(axis)
    if x.ndim < 1 or x.shape[0] != size:
        raise ValueError(
            f"alltoall requires leading axis == communicator size ({size}), "
            f"got shape {x.shape}"
        )
    x = as_varying(x, axis)
    if _pallas_ring(axis):
        from . import pallas_collectives as _pc

        return _pc.alltoall(x, axis)
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0)


def alltoallv(x, send_counts, send_displs, recv_counts, recv_displs,
              out_rows: int, axis, *, ragged=None):
    """MPI_Alltoallv over ``axis``: rows ``send_displs[j] +
    [0, send_counts[j])`` of ``x`` land at rows ``recv_displs[me] + [0,
    ...)`` of rank ``j``'s ``(out_rows, ...)`` output; other rows are zero.

    ``ragged`` (default: on TPU) takes ``lax.ragged_all_to_all``;
    otherwise every chunk is padded to ``x``'s rows for one
    ``lax.all_to_all`` and placed by mask, which moves the same rows to
    the same places (XLA:CPU has no ragged all-to-all).
    """
    size = _size(axis)
    for name, v in (("send_counts", send_counts),
                    ("send_displs", send_displs),
                    ("recv_counts", recv_counts),
                    ("recv_displs", recv_displs)):
        if v.shape != (size,):
            raise ValueError(
                f"alltoallv {name} must have shape ({size},) (one entry per "
                f"rank), got {v.shape}")
    if ragged is None:
        from .flash import target_platform

        ragged = target_platform() == "tpu"
    x, send_counts, send_displs, recv_counts, recv_displs = (
        as_varying(v, axis) for v in (x, send_counts, send_displs,
                                      recv_counts, recv_displs))
    out = jnp.zeros((out_rows,) + x.shape[1:], x.dtype)
    if ragged:
        # where each receiver puts this rank's chunk: its recv_displs[me]
        dest_displs = lax.all_to_all(recv_displs, axis, 0, 0, tiled=True)
        return lax.ragged_all_to_all(
            x, as_varying(out, axis), send_displs, send_counts, dest_displs,
            recv_counts, axis_name=axis)
    n = x.shape[0]
    i = jnp.arange(n)
    rows = jnp.clip(send_displs[:, None] + i, 0, n - 1)
    keep = (i < send_counts[:, None]).reshape((size, n) + (1,) * (x.ndim - 1))
    chunks = jnp.where(keep, x[rows], jnp.zeros((), x.dtype))
    got = lax.all_to_all(chunks, axis, 0, 0)  # row i: rank i's chunk to me
    # how many rows each rank sent here: its send_counts[me]
    sent = lax.all_to_all(send_counts, axis, 0, 0, tiled=True)
    r = jnp.arange(out_rows)
    for src in range(size):
        k = r - recv_displs[src]
        hit = ((k >= 0) & (k < sent[src])).reshape(
            (out_rows,) + (1,) * (x.ndim - 1))
        out = jnp.where(hit, got[src][jnp.clip(k, 0, n - 1)], out)
    return out


def bcast(x, root: int, axis):
    _dtypes.check_supported(x.dtype)
    x = as_varying(x, axis)
    r = _rank(axis)
    if x.dtype == jnp.bool_:
        return lax.psum(_masked(x.astype(jnp.uint8), r == root), axis) != 0
    return lax.psum(_masked(x, r == root), axis)


def reduce(x, op: ReduceOp, root: int, axis):
    # Reference contract: root receives the reduction, other ranks get their
    # input back unchanged (rank-dependent *values*, uniform shapes — SPMD ok).
    x = as_varying(x, axis)
    full = as_varying(allreduce(x, op, axis), axis)
    return jnp.where(_rank(axis) == root, full, x)


def gather(x, root: int, axis):
    # SPMD divergence (DESIGN.md): result (size, *shape) is materialized on
    # every rank; the root's view equals the reference's root result.
    del root
    return lax.all_gather(as_varying(x, axis), axis, axis=0, tiled=False)


def scatter(x, root: int, axis):
    size = _size(axis)
    if x.ndim < 1 or x.shape[0] != size:
        raise ValueError(
            f"scatter requires input shape (size, ...) = ({size}, ...) on "
            f"every rank (only root's values are read), got {x.shape}"
        )
    # all_to_all row j of the result holds rank j's chunk addressed to us;
    # row `root` is therefore exactly MPI_Scatter's result.  One collective,
    # O(|x|) traffic per rank — cheaper than bcast-then-slice (2·|x|).
    x = as_varying(x, axis)
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0)[root]


def scan(x, op: ReduceOp, axis):
    """Inclusive prefix reduction across ranks (MPI_Scan).

    Hillis–Steele over ``ppermute``: log2(size) shift-and-combine steps, each
    one ICI hop of the full buffer.  Ranks below the shift distance keep
    their partial (ppermute delivers zeros to ranks with no source; the mask
    keeps identity-correctness for non-SUM ops).
    """
    op.check_dtype(x.dtype)
    size = _size(axis)
    r = _rank(axis)
    acc = as_varying(x, axis)
    shift = 1
    while shift < size:
        shifted = lax.ppermute(
            acc, axis, [(i, i + shift) for i in range(size - shift)]
        )
        acc = jnp.where(r >= shift, op.combine(acc, shifted), acc)
        shift *= 2
    return acc.astype(x.dtype)


def sendrecv(x, perm, axis):
    """Combined send+recv along a static rank permutation (lax.ppermute).

    ``perm`` is a sequence of (source, dest) pairs — the SPMD expression of
    the reference's per-rank (source, dest) arguments
    (/root/reference/mpi4jax/_src/collective_ops/sendrecv.py:46-125).  Ranks
    not appearing as a destination receive zeros.
    """
    x = as_varying(x, axis)
    if _pallas_ring(axis):
        from . import pallas_collectives as _pc

        k = _pc.ring_shift_of(perm, _size(axis))
        if k is not None:
            return _pc.ring_shift(x, axis, k)
    return lax.ppermute(x, axis, perm)


def barrier(axis, tie=None):
    # A compiled SPMD program needs no rank barrier for correctness; this
    # returns a zero scalar that carries a genuine cross-rank data dependency
    # so callers can sequence host-visible work after it.  ``tie`` (e.g. a
    # token) is ordered before the barrier when given.
    z = as_varying(jnp.zeros((), jnp.int32), axis)
    if tie is not None:
        z = lax.optimization_barrier((z, tie))[0]
    return lax.psum(z, axis)


def ring_perm(size: int, shift: int = 1, wrap: bool = True):
    """(source, dest) pairs sending each rank's data to ``rank + shift``."""
    pairs = []
    for i in range(size):
        j = i + shift
        if wrap:
            pairs.append((i, j % size))
        elif 0 <= j < size:
            pairs.append((i, j))
    return pairs
