"""Halo (ghost-cell) exchange for domain-decomposed stencil codes.

The reference's embodiment is ``enforce_boundaries`` — up to four
token-ordered sendrecv/send/recv calls per call, serialized by the token
chain (/root/reference/examples/shallow_water.py:173-271, SURVEY.md §3.5).

TPU-first redesign: one ``lax.ppermute`` per direction per axis, *batched* —
the strips for all fields are exchanged in one collective each, there is no
token chain to serialize (SPMD order suffices), and XLA overlaps the
ppermutes of independent axes.  This addresses SURVEY.md §7 hard part 2
(per-call host round-trips would kill TPU throughput).

Layout convention: a local field of interior shape ``(m, n)`` is stored as
``(m + 2*halo, n + 2*halo)`` with ghost rings on every side.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .grid import ProcessGrid


def _axis_exchange(f, dim: int, axis_name: str, halo: int, periodic: bool):
    """Fill the two ghost strips of ``f`` along array dimension ``dim``."""
    n = lax.axis_size(axis_name)
    extent = f.shape[dim]

    lo_interior = lax.slice_in_dim(f, halo, 2 * halo, axis=dim)
    hi_interior = lax.slice_in_dim(
        f, extent - 2 * halo, extent - halo, axis=dim
    )

    if n == 1:
        if periodic:
            # self-neighbor: wrap own interior strips into own ghosts
            from_above, from_below = hi_interior, lo_interior
        else:
            return f
    else:
        from ..utils import config as _config

        use_rdma = False
        if _config.pallas_collectives_enabled():
            from ..ops import pallas_collectives as _pc

            use_rdma = _pc.can_route(axis_name)
        if use_rdma:
            # one kernel, both directions' DMAs in flight before either
            # wait — both ICI link directions busy (ring_shift2); at
            # non-periodic boundaries the wrapped values are masked below,
            # same as the zeros ppermute would deliver
            from_above, from_below = _pc.ring_shift2(
                hi_interior, lo_interior, axis_name
            )
        else:
            to_prev = [(i, i - 1) for i in range(1, n)]
            to_next = [(i, i + 1) for i in range(n - 1)]
            if periodic:
                to_prev.append((0, n - 1))
                to_next.append((n - 1, 0))
            # neighbor below (index+1) sends its low-interior strip to us →
            # our high ghost; neighbor above (index-1) sends its
            # high-interior → our low ghost.
            from_above = lax.ppermute(hi_interior, axis_name, to_next)
            from_below = lax.ppermute(lo_interior, axis_name, to_prev)

    idx = lax.axis_index(axis_name)
    lo_ghost = lax.slice_in_dim(f, 0, halo, axis=dim)
    hi_ghost = lax.slice_in_dim(f, extent - halo, extent, axis=dim)
    if not periodic:
        # at the physical boundary keep the existing ghost values (the
        # solver's boundary condition), not the zeros ppermute delivers
        from_above = jnp.where(idx > 0, from_above, lo_ghost)
        from_below = jnp.where(idx < n - 1, from_below, hi_ghost)

    start_lo = [0] * f.ndim
    start_hi = [0] * f.ndim
    start_hi[dim] = extent - halo
    f = lax.dynamic_update_slice(f, from_above.astype(f.dtype), start_lo)
    f = lax.dynamic_update_slice(f, from_below.astype(f.dtype), start_hi)
    return f


# one scope over the stacking, the strips, the collectives, the ghost
# writes and the unstacking: every instruction of an exchange carries it in
# its op_name, so a profiler trace tells them from the caller's own
@jax.named_scope("halo_exchange")
def halo_exchange(
    f,
    grid: ProcessGrid,
    *,
    halo: int = 1,
    periodic: Sequence[bool] | bool = True,
    dims: Optional[Sequence[int]] = None,
):
    """Fill ghost rings of ``f`` from grid neighbors along each dimension.

    Args:
        f: local array (or tuple of arrays — exchanged together) whose
            leading ``grid.ndim`` dimensions carry ``halo``-wide ghost rings.
        grid: the :class:`ProcessGrid`.
        halo: ghost width.
        periodic: per-dimension wraparound flag (scalar broadcasts).
        dims: which array dims correspond to grid dims (default: 0..ndim-1).
    """
    single = not isinstance(f, (tuple, list))
    fields = (f,) if single else tuple(f)
    if isinstance(periodic, bool):
        periodic = (periodic,) * grid.ndim
    if dims is None:
        dims = tuple(range(grid.ndim))

    if all(lax.axis_size(grid.axes[g]) == 1 for g in range(grid.ndim)):
        # no direction actually communicates (single-block grid): stacking
        # would only buy batched collectives, and its full-array
        # stack/unstack copies dominate the step on one chip — update each
        # field's ghosts in place instead
        out = []
        for x in fields:
            for gdim, (adim, per) in enumerate(zip(dims, periodic)):
                x = _axis_exchange(x, adim, grid.axes[gdim], halo, per)
            out.append(x)
        return out[0] if single else tuple(out)

    # Batch all fields into one stacked exchange per direction: one
    # collective instead of len(fields) — fewer, larger ICI transfers.
    stacked = jnp.stack([x.astype(fields[0].dtype) for x in fields])
    for gdim, (adim, per) in enumerate(zip(dims, periodic)):
        stacked = _axis_exchange(
            stacked, adim + 1, grid.axes[gdim], halo, per
        )
    out = tuple(stacked[i].astype(fields[i].dtype) for i in range(len(fields)))
    return out[0] if single else out
