"""Mixture-of-Experts expert parallelism: the alltoall dispatch/combine.

Experts are rank-sharded (one expert per rank of ``comm``); every token
is routed top-1 and shipped to its expert's rank with one ``alltoall``,
the expert FFN runs locally, and a second ``alltoall`` brings the
outputs home — the GShard/Switch dispatch pattern, where the exchange
volume is the activation traffic that dominates MoE scaling.  Both
transposes are :func:`mpi4jax_tpu.ops.alltoall`, so they ride whatever
the engine picks — and accept the same per-call controls:
``compression="int8"`` for the quantized wire format (EQuARX's
observation that routed activations tolerate low-precision transport,
arXiv:2506.17615) and ``algo=`` to force a schedule
(``"qalltoall"``/``"halltoall"``/``"hqalltoall"``) on a world comm.

A second layer, :func:`expert_parallel_ffn`, is the dropless form of
DeepSeek-V3-style models: each rank holds a block of ``n_experts / size``
SwiGLU experts, every token goes to its top-k experts (router
:func:`route_topk_sigmoid`), and the rows travel in exactly the numbers
routed, over :func:`mpi4jax_tpu.ops.alltoallv`.  Received rows run
through the experts as grouped matmuls (JAX's megablox ``gmm`` Pallas
kernel, whose grid covers only the rows present), and return the same
way.  Nothing is dropped: the receive buffer takes the smallest of a
short static ladder of row capacities (:func:`receive_capacities`) that
holds what was routed, chosen per call from the all-gathered counts, and
the ladder ends at the worst case (every token's k rows to one rank).

Composes with the other axes exactly like :mod:`.tp`/:mod:`.ulysses`:
``comm`` names the expert axis (a ``MeshComm`` sub-axis or a world
comm), so dp/tp/pp can own the remaining axes.  Capacity-based binning
keeps every shape static for jit: each rank sends exactly ``capacity``
token slots to every expert, overflow tokens are dropped (their output
is the zero vector — the standard Switch capacity discipline).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from .mesh import get_default_comm


def _resolve(comm):
    return comm if comm is not None else get_default_comm()


def expert_capacity(tokens: int, n_experts: int,
                    capacity_factor: float = 1.25) -> int:
    """Token slots each rank reserves per expert (static for jit)."""
    return max(1, int(math.ceil(tokens / n_experts * capacity_factor)))


def router_top1(x, w_gate):
    """Top-1 routing: ``(expert_idx, gate_prob, full_probs)`` per token.

    The softmax runs in f32 regardless of the activation dtype — the
    gate probabilities weight the combine and must not collapse in
    bf16.
    """
    logits = jnp.asarray(x) @ w_gate
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    prob = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    return idx, prob, probs


def dispatch(x, expert_idx, capacity: int, *, comm=None,
             compression=None, algo=None):
    """Bin tokens per destination expert and exchange: returns
    ``(expert_inputs, route)`` where ``expert_inputs`` is the
    ``(size, capacity, d)`` buffer of tokens routed to THIS rank's
    expert (row ``j`` from rank ``j``) and ``route`` is the opaque
    state :func:`combine` needs to scatter outputs home.

    ``compression``/``algo`` pass straight to the underlying
    :func:`~mpi4jax_tpu.ops.alltoall` — the dispatch direction and the
    combine direction are independent calls, so a caller may quantize
    one and not the other.
    """
    comm = _resolve(comm)
    size = comm.size()
    t, d = x.shape
    oh = jax.nn.one_hot(expert_idx, size, dtype=jnp.int32)
    # position of each token inside its expert's queue (0-based)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
    keep = (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1)
    buf = jnp.zeros((size, capacity, d), jnp.asarray(x).dtype)
    # .add, not .set: dropped tokens contribute zeros to a clipped slot
    # that may also hold a kept token — overwriting would corrupt it
    buf = buf.at[expert_idx, pos_c].add(
        jnp.where(keep[:, None], x, jnp.zeros_like(x)))
    recv = ops.alltoall(buf, comm=comm, compression=compression,
                        algo=algo)
    return recv, (expert_idx, pos_c, keep)


def combine(expert_out, route, *, comm=None, compression=None,
            algo=None):
    """Return trip of :func:`dispatch`: ship each expert's outputs back
    to the ranks that sent the tokens and scatter them into token
    order.  Dropped tokens come back as zeros."""
    comm = _resolve(comm)
    expert_idx, pos_c, keep = route
    back = ops.alltoall(expert_out, comm=comm, compression=compression,
                        algo=algo)
    y = back[expert_idx, pos_c]
    return jnp.where(keep[:, None], y, jnp.zeros_like(y))


def moe_ffn(x, params, *, comm=None, capacity_factor: float = 1.25,
            compression=None, algo=None):
    """One expert-parallel MoE FFN block: route, dispatch, this rank's
    expert (a two-layer relu FFN), combine, gate-weight.

    ``x``: ``(tokens, d_model)`` — this rank's local tokens.
    ``params``: ``w_gate (d_model, size)`` (replicated) plus THIS
    rank's expert ``w_in (d_model, d_ff) / b_in / w_out (d_ff,
    d_model) / b_out`` (see :func:`init_moe_params`).
    """
    comm = _resolve(comm)
    size = comm.size()
    idx, prob, _ = router_top1(x, params["w_gate"])
    cap = expert_capacity(x.shape[0], size, capacity_factor)
    recv, route = dispatch(x, idx, cap, comm=comm,
                           compression=compression, algo=algo)
    flat = recv.reshape(size * cap, -1)
    h = jnp.maximum(flat @ params["w_in"] + params["b_in"], 0)
    out = (h @ params["w_out"] + params["b_out"]).astype(x.dtype)
    y = combine(out.reshape(size, cap, -1), route, comm=comm,
                compression=compression, algo=algo)
    return y * prob[:, None].astype(y.dtype)


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    expert_rank: int, dtype=jnp.float32):
    """Replicated gate + rank ``expert_rank``'s expert weights.

    Every rank derives the expert bank from the same ``key`` and slices
    its own expert, so the sharding is reproducible without a broadcast.
    """
    kg, ki, ko = jax.random.split(key, 3)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    w_in = jax.random.normal(ki, (n_experts, d_model, d_ff), dtype) * scale_in
    w_out = jax.random.normal(ko, (n_experts, d_ff, d_model), dtype) * scale_out
    return {
        "w_gate": jax.random.normal(kg, (d_model, n_experts), dtype)
        * scale_in,
        "w_in": w_in[expert_rank],
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": w_out[expert_rank],
        "b_out": jnp.zeros((d_model,), dtype),
    }


# -- dropless top-k expert parallelism ---------------------------------------

def route_topk_sigmoid(x, w_gate, bias, top_k: int, scale: float = 1.0):
    """DeepSeek-V3 ``noaux_tc`` routing with one group: sigmoid scores in
    float32, the top-k of ``scores + bias`` chosen (the bias steers
    selection only), the chosen scores normalised to sum 1 and scaled.

    Returns ``(idx, weight)``, each ``(tokens, top_k)``: expert ids
    (int32) and float32 weights.
    """
    logits = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def _tile(dim: int, preferred: int) -> int:
    """Largest multiple of 128 dividing ``dim`` up to ``preferred``, else
    the whole dimension (a block equal to the array's dimension is
    always legal)."""
    for t in range(min(preferred, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


# grouped_matmul's row tile; receive capacities below the worst case are
# multiples of it, so that the expert kernels tile the received rows as
# they would in the worst-case buffer
_ROW_TILE = 512


def grouped_matmul(lhs, rhs, group_sizes, *, n_blocks: int = 1):
    """Rows of ``lhs`` times their group's matrix: ``group_sizes`` (int32,
    ``n_blocks * rhs.shape[0]``) gives consecutive row groups, group ``g``
    using ``rhs[g % rhs.shape[0]]``.  The ``n_blocks`` blocks of groups
    (one per sending rank after an exchange) run as chained megablox
    ``gmm`` calls with ``group_offset``, each writing its groups' rows
    into one zero-filled output.  Output in ``lhs``'s dtype, accumulated
    in float32."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ..ops.flash import _interpret_default

    interpret = _interpret_default()
    m, k = lhs.shape
    n = rhs.shape[-1]
    # tiles within the kernel's scoped VMEM on a v5e: 512 rows, and an
    # rhs block of at most 2**20 elements (double-buffered with the lhs,
    # output and accumulator blocks)
    tm = (m if m < _ROW_TILE
          else next(t for t in range(_ROW_TILE, 0, -8) if m % t == 0))
    tk = _tile(k, 512) if k > 1536 else k
    tiling = (tm, tk, _tile(n, max(128, 2 ** 20 // tk)))
    per = rhs.shape[0]
    # a zero-filled output to write into: without one, the first call
    # zeroes every row outside its groups in a pass of its own
    out = jnp.zeros((m, n), lhs.dtype)
    for b in range(n_blocks):
        out = gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                  tiling=tiling, group_offset=jnp.int32(b * per),
                  existing_out=out, interpret=interpret)
    return out


# receive capacities below the worst case, in units of the rows each rank
# routes (t * top_k), which is what a rank receives on average
_RUNGS = (1.25, 1.5, 2.0)


def receive_capacities(t: int, top_k: int, size: int):
    """The static row capacities of :func:`expert_parallel_ffn`'s receive
    buffer for ``t`` tokens a rank, routed top-``top_k`` over ``size``
    ranks, smallest first: ``_RUNGS`` of ``t * top_k`` rounded up to
    multiples of 512, those below the worst case, then the worst case
    ``size * t * top_k`` (every row of every rank to one rank)."""
    rows = t * top_k
    worst = size * rows
    below = {-(-math.ceil(share * rows) // _ROW_TILE) * _ROW_TILE
             for share in _RUNGS}
    return tuple(sorted(c for c in below if c < worst)) + (worst,)


def receive_rung(every, capacities):
    """Index of the smallest of ``capacities`` that holds the most rows
    any rank receives, from ``every`` ``(size, size, local)``: rows each
    source rank routed to each destination rank's local experts.  Over
    ``jnp``, so on the device and on the host alike."""
    most = jnp.max(jnp.sum(every, axis=(0, 2)))
    return jnp.searchsorted(jnp.asarray(capacities), most).astype(jnp.int32)


def receive_rungs(counts, top_k: int):
    """The rung of :func:`receive_capacities` each layer took, from
    ``counts`` ``(size, layers, n_experts)``: the rows each rank routed to
    each expert in each layer (the per-layer ``counts`` of
    :func:`expert_parallel_ffn`, stacked by rank).  Returns ``(layers,)``
    int32; the last rung is the worst case."""
    counts = np.asarray(counts)
    size, layers, _ = counts.shape
    t = int(counts[0, 0].sum()) // top_k
    caps = receive_capacities(t, top_k, size)
    return np.array([int(receive_rung(counts[:, layer].reshape(size, size,
                                                               -1), caps))
                     for layer in range(layers)], np.int32)


def expert_parallel_ffn(x, idx, weight, w_gate_up, w_down, *,
                        n_experts: int, comm=None):
    """Dropless expert-parallel SwiGLU experts over ``comm``.

    ``x`` ``(tokens, d)``: this rank's tokens; ``idx``/``weight``
    ``(tokens, k)``: their experts (global ids in ``[0, n_experts)``) and
    combine weights, from :func:`route_topk_sigmoid`.  This rank holds
    experts ``[rank * e, (rank + 1) * e)``, ``e = n_experts / size``:
    ``w_gate_up`` ``(e, d, 2 f)`` (gate then up) and ``w_down`` ``(e, f,
    d)``.  Each expert computes ``down(silu(gate x) * up x)``.

    Returns ``(y, counts)``: ``y`` ``(tokens, d)`` in ``x``'s dtype, the
    weighted sum of each token's k expert outputs, and ``counts``
    ``(n_experts,)`` int32, the rows this rank routed to each expert.
    """
    comm = _resolve(comm)
    size = comm.size()
    t, d = x.shape
    top_k = idx.shape[-1]
    local = w_gate_up.shape[0]
    if local * size != n_experts:
        raise ValueError(f"{local} experts per rank on {size} ranks do not "
                         f"make {n_experts}")
    f = w_down.shape[1]
    with jax.named_scope("moe_route"):
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
        rows = x[order // top_k]
    capacities = receive_capacities(t, top_k, size)
    with jax.named_scope("moe_dispatch"):
        # counts[src, dest rank, local expert] of every rank
        every = ops.allgather(counts, comm=comm).reshape(size, size, local)
        mine = jnp.take(every, comm.rank(), axis=1)  # (src, local expert)
        send = jnp.sum(jnp.take(every, comm.rank(), axis=0), axis=-1)
        recv = jnp.sum(mine, axis=-1)
        # every rank holds every rank's counts, so all take the same rung
        rung = receive_rung(every, capacities)
    groups = mine.reshape(-1)

    def experts(capacity, rows):
        """Dispatch into ``capacity`` rows, the experts, the combine."""
        with jax.named_scope("moe_dispatch"):
            # rows from each rank in rank order, each block sorted by expert
            got = ops.alltoallv(rows, send, recv, out_rows=capacity,
                                comm=comm)
        with jax.named_scope("moe_gmm"):
            h = grouped_matmul(got, w_gate_up, groups, n_blocks=size)
            # each half converted on its own, so that XLA fuses the
            # converts into the SwiGLU pass instead of writing h out in
            # float32
            act = (jax.nn.silu(h[:, :f].astype(jnp.float32))
                   * h[:, f:].astype(jnp.float32)).astype(x.dtype)
            out = grouped_matmul(act, w_down, groups, n_blocks=size)
        with jax.named_scope("moe_combine"):
            return ops.alltoallv(out, recv, send, out_rows=t * top_k,
                                 comm=comm)

    back = jax.lax.switch(
        rung, [functools.partial(experts, c) for c in capacities], rows)
    with jax.named_scope("moe_route"):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * top_k, dtype=jnp.int32))
        back = back[inverse].reshape(t, top_k, d).astype(jnp.float32)
        y = jnp.einsum("tkd,tk->td", back, weight).astype(x.dtype)
    return y, counts
