"""Operations and bytes of the Moonlight prefill, counted from its shape.

``shape`` is what ``perf/systems/moonlight.py`` reports among its
counters: the configuration's widths and depth and the cell's batch.
Each count is the least the algorithm needs: every operand read once and
every result written once, causal attention over the lower triangle with
its diagonal.  A kernel's roofline share is then ``max(flops / peak
FLOP/s, bytes / peak bytes/s)`` over its measured time, at most 1.
"""

from __future__ import annotations


def gmm(rows, shape):
    """``(flops, bytes)`` of one chip's grouped expert matmuls in a step,
    for the ``rows`` its experts received over all MoE layers: gate and
    up (``d -> 2 f``), then down (``f -> d``), bfloat16 operands."""
    d, f, b = shape["hidden"], shape["moe_width"], shape["itemsize"]
    flops = 2 * rows * (d * 2 * f + f * d)
    weights = shape["moe_layers"] * shape["experts_per_chip"] * 3 * d * f
    # gate-up reads d and writes 2f per row; down reads f and writes d
    acts = rows * (d + 2 * f + f + d)
    return flops, (weights + acts) * b


def flash(shape):
    """``(flops, bytes)`` of one chip's flash attention kernels in a step:
    ``q k^T`` over the q.k head and ``p v`` over the value head, causal;
    q, k, v read in bfloat16, the float32 partial output and its two
    row statistics written."""
    t, h = shape["seq_len"], shape["heads"]
    dk, dv = shape["qk_head"], shape["v_head"]
    n = shape["seqs_per_chip"] * shape["layers"] * h
    pairs = t * (t + 1) // 2
    flops = n * 2 * pairs * (dk + dv)
    bytes_ = n * t * ((2 * dk + dv) * shape["itemsize"] + (dv + 2) * 4)
    return flops, bytes_


def model_flops(shape):
    """The model's FLOPs in one prefill step over every chip: twice the
    parameters each token uses (attention, dense and shared FFNs, the
    router, its top-k experts; not the embedding lookup), the causal
    attention products, and the head on each sequence's last token."""
    tokens = shape["chips"] * shape["seqs_per_chip"] * shape["seq_len"]
    attn = flash(shape)[0] * shape["chips"]
    head = 2 * shape["hidden"] * shape["vocab"] * shape["chips"] \
        * shape["seqs_per_chip"]
    return 2 * shape["active_params"] * tokens + attn + head
