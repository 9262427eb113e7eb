"""Plain Moonlight-16B-A3B prefill reference, written from the equations.

The DeepSeek-V3 decoder of ``moonshotai/Moonlight-16B-A3B`` in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no mesh, no exchange, no
kernel, nothing imported from the package under test.  Each layer is
``x += attn(rms(x))``, then ``x += ffn(rms(x))``:

- MLA: ``q = x W_q`` split into ``q_nope`` (128) and ``q_pe`` (64);
  ``[c_kv, k_pe] = x W_kva``; ``[k_nope, v] = rms(c_kv) W_kvb``; RoPE on
  ``q_pe`` and the one ``k_pe`` all heads share (interleaved pairs);
  causal ``softmax(q k^T / sqrt(192)) v``, then ``W_o``.
- Router: ``s = sigmoid(x W_g)``; the experts are the top-k of ``s +
  bias``; their weights ``s``, normalised to sum 1, times the scaling
  factor.
- Experts: ``down(silu(gate x) * up x)``; the shared experts are one such
  block of twice the width, applied to every token.
- Output: the final RMSNorm of every token, and the head on each
  sequence's last token.

Weights are regenerated a layer at a time from ``weight_seed`` by the
recipe the system states (``weight``, written again here): a key per
tensor, layer and expert, N(0, 0.02) matrices rounded to the
configuration's ``dtype`` (bfloat16), unit norms, a U(-0.01, 0.01)
selection bias.  Each expert runs on the rows routed to it, one expert at
a time (padded to a power of two with rows whose output is discarded).

``dtype`` is the precision every matmul input is rounded to before the
float32 product: float32 is the reference; ``float8_e4m3fn`` is the
control that the comparison must reject.  ``layers=0`` passes the
embeddings straight to the final norm and head, the other control.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
BIAS_RANGE = 0.01


def weight(seed, name, shape, dtype, *index):
    """Tensor ``name`` of layer (and expert) ``index``, as float32."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in index:
        key = jax.random.fold_in(key, i)
    if name == "router_bias":
        return jax.random.uniform(key, shape, jnp.float32, -BIAS_RANGE,
                                  BIAS_RANGE)
    w = jax.random.normal(key, shape, jnp.float32) * INIT_STD
    return w.astype(dtype).astype(jnp.float32)


_weight = jax.jit(weight, static_argnums=(1, 2, 3))


class Shape:
    def __init__(self, c):
        self.d = c["hidden_size"]
        self.heads = c["num_attention_heads"]
        self.nope = c["qk_nope_head_dim"]
        self.rope = c["qk_rope_head_dim"]
        self.vd = c["v_head_dim"]
        self.rank = c["kv_lora_rank"]
        self.dense = c["intermediate_size"]
        self.f = c["moe_intermediate_size"]
        self.shared = c["moe_intermediate_size"] * c["n_shared_experts"]
        self.experts = c["n_routed_experts"]
        self.top_k = c["num_experts_per_tok"]
        self.scale = c["routed_scaling_factor"]
        self.eps = c["rms_norm_eps"]
        self.theta = c["rope_theta"]
        self.vocab = c["vocab_size"]
        self.layers = c["num_hidden_layers"]
        self.first_dense = c["first_k_dense_replace"]
        # the precision the weights are stored in
        self.wdtype = jnp.dtype(c.get("dtype", "bfloat16"))
        self.attn = (
            ("wq", (self.d, self.heads * (self.nope + self.rope))),
            ("wkva", (self.d, self.rank + self.rope)),
            ("wkvb", (self.rank, self.heads * (self.nope + self.vd))),
            ("wo", (self.heads * self.vd, self.d)))

    # a static argument of the jitted blocks below
    def __hash__(self):
        return hash(tuple(sorted(vars(self).items())))

    def __eq__(self, other):
        return vars(self) == vars(other)


def _round(a, dtype):
    return a if dtype == jnp.float32 else a.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype):
    return jnp.matmul(_round(a, dtype), _round(b, dtype))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """``x`` ``(b, t, h, dim)``: pair ``(x[2i], x[2i+1])`` at position
    ``p`` turned by ``p / theta**(2i/dim)``; the pairs' first elements,
    then their seconds."""
    t, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _attention(x, w, sh, dtype):
    b, t, _ = x.shape
    q = _mm(x, w["wq"], dtype).reshape(b, t, sh.heads, sh.nope + sh.rope)
    kva = _mm(x, w["wkva"], dtype)
    kv = _mm(_rms(kva[..., :sh.rank], sh.eps), w["wkvb"], dtype).reshape(
        b, t, sh.heads, sh.nope + sh.vd)
    q = jnp.concatenate([q[..., :sh.nope], _rope(q[..., sh.nope:], sh.theta)],
                        axis=-1)
    k_pe = _rope(kva[..., None, sh.rank:], sh.theta)
    k = jnp.concatenate(
        [kv[..., :sh.nope],
         jnp.broadcast_to(k_pe, (b, t, sh.heads, sh.rope))], axis=-1)
    v = kv[..., sh.nope:]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def head(args):  # one head at a time keeps the scores at (b, t, t)
        qh, kh, vh = args
        s = jnp.einsum("bqd,bkd->bqk", _round(qh, dtype), _round(kh, dtype))
        s = jnp.where(mask, s / np.sqrt(sh.nope + sh.rope), -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", _round(jax.nn.softmax(s, -1), dtype),
                          _round(vh, dtype))

    o = jax.lax.map(head, (q.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
                           v.transpose(2, 0, 1, 3)))
    return _mm(o.transpose(1, 2, 0, 3).reshape(b, t, -1), w["wo"], dtype)


def _swiglu(h, gate_up, down, dtype):
    gu = _mm(h, gate_up, dtype)
    f = down.shape[0]
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], down, dtype)


def _w(seed, sh, name, shape, *index):
    return weight(seed, name, shape, sh.wdtype, *index)


def _embed(seed, tokens, sh):
    return _w(seed, sh, "embed", (sh.vocab, sh.d))[tokens]


def _attn_block(x, seed, layer, sh, dtype):
    w = {k: _w(seed, sh, k, s, layer) for k, s in sh.attn}
    return x + _attention(_rms(x, sh.eps), w, sh, dtype)


def _dense_block(x, seed, layer, sh, dtype):
    return x + _swiglu(_rms(x, sh.eps),
                       _w(seed, sh, "w_gate_up", (sh.d, 2 * sh.dense), layer),
                       _w(seed, sh, "w_down", (sh.dense, sh.d), layer), dtype)


def _moe_front(x, seed, layer, bias, sh, dtype):
    """The MoE layer's input ``h`` (tokens, d), each token's experts (the
    top-k of sigmoid score + ``bias``) and weights (the normalised,
    scaled scores), the shared experts' output, and a zero buffer for the
    routed experts' outputs."""
    b, t, d = x.shape
    h = _rms(x, sh.eps).reshape(b * t, d)
    s = jax.nn.sigmoid(_mm(h, _w(seed, sh, "router", (d, sh.experts), layer),
                           dtype))
    _, idx = jax.lax.top_k(s + bias, sh.top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * sh.scale
    shared = _swiglu(
        h, _w(seed, sh, "shared_gate_up", (d, 2 * sh.shared), layer),
        _w(seed, sh, "shared_down", (sh.shared, d), layer), dtype)
    out = jnp.zeros((b * t * sh.top_k + _bucket(b * t), d), jnp.float32)
    return h, idx, g, shared, out


def _expert(out, h, tok, start, seed, layer, e, sh, dtype):
    """Expert ``e``'s output for the rows ``tok`` of ``h``, written into
    ``out`` from row ``start``."""
    gate_up = _w(seed, sh, "experts_gate_up", (sh.d, 2 * sh.f), layer, e)
    down = _w(seed, sh, "experts_down", (sh.f, sh.d), layer, e)
    return jax.lax.dynamic_update_slice(
        out, _swiglu(h[tok], gate_up, down, dtype), (start, 0))


def _moe_back(x, out, inverse, g, shared):
    """``x`` plus each token's routed outputs (``out`` in expert order,
    ``inverse`` the pairs' places in it) weighted by ``g``, plus the
    shared experts'."""
    b, t, d = x.shape
    routed = out[inverse].reshape(b * t, -1, d) * g[..., None]
    return x + (shared + jnp.sum(routed, axis=1)).reshape(b, t, d)


def _head(x, seed, sh):
    hidden = _rms(x, sh.eps)
    logits = _mm(hidden[:, -1], _w(seed, sh, "head", (sh.d, sh.vocab)),
                 jnp.float32)
    return hidden, logits


# each block generates its own weights inside, so that a forward pass
# compiles about fifteen programs
_static = dict(static_argnames=("sh", "dtype"))
_embed = jax.jit(_embed, static_argnames=("sh",))
_attn_block = jax.jit(_attn_block, **_static)
_dense_block = jax.jit(_dense_block, **_static)
_moe_front = jax.jit(_moe_front, **_static)
_expert = jax.jit(_expert, donate_argnums=0, **_static)
_moe_back = jax.jit(_moe_back)
_head = jax.jit(_head, static_argnames=("sh",))


def _bucket(n):
    """Rows an expert's ``n`` rows are padded to, so that few shapes are
    compiled: a power of two, at least 256."""
    return max(256, 1 << (int(n) - 1).bit_length())


def forward(config, tokens, dtype=jnp.float32, layers=None):
    """Final normed hidden states ``(b, t, d)`` and last-token logits
    ``(b, vocab)`` of ``tokens`` ``(b, t)``, both float32 numpy.
    ``layers`` (default: all) limits the decoder layers run."""
    sh = Shape(config)
    seed = config["weight_seed"]
    dt = jnp.dtype(dtype)
    n_layers = sh.layers if layers is None else layers
    with jax.default_matmul_precision("highest"):
        x = _embed(seed, jnp.asarray(tokens), sh)
        for layer in range(n_layers):
            x = _attn_block(x, seed, layer, sh, dt)
            if layer < sh.first_dense:
                x = _dense_block(x, seed, layer, sh, dt)
                continue
            bias = _weight(seed, "router_bias", (sh.experts,), jnp.float32,
                           layer)
            h, idx, g, shared, out = _moe_front(x, seed, layer, bias, sh, dt)
            # the (token, slot) pairs sorted by expert; each expert's rows
            # are one block of ``out``, padded with copies of row 0 that
            # the next expert's block (or the tail) overwrites
            flat = np.asarray(idx).reshape(-1)
            order = np.argsort(flat, kind="stable")
            ends = np.cumsum(np.bincount(flat, minlength=sh.experts))
            for e in range(sh.experts):
                start = ends[e - 1] if e else 0
                if ends[e] == start:
                    continue
                tok = np.zeros(_bucket(ends[e] - start), np.int32)
                tok[:ends[e] - start] = order[start:ends[e]] // sh.top_k
                out = _expert(out, h, jnp.asarray(tok), int(start), seed,
                              layer, e, sh, dt)
            inverse = np.empty_like(order)
            inverse[order] = np.arange(len(order))
            x = _moe_back(x, out, jnp.asarray(inverse, jnp.int32), g, shared)
        hidden, logits = _head(x, seed, sh)
    return (np.asarray(hidden, np.float32), np.asarray(logits, np.float32))


def readings(got, want):
    """The numbers the check compares, each the worst over the
    sequences: ``hidden_rel``, the relative L2 error of the final normed
    hidden states over every token of a sequence, and ``logits_rel``,
    that of its last-token logits.  Not finite reads NaN."""
    out = {}
    for name, key in (("hidden_rel", "hidden"), ("logits_rel", "logits")):
        g = np.asarray(got[key], np.float64)
        w = np.asarray(want[key], np.float64)
        axes = tuple(range(1, g.ndim))
        err = (np.sqrt(np.sum((g - w) ** 2, axis=axes))
               / np.sqrt(np.sum(w ** 2, axis=axes)))
        out[name] = float(np.max(err)) if np.all(np.isfinite(err)) \
            else float("nan")
    return out
