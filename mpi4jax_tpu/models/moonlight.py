"""Moonlight-16B-A3B (the DeepSeek-V3 architecture) prefill, expert-parallel.

The decoder of ``moonshotai/Moonlight-16B-A3B`` (``model_type``
``deepseek_v3``): multi-head latent attention (MLA) without q
compression, leading dense SwiGLU layers, then layers of routed SwiGLU
experts with shared experts, top-k sigmoid routing with a selection bias
(``noaux_tc``, one group), RMSNorm and RoPE on the rope part of q and k.

The layout is DeepSeek-V3's prefill (arXiv:2412.19437 §3.4.1) on one
mesh axis: attention, the dense layers, the shared experts, embedding and
head are data-parallel (each rank works on its own sequences), and the
routed experts are expert-parallel (rank ``r`` holds experts ``[r * e,
(r + 1) * e)``).  Each MoE layer is :func:`mpi4jax_tpu.parallel.moe.
expert_parallel_ffn`: dropless dispatch and combine over
:func:`mpi4jax_tpu.ops.alltoallv`, grouped expert matmuls.  Attention is
the flash kernel of :mod:`mpi4jax_tpu.ops.flash` with q.k over 192 and
values of 128.  Everything runs in one ``shard_map``; the layers of each
kind are stacked and iterated with ``lax.scan``.

Per layer ``x += attn(rms(x))`` then ``x += ffn(rms(x))``; a prefill
returns the final normed hidden states of every token and the logits of
each sequence's last token.

Weights are random, generated on the device from a seed by a fixed
recipe (:func:`weight`): one ``jax.random`` key per tensor, layer and
expert, N(0, 0.02) matrices rounded to the compute dtype, unit norms and a
U(-0.01, 0.01) selection bias.  A plain reference can regenerate any one
tensor alone.
"""

from __future__ import annotations

import math
import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.flash import flash_attention
from ..parallel.mesh import MeshComm
from ..parallel.moe import expert_parallel_ffn, route_topk_sigmoid

# the program's named scopes, innermost first in each instruction's
# op_name; the routed experts' grouped matmuls are Mosaic kernels under
# ``moe_gmm``, the flash kernel under ``mla_attention``
SCOPES = ("mla_attention", "moe_route", "moe_dispatch", "moe_gmm",
          "moe_combine", "moe_shared", "dense_ffn")

INIT_STD = 0.02
BIAS_RANGE = 0.01


class MoonlightConfig(NamedTuple):
    """The model's shape, under the keys of its Hugging Face config."""

    vocab_size: int = 163840
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    dtype: str = "bfloat16"

    @classmethod
    def from_hf(cls, config: dict, **overrides):
        """From a Hugging Face ``config.json`` dict (other keys ignored)."""
        keys = {k: config[k] for k in cls._fields if k in config}
        return cls(**{**keys, **overrides})

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def _shapes(cfg: MoonlightConfig):
    """``{stack: {name: per-layer shape}}`` of every weight."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    r = cfg.kv_lora_rank
    attn = {"attn_norm": (d,), "wq": (d, h * (nope + rope)),
            "wkva": (d, r + rope), "kv_norm": (r,),
            "wkvb": (r, h * (nope + vd)), "wo": (h * vd, d),
            "ffn_norm": (d,)}
    shared = cfg.moe_intermediate_size * cfg.n_shared_experts
    f = cfg.moe_intermediate_size
    return {
        "top": {"embed": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
                "final_norm": (d,)},
        "dense": {**attn, "w_gate_up": (d, 2 * cfg.intermediate_size),
                  "w_down": (cfg.intermediate_size, d)},
        "moe": {**attn, "router": (d, cfg.n_routed_experts),
                "router_bias": (cfg.n_routed_experts,),
                "shared_gate_up": (d, 2 * shared),
                "shared_down": (shared, d)},
        "experts": {"experts_gate_up": (d, 2 * f), "experts_down": (f, d)},
    }


def weight(seed: int, name: str, shape, dtype, *index):
    """The recipe of every weight: tensor ``name`` of layer (and expert)
    ``index``.  Norms are ones; ``router_bias`` is U(-0.01, 0.01) in
    float32; every matrix is N(0, 0.02) drawn in float32 from the key
    ``fold_in(PRNGKey(seed), crc32(name))`` folded with each index, then
    rounded to ``dtype``."""
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in index:
        key = jax.random.fold_in(key, i)
    if name == "router_bias":
        return jax.random.uniform(key, shape, jnp.float32, -BIAS_RANGE,
                                  BIAS_RANGE)
    return (jax.random.normal(key, shape, jnp.float32)
            * INIT_STD).astype(dtype)


def _normed(x, eps):
    """RMS-normalised ``x`` in float32."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _rms(x, w, eps):
    return _normed(x, eps).astype(x.dtype) * w


def _rope_tables(t: int, dim: int, theta: float):
    """``cos, sin`` ``(t, dim // 2)`` in float32 for positions ``0..t-1``."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """RoPE on ``(b, t, h, dim)`` in the interleaved-pair layout of the
    DeepSeek-V3 code: pair ``(x[2i], x[2i+1])`` turns by frequency ``i``,
    and the result lists the pairs' first elements, then their seconds."""
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    c, s = cos[None, :, None], sin[None, :, None]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)


def _swiglu(h, w_gate_up, w_down):
    gu = jnp.dot(h, w_gate_up)
    f = w_down.shape[0]
    act = (jax.nn.silu(gu[..., :f].astype(jnp.float32))
           * gu[..., f:].astype(jnp.float32)).astype(h.dtype)
    return jnp.dot(act, w_down)


class Moonlight:
    """The model bound to a mesh whose axis ``"ep"`` holds the experts and
    splits the batch of sequences."""

    axis = "ep"

    def __init__(self, cfg: MoonlightConfig, mesh: Mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.size = mesh.shape[self.axis]
        if cfg.n_routed_experts % self.size:
            raise ValueError(f"{cfg.n_routed_experts} experts do not split "
                             f"over {self.size} ranks")
        self.local_experts = cfg.n_routed_experts // self.size
        self.dtype = jnp.dtype(cfg.dtype)

    # -- weights --------------------------------------------------------------
    def param_specs(self):
        shapes = _shapes(self.cfg)
        specs = {k: P() for k in shapes["top"]}
        for stack in ("dense", "moe"):
            specs[stack] = {k: P() for k in shapes[stack]}
        specs["moe"].update({k: P(None, self.axis)
                             for k in shapes["experts"]})
        return specs

    def init_params(self, seed: int):
        """Every weight, generated on the devices by :func:`weight`: one
        jitted call for the embedding and head, one per stack of layers;
        each rank generates only its own experts."""
        cfg, dt = self.cfg, self.dtype
        shapes = _shapes(cfg)
        rep = NamedSharding(self.mesh, P())

        def stack(names, layers):
            def one(layer):
                return {k: weight(seed, k, s, dt, layer)
                        for k, s in names.items()}
            return jax.jit(lambda: lax.map(one, layers),
                           out_shardings=rep)()

        params = jax.jit(
            lambda: {k: weight(seed, k, s, dt)
                     for k, s in shapes["top"].items()},
            out_shardings=rep)()
        first = cfg.first_k_dense_replace
        params["dense"] = stack(shapes["dense"], jnp.arange(first))
        moe_layers = jnp.arange(first, cfg.num_hidden_layers)
        params["moe"] = stack(shapes["moe"], moe_layers)

        e = self.local_experts

        def local_experts():
            ids = lax.axis_index(self.axis) * e + jnp.arange(e)

            def one(layer):
                return {k: jax.vmap(lambda i: weight(seed, k, s, dt, layer,
                                                     i))(ids)
                        for k, s in shapes["experts"].items()}
            return lax.map(one, moe_layers)

        params["moe"].update(jax.jit(jax.shard_map(
            local_experts, mesh=self.mesh, in_specs=(),
            out_specs=P(None, self.axis), check_vma=False))())
        return params

    # -- per-rank forward (inside shard_map) ---------------------------------
    def _attention(self, h, p, cos, sin):
        cfg = self.cfg
        b, t, _ = h.shape
        nh, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim)
        r = cfg.kv_lora_rank
        with jax.named_scope("mla_attention"):
            q = jnp.dot(h, p["wq"]).reshape(b, t, nh, nope + rope)
            kva = jnp.dot(h, p["wkva"])
            c_kv, k_pe = kva[..., :r], kva[..., r:]
            kv = jnp.dot(_rms(c_kv, p["kv_norm"], cfg.rms_norm_eps),
                         p["wkvb"]).reshape(b, t, nh, nope + vd)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], cos, sin)], axis=-1)
            k_pe = _rope(k_pe[:, :, None, :], cos, sin)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, nh, rope))],
                axis=-1)
            o = flash_attention(q, k, kv[..., nope:], causal=True)
            return jnp.dot(o.reshape(b, t, nh * vd), p["wo"])

    def _moe(self, x, p, comm):
        """The MoE FFN of ``x`` ``(tokens, d)``; the router reads the
        float32 norm, the experts its rounding to the compute dtype."""
        cfg = self.cfg
        normed = _normed(x, cfg.rms_norm_eps)
        h = normed.astype(x.dtype) * p["ffn_norm"]
        with jax.named_scope("moe_route"):
            idx, w = route_topk_sigmoid(normed * p["ffn_norm"], p["router"],
                                        p["router_bias"],
                                        cfg.num_experts_per_tok,
                                        cfg.routed_scaling_factor)
        y, counts = expert_parallel_ffn(
            h, idx, w, p["experts_gate_up"], p["experts_down"],
            n_experts=cfg.n_routed_experts, comm=comm)
        with jax.named_scope("moe_shared"):
            y = y + _swiglu(h, p["shared_gate_up"], p["shared_down"])
        return y, counts

    def _forward_local(self, params, tokens):
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        comm = MeshComm(self.axis, mesh=self.mesh)
        b, t = tokens.shape
        d = cfg.hidden_size
        cos, sin = _rope_tables(t, cfg.qk_rope_head_dim, cfg.rope_theta)
        x = params["embed"][tokens]

        def dense_layer(x, p):
            x = x + self._attention(_rms(x, p["attn_norm"], eps), p, cos, sin)
            with jax.named_scope("dense_ffn"):
                h = _rms(x, p["ffn_norm"], eps)
                x = x + _swiglu(h, p["w_gate_up"], p["w_down"])
            return x, None

        def moe_layer(x, p):
            x = x + self._attention(_rms(x, p["attn_norm"], eps), p, cos, sin)
            y, counts = self._moe(x.reshape(b * t, d), p, comm)
            return x + y.reshape(b, t, d), counts

        x, _ = lax.scan(dense_layer, x, params["dense"])
        x, counts = lax.scan(moe_layer, x, params["moe"])
        hidden = _rms(x, params["final_norm"], eps)
        logits = jnp.dot(hidden[:, -1], params["head"],
                         preferred_element_type=jnp.float32)
        return hidden, logits, counts[None]

    def prefill_fn(self):
        """``prefill(params, tokens)`` for ``tokens`` ``(B, T)`` int32, the
        batch split over the axis: ``(hidden (B, T, d), logits (B, vocab)
        float32, counts (size, moe layers, experts))``, where ``counts[r,
        l, e]`` is the rows rank ``r`` routed to expert ``e`` in MoE layer
        ``l``."""
        fn = jax.shard_map(
            self._forward_local, mesh=self.mesh,
            in_specs=(self.param_specs(), P(self.axis)),
            out_specs=(P(self.axis), P(self.axis), P(self.axis)),
            check_vma=False)
        return jax.jit(fn)


def parameter_count(cfg: MoonlightConfig, experts_held: int) -> int:
    """Parameters one rank holds with ``experts_held`` of each layer's
    routed experts."""
    shapes = _shapes(cfg)
    n = lambda names: sum(math.prod(s) for s in names.values())
    return (n(shapes["top"]) + cfg.first_k_dense_replace * n(shapes["dense"])
            + cfg.n_moe_layers * (n(shapes["moe"])
                                  + experts_held * n(shapes["experts"])))
