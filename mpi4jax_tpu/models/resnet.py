"""Data-parallel residual CNN (the grad-allreduce training config).

BASELINE.md lists "Data-parallel ResNet-50 grad allreduce" among the
reference's benchmark configs; the reference itself only provides the
collective (differentiable allreduce).  This module supplies the model
family: a parameterizable residual CNN (depth/width scale up to
ResNet-50-class) trained data-parallel with the framework's
allreduce-synced gradients (parallel/dp.py).

TPU notes: convolutions run through ``lax.conv_general_dilated`` in NHWC
(MXU-friendly); normalization is GroupNorm (stateless — no cross-device
batch statistics, so DP sync is gradients-only).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel import dp


class ResNetConfig(NamedTuple):
    stages: Sequence[int] = (2, 2, 2, 2)   # blocks per stage (ResNet-18)
    widths: Sequence[int] = (64, 128, 256, 512)
    n_classes: int = 10
    in_channels: int = 3
    groups: int = 8
    block: str = "basic"  # "basic": two 3x3 convs (ResNet-18/34);
    #                       "bottleneck": 1x1 -> 3x3 -> 1x1 with 4x
    #                       expansion (ResNet-50-class: stages
    #                       (3, 4, 6, 3) + bottleneck = ResNet-50)
    dtype: str = "float32"  # conv compute dtype; "bfloat16" on real TPU
    # mixed precision: master params stay f32 (the optimizer update and
    # the DP grad-allreduce run in f32); forward casts per use, autodiff
    # transposes the casts so grads come back f32.  Measured r3 on the
    # v5e at ResNet-34/B=32/224^2: 6.5x over f32 convs (f32 hits the
    # MXU at 1/8 rate).
    stem: str = "small"  # "small": 3x3/1 conv, no pool (CIFAR-style,
    #                      the historical default — keeps existing
    #                      configs/params valid); "imagenet": 7x7/2
    #                      conv + 3x3/2 avg pool, the standard ResNet
    #                      head — stage 1 sees 1/16 the pixels (use
    #                      for 224^2-class inputs)


def _expansion(cfg: ResNetConfig) -> int:
    return 4 if cfg.block == "bottleneck" else 1


def resnet50_config(**overrides) -> ResNetConfig:
    """The BASELINE.md-named config: ResNet-50 = bottleneck (3, 4, 6, 3)."""
    base = dict(stages=(3, 4, 6, 3), block="bottleneck", n_classes=1000,
                stem="imagenet")
    base.update(overrides)
    return ResNetConfig(**base)


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _groupnorm(x, scale, bias, groups):
    # normalization statistics in f32 regardless of the compute dtype
    # (bf16 mean/var over 224^2 spatial positions loses too many bits)
    dt = x.dtype
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.astype(jnp.float32).reshape(n, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * lax.rsqrt(var + 1e-5)
    return (xg.reshape(n, h, w, c) * scale + bias).astype(dt)


def init_params(cfg: ResNetConfig, seed: int = 0):
    rng = np.random.RandomState(seed)

    def conv_w(k, cin, cout):
        fan = k * k * cin
        return jnp.asarray(
            (rng.randn(k, k, cin, cout) * np.sqrt(2.0 / fan)).astype(
                np.float32
            )
        )

    exp = _expansion(cfg)
    stem_k = 7 if cfg.stem == "imagenet" else 3
    params = {
        "stem": conv_w(stem_k, cfg.in_channels, cfg.widths[0]),
        "stem_gn": (jnp.ones(cfg.widths[0]), jnp.zeros(cfg.widths[0])),
        "stages": [],
        "head": jnp.asarray(
            (rng.randn(cfg.widths[-1] * exp, cfg.n_classes) * 0.01).astype(
                np.float32
            )
        ),
        "head_b": jnp.zeros(cfg.n_classes),
    }
    cin = cfg.widths[0]
    for si, (depth, width) in enumerate(zip(cfg.stages, cfg.widths)):
        blocks = []
        for b in range(depth):
            stride, has_proj = _block_plan(cfg, si, b, cin)
            del stride  # static; recomputed in forward
            cout = width * exp
            if cfg.block == "bottleneck":
                blk = {
                    "conv1": conv_w(1, cin, width),
                    "gn1": (jnp.ones(width), jnp.zeros(width)),
                    "conv2": conv_w(3, width, width),
                    "gn2": (jnp.ones(width), jnp.zeros(width)),
                    "conv3": conv_w(1, width, cout),
                    "gn3": (jnp.ones(cout), jnp.zeros(cout)),
                    "proj": conv_w(1, cin, cout) if has_proj else None,
                }
            else:
                blk = {
                    "conv1": conv_w(3, cin, width),
                    "gn1": (jnp.ones(width), jnp.zeros(width)),
                    "conv2": conv_w(3, width, width),
                    "gn2": (jnp.ones(width), jnp.zeros(width)),
                    "proj": conv_w(1, cin, cout) if has_proj else None,
                }
            blocks.append(blk)
            cin = cout
        params["stages"].append(blocks)
    return params


def _block_plan(cfg: ResNetConfig, stage: int, block: int, cin: int):
    """Static (stride, needs_projection) for a block — shared by init and
    forward so the pytree holds arrays only."""
    width = cfg.widths[stage] * _expansion(cfg)
    stride = 2 if (block == 0 and stage > 0) else 1
    return stride, (cin != width or stride > 1)


def forward(params, x, cfg: ResNetConfig):
    g = cfg.groups
    x = x.astype(jnp.dtype(cfg.dtype))
    stem_stride = 2 if cfg.stem == "imagenet" else 1
    h = jnp.maximum(
        _groupnorm(
            _conv(x, params["stem"], stem_stride), *params["stem_gn"], g
        ),
        0,
    )
    if cfg.stem == "imagenet":
        # 3x3/2 average pool as a depthwise conv (constant 1/9 kernel):
        # fully differentiable and MXU-scheduled, where max pool's
        # gradient is a SelectAndScatter.
        c = h.shape[-1]
        kern = jnp.full((3, 3, 1, c), 1.0 / 9.0, h.dtype)
        h = lax.conv_general_dilated(
            h, kern, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c,
        )
    cin = cfg.widths[0]
    exp = _expansion(cfg)
    for si, blocks in enumerate(params["stages"]):
        for b, blk in enumerate(blocks):
            stride, _ = _block_plan(cfg, si, b, cin)
            if cfg.block == "bottleneck":
                # 1x1 reduce -> 3x3 (strided) -> 1x1 expand
                y = jnp.maximum(
                    _groupnorm(_conv(h, blk["conv1"]), *blk["gn1"], g), 0
                )
                y = jnp.maximum(
                    _groupnorm(
                        _conv(y, blk["conv2"], stride), *blk["gn2"], g
                    ),
                    0,
                )
                y = _groupnorm(_conv(y, blk["conv3"]), *blk["gn3"], g)
            else:
                y = _conv(h, blk["conv1"], stride)
                y = jnp.maximum(_groupnorm(y, *blk["gn1"], g), 0)
                y = _groupnorm(_conv(y, blk["conv2"]), *blk["gn2"], g)
            skip = h
            if blk["proj"] is not None:
                skip = _conv(h, blk["proj"], stride)
            h = jnp.maximum(y + skip, 0)
            cin = cfg.widths[si] * exp
    pooled = jnp.mean(h.astype(jnp.float32), axis=(1, 2))
    return pooled @ params["head"] + params["head_b"]


def make_dp_train_step(cfg: ResNetConfig, mesh, lr=1e-2, axis="mpi"):
    """Jitted DP training step: batch sharded over ``axis``, grads synced
    with allreduce-mean."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import MeshComm

    comm = MeshComm(axis, mesh=mesh)

    def local_loss(params, xb, yb):
        logits = forward(params, xb, cfg)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, yb[:, None], axis=-1)
        )

    def per_rank(params, xb, yb):
        loss, grads = dp.value_and_synced_grad(local_loss, comm=comm)(
            params, xb, yb
        )
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return loss[None], params

    # `stride`/None leaves are static pytree data; strip them from specs
    def spec_tree(tree):
        return jax.tree.map(lambda _: P(), tree)

    example = init_params(cfg)

    mapped = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(spec_tree(example), P(axis), P(axis)),
        out_specs=(P(axis), spec_tree(example)),
        check_vma=False,
    )

    @jax.jit
    def step(params, images, labels):
        loss, params = mapped(params, images, labels)
        return loss[0], params

    return step
