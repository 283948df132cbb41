"""Pallas TPU kernel: fused hybrid landmark scoring pass (paper §3.3).

One sweep over the KV cache computing BOTH selection terms per key:
  * raw attention logits per query head (density term, pre-softmax — the
    softmax normalizer is a cheap [B,H,T] reduction done by the wrapper), and
  * min distance to the current landmark set (coverage term),
so keys are read from HBM exactly once instead of twice. This is the
bandwidth-bound half of the Topological Synapse; the tiny top-k/argmax that
follows is XLA-native.

Tiling: grid (B, T/blkT). Per program: keys block [blkT, Hkv*D] in VMEM (the
cache layout, reshaped for free), queries [Hkv, G, D], landmark centroids
[Kc, D]. Each kv head is a static, lane-aligned D-wide column slice of the
key block, so no in-kernel reshape or transpose is needed. Keys run along
lanes in every output ([.., blkT] rows), as the (8, 128) tiling wants.
blkT, D multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def mxu_dot(a, b, dims):
    """MXU contraction with an f32 result. f32 operands get the full-precision
    passes; bf16 x bf16 products are exact in f32 in a single pass (Mosaic
    refuses a precision request on them)."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32, precision=prec)


def _dot_t(a, b):
    """a [M, D] . b [N, D]^T -> [M, N] f32."""
    return mxu_dot(a, b, (((1,), (1,)), ((), ())))


def _kernel(q_ref, k_ref, *rest, scale: float, hkv: int, true_d: int, with_dist: bool):
    # q_ref: [Hkv, G, D]; k_ref: [blkT, Hkv*D]; lm_ref: [Kc, D]
    # logits_ref: [Hkv, G, blkT]; dist_ref: [1, blkT] (both absent unless with_dist)
    if with_dist:
        lm_ref, logits_ref, dist_ref = rest
    else:
        (logits_ref,) = rest
    d = q_ref.shape[-1]
    pooled = None
    for j in range(hkv):  # static: Hkv is a handful of heads
        kj = k_ref[:, j * d:(j + 1) * d]                       # [blkT, D]
        # density term: head h uses kv head h // G
        logits_ref[j] = (_dot_t(q_ref[j], kj) * scale).astype(logits_ref.dtype)
        if with_dist:
            kf = kj.astype(jnp.float32)
            pooled = kf if pooled is None else pooled + kf
    if not with_dist:
        return
    # coverage term: min_j || mean_kv(k_t) - lm_j || / sqrt(d), computed with
    # keys along lanes: d2[j, t] = |lm_j|^2 + |k_t|^2 - 2 lm_j . k_t
    pooled = pooled / hkv                                      # [blkT, D]
    lm = lm_ref[...].astype(jnp.float32)                       # [Kc, D]
    cross = _dot_t(lm, pooled)                                 # [Kc, blkT]
    l2 = jnp.sum(lm * lm, axis=-1, keepdims=True)              # [Kc, 1]
    k2 = _dot_t(jnp.ones((1, d), jnp.float32), pooled * pooled)  # [1, blkT]
    d2 = jnp.maximum(k2 + l2 - 2.0 * cross, 0.0)
    dist_ref[...] = jnp.sqrt(jnp.min(d2, axis=0, keepdims=True) / true_d).astype(dist_ref.dtype)


def landmark_score(q, keys, landmarks=None, *, scale: float | None = None, true_d: int | None = None, block_t: int = 512, interpret: bool = False):
    """q: [B, H, D]; keys: [B, T, Hkv, D]; landmarks: [B, Kc, D] (pooled),
    or None for the density-only sweep (the coverage block is skipped).

    Returns (logits [B, H, T] f32 — pre-softmax density logits,
             min_dist [B, T] f32 — normalized distance to landmark set, or
             None when landmarks is None).
    T must be a multiple of block_t; D multiple of 128 (ops.py pads).
    """
    B, H, D = q.shape
    T, Hkv = keys.shape[1], keys.shape[2]
    G = H // Hkv
    with_dist = landmarks is not None
    scale = (1.0 / (D ** 0.5)) if scale is None else scale
    true_d = D if true_d is None else true_d
    in_specs = [
        pl.BlockSpec((None, Hkv, G, D), lambda b, t: (b, 0, 0, 0)),
        pl.BlockSpec((None, block_t, Hkv * D), lambda b, t: (b, t, 0)),
    ]
    args = [q.reshape(B, Hkv, G, D), keys.reshape(B, T, Hkv * D)]
    out_specs = [pl.BlockSpec((None, Hkv, G, block_t), lambda b, t: (b, 0, 0, t))]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, G, T), jnp.float32)]
    if with_dist:
        Kc = landmarks.shape[1]
        in_specs.append(pl.BlockSpec((None, Kc, D), lambda b, t: (b, 0, 0)))
        args.append(landmarks)
        out_specs.append(pl.BlockSpec((None, 1, block_t), lambda b, t: (b, 0, t)))
        out_shape.append(jax.ShapeDtypeStruct((B, 1, T), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_kernel, scale=scale, hkv=Hkv, true_d=true_d, with_dist=with_dist),
        grid=(B, T // block_t),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="landmark_score",
    )(*args)
    logits = res[0].reshape(B, H, T)
    return (logits, res[1][:, 0]) if with_dist else (logits, None)
