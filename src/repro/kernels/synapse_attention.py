"""Pallas TPU kernel: single-token decode attention over a synapse token set.

The per-tick hot loop of every Warp-Cortex agent: one query against the
concatenated [landmarks; window; inject] key set (T = K + W + J, a few
hundred to a few thousand — this is the whole point of the synapse). The
kernel fuses the masked attend AND the paper's density statistic (attention
mass per key, summed over heads) into one VMEM-resident pass, so the key set
is read from HBM exactly once per step.

Tiling: grid (B, Hkv); per program the full [T, D] K and V tiles for one kv
head live in VMEM (T<=8192, D<=256 -> <=8 MiB bf16), queries are the G = H/Hkv
group rows. Scores accumulate in fp32 on the MXU; D and T must be multiples
of 128 for lane alignment (callers pad — see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.landmark_score import mxu_dot

NEG_INF = -1e30


def _kernel_batched(q_ref, k_ref, v_ref, valid_ref, o_ref, mass_ref, *, scale: float):
    # Fat-block variant: the whole (B*Hkv) batch lives in ONE program.
    # q_ref:    [BB, G, D]; k_ref/v_ref: [BB, T, D]; valid_ref: [BB, T] int8
    # o_ref:    [BB, G, D]; mass_ref: [BB, T]
    # Used in interpret mode (CPU), where per-program interpreter overhead
    # dominates: grid (B, Hkv) costs ~B*Hkv program invocations, grid (1,)
    # costs one. On TPU the per-(b,h) grid below keeps [T, D] tiles aligned.
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    valid = valid_ref[...] != 0

    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale  # [BB, G, T]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / denom
    o = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [BB, G, D]
    o_ref[...] = o.astype(o_ref.dtype)
    mass_ref[...] = jnp.sum(p, axis=1).astype(mass_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, mass_ref, *, scale: float):
    # One program per (batch row, kv head):
    # q_ref:    [G, D]      queries of this kv head's group
    # k_ref:    [T, D]      keys (one kv head)
    # v_ref:    [T, D]      values
    # valid_ref:[1, T]      int32 mask, a lane-major row
    # o_ref:    [G, D]      attention output
    # mass_ref: [1, T]      per-key probability mass summed over the G heads
    valid = valid_ref[...] != 0
    s = mxu_dot(q_ref[...], k_ref[...], (((1,), (1,)), ((), ()))) * scale  # [G, T]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / denom  # [G, T]
    o = mxu_dot(p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())))  # [G, D]
    o_ref[...] = o.astype(o_ref.dtype)
    mass_ref[...] = jnp.sum(p, axis=0, keepdims=True).astype(mass_ref.dtype)


def synapse_attention(
    q, keys, values, valid, *, scale: float | None = None, interpret: bool = False,
    batched: bool | None = None,
):
    """q: [B, H, D]; keys/values: [B, T, Hkv, D]; valid: [B, T] bool.

    Returns (out [B, H, D], mass [B, T] f32). T and D must be multiples of
    128 (pad via ops.py wrapper). ``batched`` collapses the (B, Hkv) grid
    into one program — the default under interpret mode, where per-program
    overhead dominates the tiny decode shapes.
    """
    B, H, D = q.shape
    T, Hkv = keys.shape[1], keys.shape[2]
    G = H // Hkv
    scale = (1.0 / (D ** 0.5)) if scale is None else scale
    batched = interpret if batched is None else batched
    qg = q.reshape(B, Hkv, G, D)

    if batched:
        BB = B * Hkv
        qb = qg.swapaxes(1, 0).reshape(BB, G, D)      # [Hkv*B, G, D]
        kb = keys.transpose(2, 0, 1, 3).reshape(BB, T, D)
        vb = values.transpose(2, 0, 1, 3).reshape(BB, T, D)
        validb = jnp.tile(valid.astype(jnp.int8), (Hkv, 1))  # [Hkv*B, T]
        out, mass = pl.pallas_call(
            functools.partial(_kernel_batched, scale=scale),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((BB, G, D), lambda i: (0, 0, 0)),
                pl.BlockSpec((BB, T, D), lambda i: (0, 0, 0)),
                pl.BlockSpec((BB, T, D), lambda i: (0, 0, 0)),
                pl.BlockSpec((BB, T), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((BB, G, D), lambda i: (0, 0, 0)),
                pl.BlockSpec((BB, T), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BB, G, D), q.dtype),
                jax.ShapeDtypeStruct((BB, T), jnp.float32),
            ],
            interpret=interpret,
            name="synapse_attention",
        )(qb, kb, vb, validb)
        out = out.reshape(Hkv, B, G, D).swapaxes(1, 0).reshape(B, H, D)
        mass = mass.reshape(Hkv, B, T).sum(axis=0)
        return out, mass

    # grid (B, Hkv). Keys stay in their [B, T, Hkv*D] cache layout (a free
    # reshape): each program's [T, D] tile is the h-th D-wide column block,
    # so no head-major copy of the key set is made. The mask and the mass
    # travel as [B, 1, T] / [B, Hkv, 1, T] rows whose last two block dims
    # equal the array dims, as the TPU's (8, 128) tiling requires.
    valid_rows = valid.astype(jnp.int32)[:, None, :]
    out, mass = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((None, None, G, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, T, D), lambda b, h: (b, 0, h)),
            pl.BlockSpec((None, T, D), lambda b, h: (b, 0, h)),
            pl.BlockSpec((None, 1, T), lambda b, h: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, G, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, 1, T), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, 1, T), jnp.float32),
        ],
        interpret=interpret,
        name="synapse_attention",
    )(qg, keys.reshape(B, T, Hkv * D), values.reshape(B, T, Hkv * D), valid_rows)
    return out.reshape(B, H, D), mass.sum(axis=(1, 2))
