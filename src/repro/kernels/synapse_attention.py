"""Pallas TPU kernels: single-token decode attention over a synapse token set.

The per-tick hot loop of every Warp-Cortex agent: one query against the
[landmarks; window; inject] key set (T = K + W + J, a few hundred to a few
thousand: this is the whole point of the synapse). Each kernel fuses the
masked attend AND the paper's density statistic (attention mass per key,
summed over heads) into one VMEM-resident pass, so the key set is read from
HBM exactly once per step.

Two kernels, both named ``synapse_attention`` in the compiled program:

* :func:`synapse_attention_pieces`, the side pass's path. It reads the
  three pieces in place, each from the stack of every layer's
  ``[B, T_i, Hkv*D]`` cache rows at the layer the grid's index maps are
  given, and takes one softmax across them: no copy of the layer, no
  concatenation and no padding. A grid step holds a block of lanes and
  every kv head of each lane (the full ``Hkv*D`` lane row); per-head
  scores come from a block-diagonal query, whose zero products are exact.
  Needs ``Hkv*D`` a multiple of 128 and every ``T_i`` a multiple of 16
  (:func:`fits_in_place`).
* :func:`synapse_attention`, one pre-joined key set. Grid (B, Hkv); per
  program the full [T, D] K and V tiles for one kv head live in VMEM
  (T<=8192, D<=256 -> <=8 MiB bf16), queries are the G = H/Hkv group rows.
  D and T must be multiples of 128 for lane alignment (``ops.py`` pads).

Scores accumulate in fp32 on the MXU (bf16 operands in one pass); p·v runs
in f32 at HIGHEST.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# Lanes per grid step of the piece-wise kernel: the largest that divides B
# and whose step, as :func:`_step_bytes` counts it, fits the VMEM budget,
# which leaves a quarter of Mosaic's limit for what the count misses.
_LANE_BLOCKS = (32, 16, 8)
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4
_SUBLANES_BF16 = 16


def _step_bytes(bb: int, H: int, sizes, width: int, itemsize: int) -> int:
    """VMEM of one grid step of ``bb`` lanes: every block, double-buffered
    (q, K, V and the int32 masks in; out and the f32 masses out), and the
    kernel's f32 temporaries: scores, exponentials and p, with the three
    bf16 parts a HIGHEST dot splits p into; the f32 copy of the largest V
    piece and its three bf16 parts; p·v and the output accumulator."""
    T = sum(sizes)
    blocks = itemsize * 2 * (H + T) * width + 4 * 2 * T
    temps = (3 * 4 + 3 * 2) * H * T + (4 + 3 * 2) * max(sizes) * width + 2 * 4 * H * width
    return bb * (2 * blocks + temps)


def _lane_block(B: int, H: int, sizes, width: int, itemsize: int) -> int | None:
    fits = lambda bb: _step_bytes(bb, H, sizes, width, itemsize) <= _VMEM_BUDGET
    for bb in _LANE_BLOCKS:
        if B % bb == 0 and fits(bb):
            return bb
    if B < _LANE_BLOCKS[-1] and fits(B):
        return B  # a block as large as the array needs no (8, 128) alignment
    return None


def fits_in_place(B: int, H: int, sizes, width: int, itemsize: int) -> bool:
    """Whether :func:`synapse_attention_pieces` can tile these pieces:
    ``width`` = Hkv*D fills whole 128-lane rows, every piece length is a
    whole number of bf16 sublane tiles, and a block of lanes with ``H``
    query heads, K/V of ``itemsize`` bytes, fits VMEM."""
    return (width % 128 == 0 and all(t % _SUBLANES_BF16 == 0 for t in sizes)
            and _lane_block(B, H, sizes, width, itemsize) is not None)


def _pieces_kernel(layer_ref, q_ref, *refs, scale: float, n: int):
    # layer_ref: [1] int32 in SMEM, the layer the K/V blocks were read at
    # q_ref: [bb, H, Hkv*D], head h's row zero outside its kv head's D lanes
    # refs:  n keys [bb, T_i, Hkv*D], n values (same), n masks [bb, T_i]
    #        int32; then o_ref [bb, H, Hkv*D] and n masses [bb, T_i] f32
    del layer_ref
    ks, vs, valids = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    o_ref, mass_refs = refs[3 * n], refs[3 * n + 1:]
    q = q_ref[...]
    scores = []
    for k_ref, valid_ref in zip(ks, valids):
        s = mxu_dot(q, k_ref[...], (((2,), (2,)), ((0,), (0,)))) * scale  # [bb, H, T_i]
        scores.append(jnp.where(valid_ref[...][:, None, :] != 0, s, NEG_INF))
    m = functools.reduce(jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in scores])
    es = [jnp.exp(s - m) for s in scores]
    denom = functools.reduce(jnp.add, [jnp.sum(e, axis=-1, keepdims=True) for e in es])
    o = None
    for e, v_ref, mass_ref in zip(es, vs, mass_refs):
        p = e / denom
        pv = mxu_dot(p, v_ref[...].astype(jnp.float32), (((2,), (1,)), ((0,), (0,))))
        o = pv if o is None else o + pv
        mass_ref[...] = jnp.sum(p, axis=1).astype(mass_ref.dtype)
    o_ref[...] = o.astype(o_ref.dtype)


def synapse_attention_pieces(q, pieces, valids, layer, *, scale: float | None = None,
                             interpret: bool = False):
    """q: [B, H, D]; pieces: [(k_i, v_i)], each the stack [NL, B, T_i,
    Hkv*D] of every layer's rows (the cache layout: kv heads side by side);
    valids: [B, T_i] bools; layer: the layer to attend (an int or a traced
    int32 scalar, read by the grid's index maps).

    Returns (out [B, H, D], masses: one [B, T_i] f32 per piece). One
    ``pallas_call`` over every lane and piece, :func:`_lane_block`'s
    choice of lanes a grid step; the caller checks :func:`fits_in_place`
    first.

    p·v contracts p with the whole ``Hkv*D`` row, so each head also
    gathers the other kv heads' values in their lanes, which the wrapper
    discards: the f32 p·v work and the kernel's output are ``Hkv`` times
    what one head's D lanes need (2x at Hkv 2, 8x at Hkv 8).
    """
    B, H, D = q.shape
    width = pieces[0][0].shape[3]
    Hkv = width // D
    G = H // Hkv
    sizes = [k.shape[2] for k, _ in pieces]
    n = len(pieces)
    scale = (1.0 / (D ** 0.5)) if scale is None else scale
    bb = _lane_block(B, H, sizes, width, jnp.dtype(pieces[0][0].dtype).itemsize)
    assert bb is not None and B % bb == 0, ("pieces do not tile", B, sizes, width)
    # block-diagonal query: head h keeps its D values in its kv head's lanes
    own = (jnp.arange(H)[:, None] // G) == jnp.arange(Hkv)[None, :]  # [H, Hkv]
    qb = jnp.where(own[None, :, :, None], q[:, :, None, :], 0).reshape(B, H, width)

    row = lambda t: pl.BlockSpec((None, bb, t, width), lambda i, l: (l[0], i, 0, 0))
    mask = lambda t: pl.BlockSpec((bb, t), lambda i, l: (i, 0))
    lanes = pl.BlockSpec((bb, H, width), lambda i, l: (i, 0, 0))
    out, *masses = pl.pallas_call(
        functools.partial(_pieces_kernel, scale=scale, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // bb,),
            in_specs=[lanes] + [row(t) for t in sizes] * 2 + [mask(t) for t in sizes],
            out_specs=[lanes] + [mask(t) for t in sizes],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, width), q.dtype)]
        + [jax.ShapeDtypeStruct((B, t), jnp.float32) for t in sizes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="synapse_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), qb, *[k for k, _ in pieces],
      *[v for _, v in pieces], *[m.astype(jnp.int32) for m in valids])
    # head h's D values are the lanes of its own kv head; the other kv
    # heads' lanes hold p_h·v of their values, and the where drops them
    out = jnp.where(own[None, :, :, None], out.reshape(B, H, Hkv, D), 0).sum(axis=2)
    return out.astype(q.dtype), masses
