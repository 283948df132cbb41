"""Sharding-aware primitives for the streaming synapse decode.

Two properties of GSPMD on token-sharded synapse buffers (seen lowering
qwen3-8b at the long_500k shape) drive this module:

1. GSPMD turns dynamic-index scatter/gather on token-sharded synapse buffers
   into "involuntary full rematerialization" (replicate -> scatter ->
   reshard), and the attend over the concat forces a per-step f32 all-gather
   of every buffer. One-hot select/contract formulations are elementwise
   over the token dim and shard for free.

2. Softmax over a token-sharded axis cannot be expressed by GSPMD without a
   gather; a shard_map flash-decode (local partial max/sum + psum combine)
   moves only [B,Hkv,G]-sized statistics across chips instead of the
   buffers themselves.

Shard placement is SCOPED, not global: callers either pass an explicit
:class:`ShardContext` (the engine threads one via its ``SynapsePolicy``) or
enter :func:`token_sharding` around tracing (the dry-run). The old
``set_shard_axis`` module global is gone — a test or launch script that set
it would leak interpreter-wide state into every later trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from contextvars import ContextVar

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map as _shard_map

NEG_INF = -1e30


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """``shard_map`` with the replication check disabled (the engine's
    macro tick mixes replicated main-lane state with lane-sharded side
    state — the static checker cannot prove that)."""
    return _shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """Token-shard placement for the synapse buffers: the mesh axis their
    token dims are split over (None = everything local) plus the mesh that
    owns the axis (required whenever ``axis`` is set)."""

    axis: str | None = None
    mesh: object | None = None


_CTX: ContextVar[ShardContext] = ContextVar(
    "synapse_shard_ctx", default=ShardContext()
)


@contextlib.contextmanager
def token_sharding(axis: str | None, mesh=None):
    """Scoped token-shard placement for code that cannot thread an explicit
    :class:`ShardContext` (e.g. the dry-run tracing a whole decode step).
    Always restores the previous context on exit, even on error — the
    leak-proof replacement for the old ``set_shard_axis`` global."""
    token = _CTX.set(ShardContext(axis, mesh))
    try:
        yield _CTX.get()
    finally:
        _CTX.reset(token)


def current_context() -> ShardContext:
    return _CTX.get()


def get_shard_axis() -> str | None:
    return _CTX.get().axis


def _resolve(ctx: ShardContext | None) -> ShardContext:
    return _CTX.get() if ctx is None else ctx


def onehot_write(buf, slot, new, mask=None, *, layer=None, ctx: ShardContext | None = None):
    """buf [B,T,...] <- new [B,...] at per-lane `slot`, via one-hot select.
    With ``layer``, buf is a stack [NL,B,T,...] of per-layer buffers and
    the rows land in layer ``layer`` of it.

    Single-device (no shard axis — the engine hot path): a plain per-lane
    scatter, bitwise-identical to the one-hot select for in-bounds slots
    (0 <= slot < T, which every caller guarantees — the one-hot form drops
    out-of-range slots while a scatter would clamp) but without
    materializing [B,T]-shaped masks for every ring write of every layer
    of every virtual tick. Into a stack the scatter is in place: no copy
    of the layer's buffer is made."""
    if _resolve(ctx).axis is None:
        lane = jnp.arange(new.shape[0])
        at = (lane, slot) if layer is None else (layer, lane, slot)
        val = new.astype(buf.dtype)
        if mask is not None:
            cur = buf[at]
            m = mask.reshape(mask.shape + (1,) * (val.ndim - 1))
            val = jnp.where(m, val, cur)
        return buf.at[at].set(val)
    if layer is not None:
        return buf.at[layer].set(onehot_write(buf[layer], slot, new, mask, ctx=ctx))
    T = buf.shape[1]
    oh = jax.nn.one_hot(slot, T, dtype=bool)  # [B, T]
    if mask is not None:
        oh = oh & mask[:, None]
    oh = oh.reshape(oh.shape + (1,) * (buf.ndim - 2))
    return jnp.where(oh, new[:, None].astype(buf.dtype), buf)


def onehot_read(buf, slot, *, layer=None, ctx: ShardContext | None = None):
    """buf [B,T,...] -> [B,...] at per-lane slot (one-hot contraction; plain
    per-lane gather when no shard axis is live — exact for f32/int32 and
    in-bounds slots, so the two formulations are interchangeable there).
    With ``layer``, buf is a stack [NL,B,T,...] read at layer ``layer``."""
    if _resolve(ctx).axis is None:
        lane = jnp.arange(slot.shape[0])
        return buf[lane, slot] if layer is None else buf[layer, lane, slot]
    if layer is not None:
        buf = buf[layer]
    T = buf.shape[1]
    oh = jax.nn.one_hot(slot, T, dtype=jnp.float32)
    out = jnp.einsum("bt,bt...->b...", oh, buf.astype(jnp.float32))
    return out.astype(buf.dtype)


def piece_attend(q, pieces, valids, scale, *, layer, ctx: ShardContext | None = None):
    """Flash-decode attend over token-sharded (k, v) pieces.

    q: [B,H,D]; pieces: [(k_i, v_i)] with k_i/v_i the stacks
    [NL,B,T_i,Hkv*D] of every layer's lane-dense rows (the synapse cache's
    layout), sharded on T_i over ``ctx.axis`` and attended at layer
    ``layer``; valids: [(B,T_i)] bools.
    Returns (out [B,H,D], masses [(B,T_i)] — per-key probability mass).

    No shard axis (the lane-sharded engine's per-shard body, and the
    single-device fallback): ``kernels.ops.attend_pieces``, the exact
    computation of the default "pallas" attend, so lane-sharded and
    single-device engines stay BITWISE identical
    (tests/test_lane_sharded.py pins this).
    """
    axis = _resolve(ctx).axis
    if axis is None:
        from repro.kernels import ops  # deferred: keeps core importable alone

        return ops.attend_pieces(q, pieces, valids, scale, layer=layer)
    B, H, D = q.shape
    heads = lambda a: a[layer].reshape(a.shape[1:3] + (-1, D))
    pieces = [(heads(k), heads(v)) for k, v in pieces]
    Hkv = pieces[0][0].shape[2]
    G = H // Hkv

    def body(q, *flat):
        n = len(pieces)
        ks, vs, ms = flat[:n], flat[n : 2 * n], flat[2 * n :]
        k_loc = jnp.concatenate(ks, axis=1)
        v_loc = jnp.concatenate(vs, axis=1)
        valid_loc = jnp.concatenate(ms, axis=1)
        qg = q.reshape(B, Hkv, G, D)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k_loc).astype(jnp.float32) * scale
        s = jnp.where(valid_loc[:, None, None, :], s, NEG_INF)
        m = jax.lax.pmax(jnp.max(s, axis=-1), axis)
        e = jnp.exp(s - m[..., None])
        denom = jax.lax.psum(jnp.sum(e, axis=-1), axis)
        p = e / denom[..., None]
        out = jax.lax.psum(
            jnp.einsum("bkgt,btkd->bkgd", p.astype(v_loc.dtype), v_loc), axis
        )
        mass_loc = p.sum(axis=(1, 2))
        splits = list(np.cumsum([k.shape[1] for k in ks]))[:-1]
        masses = jnp.split(mass_loc, splits, axis=1)
        return (out.reshape(B, H, D), *masses)

    from jax.sharding import PartitionSpec as P

    mesh = _resolve(ctx).mesh
    if mesh is None:
        raise ValueError("piece_attend: ShardContext has an axis but no mesh")
    tok = P(None, axis, None, None)
    tokm = P(None, axis)
    rep3 = P(None, None, None)
    in_specs = (rep3, *([tok] * len(pieces)), *([tok] * len(pieces)), *([tokm] * len(pieces)))
    out_specs = (rep3, *([tokm] * len(pieces)))
    flat = [k for k, _ in pieces] + [v for _, v in pieces] + list(valids)
    res = shard_map_nocheck(body, mesh, in_specs, out_specs)(q, *flat)
    return res[0], list(res[1:])
