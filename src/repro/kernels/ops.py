"""Jit'd public wrappers around the Pallas kernels.

Handles tile alignment, dtype policy, and the interpret-mode switch (CPU
container: interpret=True executes the kernel body in Python for
correctness; on TPU the same code compiles to Mosaic). ``INTERPRET``
auto-detects the backend.

Which path pads: :func:`attend_pieces`, the side pass's attend, reads the
synapse pieces in place from the layer stacks when they tile (no copy, no
pad, no concatenate); :func:`synapse_attention` on one pre-joined key set
pads T and D to multiples of 128, as does :func:`landmark_score` on the
river's cache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import landmark_score as _ls
from repro.kernels import ref as _ref
from repro.kernels import synapse_attention as _sa

INTERPRET = jax.default_backend() != "tpu"
# finite mask shared with the kernels AND the per-lane sampler: keeps
# all-invalid rows NaN-free
NEG_INF = _sa.NEG_INF


def ring_append(ring, vals, cursor):
    """Append one column to the device token rings: ring [B, R] <- vals [B]
    at column ``cursor`` ([] int32, traced).

    The rings are the engine's zero-host-sync drain buffers; inside the
    macro-tick ``lax.scan`` the cursor is the scan carry, so the same
    program serves every virtual tick of a window.
    """
    return jax.lax.dynamic_update_slice(
        ring, vals.astype(ring.dtype)[:, None], (jnp.zeros_like(cursor), cursor)
    )


def _pad_to(x, axis: int, mult: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("interpret", "scale"))
def synapse_attention(q, keys, values, valid, *, scale: float | None = None, interpret: bool | None = None):
    """Padded/aligned wrapper. q [B,H,D]; keys/values [B,T,Hkv,D]; valid [B,T].
    ``scale`` defaults to 1/sqrt(D of q).

    Tile alignment only matters for the compiled Mosaic path; under
    interpret mode padding just multiplies the emulated kernel's work (and
    materializes pad/slice ops), so the CPU path runs the true shapes.
    """
    interpret = INTERPRET if interpret is None else interpret
    B, H, D = q.shape
    T = keys.shape[1]
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    if interpret:
        if T <= 512:
            # decode-sized problems: the Pallas interpreter's grid/blocking
            # machinery costs more than the math — the jnp oracle computes
            # the same masked softmax attend (same NEG_INF mask) faster on
            # CPU, and this is the engine's per-tick hot path
            return _ref.synapse_attention_ref(q, keys, values, valid, scale=scale)
        return _sa.synapse_attention(q, keys, values, valid, scale=scale, interpret=True)
    qp = _pad_to(q, 2, 128)
    kp = _pad_to(_pad_to(keys, 3, 128), 1, 128)
    vp = _pad_to(_pad_to(values, 3, 128), 1, 128)
    validp = _pad_to(valid, 1, 128, value=False)
    out, mass = _sa.synapse_attention(qp, kp, vp, validp, scale=scale, interpret=False)
    return out[:, :, :D], mass[:, :T]


def synapse_attend(q, pieces, valids, *, layer, scale: float | None = None, policy=None):
    """Policy-routed attend over [landmarks; window; inject] k/v pieces —
    the single entry the synapse decode calls, threading the engine-owned
    ``SynapsePolicy`` (no module globals).

    Routing: a live token-shard axis — from ``policy.shard_axis`` or an
    enclosing :func:`repro.core.synapse_sharded.token_sharding` scope — or
    ``policy.attend_impl == "piece"`` selects the flash-decode
    ``piece_attend`` path; otherwise :func:`attend_pieces` on this device.
    ``piece_attend`` with no live axis calls :func:`attend_pieces` too, so
    the choice never perturbs token streams (the lane-sharded engine's
    bitwise-parity contract). The pieces are stacks [NL,B,T_i,Hkv*D] of
    every layer's rows, and layer ``layer`` of them is attended.
    Returns (out [B,H,D], masses — one [B,T_i] per piece).
    """
    from repro.core import synapse_sharded as sharded  # deferred: no cycle

    ctx = sharded.current_context()
    p_axis = getattr(policy, "shard_axis", None)
    if p_axis is not None:
        ctx = sharded.ShardContext(p_axis, ctx.mesh)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if ctx.axis is not None or getattr(policy, "attend_impl", "pallas") == "piece":
        return sharded.piece_attend(q, pieces, valids, scale, ctx=ctx, layer=layer)
    return attend_pieces(q, pieces, valids, scale, layer=layer)


def attend_pieces(q, pieces, valids, scale: float, *, layer):
    """ONE fused attend over k/v pieces on one device. q [B,H,D]; each
    piece is the stack [NL,B,T_i,Hkv*D] of every layer's lane-dense rows
    (the synapse cache's layout), attended at layer ``layer`` (an int or a
    traced index).

    On the chip, pieces that tile (:func:`synapse_attention.fits_in_place`:
    Hkv*D a multiple of 128, every T_i a multiple of 16) go to the
    piece-wise kernel, read in place from the stacks: no copy of the
    layer, no concatenation, no padding, one call. Other shapes are joined
    along T and take the padded :func:`synapse_attention`. In interpret
    mode (CPU) the pieces are joined too, and decode sizes run the jnp
    oracle.
    Returns (out [B,H,D], masses — one [B,T_i] per piece).
    """
    B, H, D = q.shape
    sizes = [k.shape[2] for k, _ in pieces]
    width = pieces[0][0].shape[3]
    if not INTERPRET and _sa.fits_in_place(B, H, sizes, width, pieces[0][0].dtype.itemsize):
        return _sa.synapse_attention_pieces(q, pieces, valids, layer, scale=scale)
    heads = lambda a: a[layer].reshape(a.shape[1:3] + (width // D, D))
    k_all = jnp.concatenate([heads(k) for k, _ in pieces], axis=1)
    v_all = jnp.concatenate([heads(v) for _, v in pieces], axis=1)
    valid_all = jnp.concatenate(list(valids), axis=1)
    out, mass = synapse_attention(q, k_all, v_all, valid_all, scale=scale)
    splits = [sum(sizes[: i + 1]) for i in range(len(sizes) - 1)]
    return out, list(jnp.split(mass, splits, axis=1))


@partial(jax.jit, static_argnames=("interpret", "block_t"))
def landmark_score(q, keys, landmarks=None, valid=None, *, block_t: int = 512, interpret: bool | None = None):
    """Returns (density [B,T] — per-head softmax mass summed over heads,
    min_dist [B,T] — or None when ``landmarks`` is None: the coverage block
    of the kernel is skipped for density-only sweeps). Handles padding;
    softmax normalization over the true T. ``valid`` ([B,T] bool, optional)
    restricts the softmax to valid keys — the per-head normalizers only
    count the live prefix of the cache."""
    interpret = INTERPRET if interpret is None else interpret
    B, H, D = q.shape
    T = keys.shape[1]
    if interpret:
        # no tile alignment needed when emulating: one block over the true T
        logits, dist = _ls.landmark_score(
            q, keys, landmarks, scale=1.0 / (D ** 0.5), true_d=D, block_t=T, interpret=True
        )
    else:
        block_t = min(block_t, max(128, ((T + 127) // 128) * 128))
        qp = _pad_to(q, 2, 128)
        kp = _pad_to(_pad_to(keys, 3, 128), 1, block_t)
        lmp = None if landmarks is None else _pad_to(landmarks, 2, 128)
        logits, dist = _ls.landmark_score(
            qp, kp, lmp, scale=1.0 / (D ** 0.5), true_d=D, block_t=block_t, interpret=False
        )
        logits = logits[:, :, :T]
        dist = None if dist is None else dist[:, :T]
    if valid is not None:
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    density = jax.nn.softmax(logits, axis=-1).sum(axis=1)  # paper: sum_h softmax_h
    return density, dist
