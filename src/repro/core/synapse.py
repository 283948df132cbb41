"""The Topological Synapse (paper §3.3) — KV-cache landmark sparsification.

Two modes:

1. ``compress`` (paper-faithful): one-shot hybrid density-coverage landmark
   selection from a full cache, used when spawning a side agent. The hybrid
   score is
       score_i = alpha * density_i + (1 - alpha) * coverage_i
   where density_i is the paper's "Attention Score Summation" (softmax
   attention mass of the main agent's current query over key i, summed over
   heads — an inverse kernel-density estimate on the KV point cloud) and
   coverage_i is the greedy maxmin (farthest-point) term that bounds the
   Hausdorff distance of the landmark set to the context manifold. This is
   exactly the hybrid landmarking of [Ruiz Williams 2025] ported to the
   transformer latent space.

2. ``synapse_decode`` (streaming extension, beyond-paper): the same policy
   run online during decode — a recent-window ring plus a landmark buffer
   with hybrid-score eviction. This makes dense-architecture decode O(K+W+J)
   per step and is what unlocks the long_500k shape (DESIGN.md §4).

Both operate per layer, vectorized over the batch/agent axis.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import synapse_sharded as sharded
from repro.models import cache as cache_lib
from repro.models.attention import decode_attend, _project_qkv, _rotate
from repro.models.config import ModelConfig

NEG_INF = -1e30


@dataclass(frozen=True)
class SynapsePolicy:
    alpha: float = 0.5        # density vs coverage blend
    score_ema: float = 0.99   # per-step decay of accumulated attention mass
    coverage_cap: float = 4.0 # maxmin distances saturate here (normalized units)
    # decode attend implementation: "pallas" = one fused kernels.ops attend
    # over the [landmarks; window; inject] pieces (single device, interpret
    # mode on CPU); "piece" = synapse_sharded.piece_attend (the multi-chip
    # flash-decode). A live shard axis always forces "piece".
    attend_impl: str = "pallas"
    # mesh axis the synapse token dims are sharded over (None = local). The
    # engine-owned replacement for the old synapse_sharded.set_shard_axis
    # module global: the policy rides the CacheSpec through decode_step into
    # kernels.ops.synapse_attend, so shard placement is scoped to the trace
    # that owns it. (The engine's LANE sharding keeps this None — lanes are
    # split across devices, each lane's token dims stay local.)
    shard_axis: str | None = None


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def _pool_heads(k):
    """[..., Hkv, D] -> [..., D] mean over kv heads (coverage geometry)."""
    return k.astype(jnp.float32).mean(axis=-2)


def _pool_rows(k, d: int):
    """Lane-dense [..., Hkv*D] -> [..., D] mean over kv heads, summed over
    the heads' column slices (no [..., Hkv, D] relayout of the rows)."""
    hkv = k.shape[-1] // d
    kf = k.astype(jnp.float32)
    return functools.reduce(jnp.add, [kf[..., j * d:(j + 1) * d] for j in range(hkv)]) / hkv


def _normed_dist(a, b):
    """||a-b|| / sqrt(d): a [..., T, D], b [..., D] -> [..., T]."""
    d = a.shape[-1]
    diff = a - b[..., None, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1) / d)


def attention_density(q, keys, valid):
    """Paper Eq. in §3.3: softmax attention mass per key, summed over heads.

    q: [B, H, D]; keys: [B, T, Hkv, D]; valid: [B, T] -> [B, T] f32.
    """
    _, mass = decode_attend(q, keys, jnp.zeros_like(keys), valid)
    return mass


def kernel_density(q, keys, valid):
    """attention_density via kernels.ops.landmark_score: one fused sweep over
    the cache computes the per-head logits (the bandwidth-bound half); the
    valid-masked softmax normalization is a cheap [B,H,T] reduction. Falls
    back to the jnp path when a shard axis is live (Pallas blocks are not
    GSPMD-partitionable)."""
    from repro.kernels import ops  # deferred: kernels are optional at import

    if sharded.get_shard_axis() is not None:
        return attention_density(q, keys, valid)
    density, _ = ops.landmark_score(q, keys, None, valid)  # density-only sweep
    return density


def _attend(q1, pieces, valids, scale, policy: SynapsePolicy, layer):
    """Attend over layer ``layer`` of the stacked [landmarks; window;
    inject] k/v pieces — delegates to :func:`repro.kernels.ops.synapse_attend`,
    which routes on the policy (fused Pallas attend vs the token-sharded
    flash-decode piece_attend).
    Returns (out [B,H,D], masses — one [B,T_i] per piece)."""
    from repro.kernels import ops

    return ops.synapse_attend(q1, pieces, valids, scale=scale, policy=policy, layer=layer)


# ---------------------------------------------------------------------------
# one-shot compression (paper-faithful side-agent spawn)
# ---------------------------------------------------------------------------
def select_landmarks(keys, valid, density, k: int, policy: SynapsePolicy):
    """Greedy hybrid density-coverage selection.

    keys: [B, T, Hkv, D]; valid: [B, T]; density: [B, T].
    Returns indices [B, k] (sorted by position) and the hybrid scores [B, k].
    """
    B, T = density.shape
    pooled = _pool_heads(keys)  # [B, T, D]
    density = density / (jnp.max(density, axis=-1, keepdims=True) + 1e-9)
    cap = policy.coverage_cap

    def body(i, carry):
        min_dist, chosen_idx, chosen_score, taken = carry
        cov = jnp.minimum(min_dist, cap) / cap
        score = policy.alpha * density + (1.0 - policy.alpha) * cov
        score = jnp.where(valid & ~taken, score, NEG_INF)
        idx = jnp.argmax(score, axis=-1)  # [B]
        best = jnp.take_along_axis(score, idx[:, None], axis=-1)[:, 0]
        new_lm = jnp.take_along_axis(pooled, idx[:, None, None], axis=1)[:, 0]  # [B, D]
        min_dist = jnp.minimum(min_dist, _normed_dist(pooled, new_lm))
        taken = taken | (jax.nn.one_hot(idx, T, dtype=bool))
        chosen_idx = chosen_idx.at[:, i].set(idx)
        chosen_score = chosen_score.at[:, i].set(best)
        return min_dist, chosen_idx, chosen_score, taken

    init = (
        jnp.full((B, T), jnp.inf, jnp.float32),
        jnp.zeros((B, k), jnp.int32),
        jnp.zeros((B, k), jnp.float32),
        jnp.zeros((B, T), bool),
    )
    _, idx, score, _ = jax.lax.fori_loop(0, k, body, init)
    picked_valid = score > NEG_INF / 2  # False when T_valid < k (short prompts)
    return idx, score, picked_valid


def compress(
    cfg: ModelConfig,
    cache: cache_lib.FullCache,
    query,  # [B, H, D] — the main agent's current query state (paper: Q_t), or
            # None to use the cache's accumulated attention-mass density
    n_landmarks: int,
    window: int,
    n_inject: int = 0,
    policy: SynapsePolicy = SynapsePolicy(),
) -> cache_lib.SynapseCache:
    """Full cache -> SynapseCache for a freshly spawned side agent."""
    B, T = cache.pos.shape
    slots = jnp.arange(T)
    valid = slots[None, :] < cache.length[:, None]
    density = kernel_density(query, cache.k, valid) if query is not None else cache.score
    idx, score, picked = select_landmarks(cache.k, valid, density, n_landmarks, policy)
    # stable order: sort landmarks by original position; invalid picks last
    pos_sel = jnp.take_along_axis(cache.pos, idx, axis=1)
    pos_sel = jnp.where(picked, pos_sel, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(pos_sel, axis=1)
    idx = jnp.take_along_axis(idx, order, axis=1)
    score = jnp.take_along_axis(score, order, axis=1)

    gather = lambda a: jnp.take_along_axis(a, idx[:, :, None, None], axis=1).reshape(
        B, n_landmarks, -1)  # lane-dense [B, K, Hkv*D]
    syn = cache_lib.init_synapse_cache(
        cfg, B, n_landmarks, window, n_inject, dtype=cache.k.dtype
    )
    k_valid = jnp.minimum(cache.length, n_landmarks)
    return cache_lib.SynapseCache(
        lm_k=gather(cache.k),
        lm_v=gather(cache.v),
        lm_pos=jnp.take_along_axis(cache.pos, idx, axis=1),
        lm_score=score,
        lm_count=k_valid,
        win_k=syn.win_k,
        win_v=syn.win_v,
        win_pos=syn.win_pos,
        win_score=syn.win_score,
        inj_k=syn.inj_k,
        inj_v=syn.inj_v,
        inj_pos=syn.inj_pos,
        inj_count=syn.inj_count,
        win_count=jnp.zeros_like(cache.length),
        length=cache.length,
    )


# ---------------------------------------------------------------------------
# streaming decode over a SynapseCache
# ---------------------------------------------------------------------------
def synapse_decode(
    attn_params,
    cfg: ModelConfig,
    x,          # [B, 1, dm]
    cache: cache_lib.SynapseCache,
    positions,  # [B] (or [B,3] mrope)
    policy: SynapsePolicy = SynapsePolicy(),
    *,
    layer,
):
    """One decode step: attend over [landmarks; window; inject slots], write
    the new token into the window ring, graduate/evict on overflow.

    ``cache`` holds every layer's synapse ([NL, B, ...] leaves: the decode
    scan carries it) and the step reads and writes layer ``layer`` (an int
    or a traced index): K/V rows are written into the stack in place and
    the attend reads the stacked pieces, so no per-layer copy of the key
    set is made.

    Returns (y [B,1,dm], new_cache, stats dict).
    """
    at = lambda a: a[layer]  # this layer's slice of a small leaf
    B = x.shape[0]
    K, W, J = cache.n_landmarks, cache.window, cache.n_inject
    win_count, lm_count, lm_score = at(cache.win_count), at(cache.lm_count), at(cache.lm_score)
    win_pos, win_score = at(cache.win_pos), at(cache.win_score)
    q, k, v = _project_qkv(attn_params, cfg, x)
    if cfg.rope_kind == "mrope":
        q = _rotate(cfg, q, positions[..., None])
        k = _rotate(cfg, k, positions[..., None])
        pos_scalar = positions[:, 0]
    else:
        q = _rotate(cfg, q, positions[..., None])
        k = _rotate(cfg, k, positions[..., None])
        pos_scalar = positions
    D = q.shape[-1]
    # the synapse stores K/V lane-dense: one [Hkv*D] row per slot
    q1, k1, v1 = q[:, 0], k[:, 0].reshape(B, -1), v[:, 0].reshape(B, -1)

    # ---- 1. graduation: the slot the new token will overwrite ----
    # one-hot reads/writes shard over the token dim without scatter
    # (EXPERIMENTS.md §Perf: SPMD 'involuntary full rematerialization').
    slot = win_count % W  # [B]
    win_full = win_count >= W
    grad_k = sharded.onehot_read(cache.win_k, slot, layer=layer)  # [B, Hkv*D]
    grad_v = sharded.onehot_read(cache.win_v, slot, layer=layer)
    grad_pos = sharded.onehot_read(win_pos, slot)
    grad_score = sharded.onehot_read(win_score, slot)

    pooled_lm = _pool_rows(at(cache.lm_k), D)             # [B, K, D]
    grad_pooled = _pool_rows(grad_k, D)                   # [B, D]
    dist = _normed_dist(pooled_lm, grad_pooled)           # [B, K]
    lm_slot_valid = jnp.arange(K)[None, :] < lm_count[:, None]
    min_dist = jnp.min(jnp.where(lm_slot_valid, dist, jnp.inf), axis=-1)
    cov = jnp.minimum(jnp.where(jnp.isfinite(min_dist), min_dist, policy.coverage_cap), policy.coverage_cap) / policy.coverage_cap

    # Rate-based comparison: landmark scores are EMAs that saturate at
    # mass_rate/(1-ema) after long residency, while a graduating token only
    # accumulated for ~W steps — comparing raw totals freezes the landmark
    # set on the earliest tokens. Convert both to per-step attention-mass
    # rates; the coverage bonus is scaled into rate units by the mean
    # landmark rate so the hybrid blend stays dimensionally consistent.
    one_minus_ema = max(1.0 - policy.score_ema, 1e-6)
    resid = jnp.minimum(jnp.maximum(win_count.astype(jnp.float32), 1.0), float(W))
    grad_rate = grad_score / resid
    lm_rate = lm_score * one_minus_ema                            # [B, K]
    lm_rate_masked = jnp.where(lm_slot_valid, lm_rate, jnp.inf)
    min_lm_rate = jnp.min(lm_rate_masked, axis=-1)
    mean_lm_rate = jnp.sum(jnp.where(lm_slot_valid, lm_rate, 0.0), axis=-1) / jnp.maximum(
        lm_count.astype(jnp.float32), 1.0
    )
    hybrid_rate = policy.alpha * grad_rate + (1 - policy.alpha) * cov * jnp.maximum(
        mean_lm_rate, grad_rate
    )

    # candidate landmark slot: first empty, else argmin rate
    evict_slot = jnp.where(
        lm_count < K,
        lm_count,
        jnp.argmin(jnp.where(lm_slot_valid, lm_rate, jnp.inf), axis=-1),
    )
    promote = win_full & ((lm_count < K) | (hybrid_rate > min_lm_rate))

    lm_k = sharded.onehot_write(cache.lm_k, evict_slot, grad_k, mask=promote, layer=layer)
    lm_v = sharded.onehot_write(cache.lm_v, evict_slot, grad_v, mask=promote, layer=layer)
    lm_pos = sharded.onehot_write(at(cache.lm_pos), evict_slot, grad_pos, mask=promote)
    # store back in EMA-steady units so future comparisons stay consistent
    lm_score = sharded.onehot_write(
        lm_score, evict_slot, hybrid_rate / one_minus_ema, mask=promote
    )
    lm_count = jnp.where(promote, jnp.minimum(lm_count + 1, K), lm_count)

    # ---- 2. write the new token into the ring ----
    win_k = sharded.onehot_write(cache.win_k, slot, k1, layer=layer)
    win_v = sharded.onehot_write(cache.win_v, slot, v1, layer=layer)
    win_pos = sharded.onehot_write(win_pos, slot, pos_scalar)
    win_score = sharded.onehot_write(win_score, slot, jnp.zeros((B,), jnp.float32))

    # ---- 3. attend over [landmarks; window; inject] ----
    # default: one fused Pallas pass over the three pieces, read in place
    # from the stacks (the buffers leave HBM exactly once per step);
    # sharded runs flash-decode over token-sharded pieces, crossing chips
    # with [B,Hkv,G] stats only.
    lm_valid = jnp.arange(K)[None, :] < lm_count[:, None]
    win_valid = jnp.arange(W)[None, :] < jnp.minimum(win_count + 1, W)[:, None]
    inj_valid = jnp.arange(J)[None, :] < at(cache.inj_count)[:, None]
    scale = 1.0 / (q1.shape[-1] ** 0.5)
    out, masses = _attend(
        q1,
        [(lm_k, lm_v), (win_k, win_v), (cache.inj_k, cache.inj_v)],
        [lm_valid, win_valid, inj_valid],
        scale,
        policy,
        layer,
    )
    y = out.reshape(B, -1) @ attn_params["wo"]

    # ---- 4. accumulate attention mass (density statistic) ----
    ema = policy.score_ema
    put = lambda stack, val: stack.at[layer].set(val)
    new_cache = dataclasses.replace(
        cache, lm_k=lm_k, lm_v=lm_v, win_k=win_k, win_v=win_v,
        lm_pos=put(cache.lm_pos, lm_pos),
        lm_score=put(cache.lm_score, lm_score * ema + masses[0]),
        lm_count=put(cache.lm_count, lm_count),
        win_pos=put(cache.win_pos, win_pos),
        win_score=put(cache.win_score, win_score * ema + masses[1]),
        win_count=put(cache.win_count, win_count + 1),
        length=put(cache.length, at(cache.length) + 1),
    )
    stats = {"promoted": promote, "attn_mass_landmarks": masses[0].sum(-1)}
    return y[:, None, :], new_cache, stats


def synapse_bytes(cfg: ModelConfig, n_landmarks: int, window: int, n_inject: int, n_layers: int | None = None) -> int:
    """Per-agent synapse footprint (the paper's ~10 MB claim)."""
    syn = cache_lib.init_synapse_cache(cfg, 1, n_landmarks, window, n_inject)
    per_layer = cache_lib.cache_bytes(syn)
    return per_layer * (n_layers if n_layers is not None else cfg.n_layers)
