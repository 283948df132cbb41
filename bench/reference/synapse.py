"""Plain reference of a Warp-Cortex side agent: spawn compression of the
parent's keys and values, then decode over landmark, window and inject
slots (paper §3.3 and its streaming extension), in float32.

Semantics, as the configuration's synapse block states them (``alpha``,
``score_ema``, ``coverage_cap``, K landmarks, a W-slot window, J inject
slots):

Spawn, per layer, from the parent's P prompt keys and values:
  * the query is the parent's newest key, read by the heads of its group;
  * density_t = sum over heads of softmax_t(q·k_t / sqrt(D)), divided by
    its maximum;
  * K greedy picks: score_t = alpha·density_t + (1-alpha)·cov_t, with
    cov_t = min(d_t, cap)/cap, d_t the distance (root mean square over D of
    the difference of the kv-head means) from key t to the nearest landmark
    picked so far (no landmark yet: cov 1); the best unpicked key wins, the
    lowest index on ties; a landmark keeps its score; landmarks are ordered
    by position.

Decode step, per layer, for the new token's k, v:
  * when the window is full, its oldest entry graduates: with
    rate = its accumulated mass / W, landmark rates score·(1-ema), and
    hybrid = alpha·rate + (1-alpha)·cov·max(mean landmark rate, rate)
    (cov against the landmarks as above), it replaces the landmark of the
    lowest rate if hybrid exceeds that rate (or fills a free landmark
    slot), and that landmark's score becomes hybrid/(1-ema);
  * the new token takes the oldest window slot with mass 0;
  * attention over the landmarks, the window's filled slots (the new token
    included) and the filled inject slots;
  * every slot's accumulated mass decays by ``ema`` and gains the mass the
    step's heads put on it.

The reference imports nothing of the program under test; the block
arithmetic is ``qwen_dense``'s.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import qwen_dense as qd


def _pool(k):
    return k.mean(axis=-2)  # [..., Hkv, D] -> [..., D]


def _dist(a, b):
    """RMS distance over D: a [T, D], b [D] -> [T]."""
    diff = a - b[None, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1) / a.shape[-1])


@partial(jax.jit, static_argnames=("n_landmarks", "alpha", "cap"))
def select_landmarks(k, v, allowed, *, n_landmarks, alpha, cap):
    """One layer's spawn compression. k/v [P, Hkv, D] f32 (positions
    0..P-1). ``allowed`` [P] bool: the landmark set being judged (all True
    for a free selection). Each greedy step takes the best allowed key; its
    gap is how far its score lies below the best key of all. A selection
    that rounding alone moved has small gaps; a wrong one, large.
    Returns (lm_k, lm_v [K,Hkv,D], lm_pos [K], lm_score [K], count, gap)."""
    P, Hkv, D = k.shape
    q = k[P - 1]  # [Hkv, D]: every head of group g reads kv head g's newest key
    logits = jnp.einsum("kd,tkd->kt", q, k, precision=qd.HI) / np.sqrt(D)
    # the G heads of a group share a query, so the sum over heads is G times
    # the sum over groups; the maximum normalises G away
    mass = jax.nn.softmax(logits, axis=-1).sum(axis=0)
    density = mass / (jnp.max(mass) + 1e-9)
    pooled = _pool(k)
    n = min(n_landmarks, P)

    def body(i, carry):
        min_dist, taken, idx, score, gap = carry
        cov = jnp.minimum(min_dist, cap) / cap
        s = jnp.where(taken, -jnp.inf, alpha * density + (1.0 - alpha) * cov)
        j = jnp.argmax(jnp.where(allowed, s, -jnp.inf))
        gap = jnp.maximum(gap, jnp.max(s) - s[j])
        min_dist = jnp.minimum(min_dist, _dist(pooled, pooled[j]))
        return min_dist, taken.at[j].set(True), idx.at[i].set(j), score.at[i].set(s[j]), gap

    init = (jnp.full((P,), jnp.inf), jnp.zeros((P,), bool),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32), jnp.float32(0.0))
    _, _, idx, score, gap = jax.lax.fori_loop(0, n, body, init)
    order = jnp.argsort(idx)
    idx, score = idx[order], score[order]
    pad = n_landmarks - n
    padk = lambda a: jnp.concatenate([a[idx], jnp.zeros((pad,) + a.shape[1:], a.dtype)])
    return (padk(k), padk(v),
            jnp.concatenate([idx, jnp.zeros((pad,), jnp.int32)]),
            jnp.concatenate([score, jnp.full((pad,), -jnp.inf)]), n, gap)


def spawn_state(prompt_kv, syn: dict, picks=None):
    """Per-layer side state at spawn, from the parent's prompt (k, v).
    ``picks`` [L, K]: the landmark positions to judge and keep (None: the
    reference's own free selection). Returns (state, widest selection gap)."""
    K, W, J = syn["n_landmarks"], syn["window"], syn["n_inject"]
    layers, gap = [], 0.0
    for l, (k, v) in enumerate(prompt_kv):
        P = k.shape[0]
        allowed = np.ones(P, bool)
        if picks is not None:
            allowed = np.zeros(P, bool)
            allowed[np.asarray(picks[l])] = True
        lm_k, lm_v, lm_pos, lm_score, n, g = select_landmarks(
            k, v, jnp.asarray(allowed), n_landmarks=K, alpha=float(syn["alpha"]),
            cap=float(syn["coverage_cap"]))
        gap = max(gap, float(g))
        Hkv, D = k.shape[1:]
        layers.append({
            "lm_k": lm_k, "lm_v": lm_v, "lm_pos": lm_pos, "lm_score": lm_score,
            "lm_count": jnp.asarray(n, jnp.int32),
            "win_k": jnp.zeros((W, Hkv, D)), "win_v": jnp.zeros((W, Hkv, D)),
            "win_score": jnp.zeros((W,)), "win_count": jnp.asarray(0, jnp.int32),
            "inj_k": jnp.zeros((J, Hkv, D)), "inj_v": jnp.zeros((J, Hkv, D)),
            "inj_count": jnp.asarray(0, jnp.int32),
        })
    return jax.tree.map(lambda *a: jnp.stack(a), *layers), gap


def _layer_step(w, st, x, pos, dims: qd.Dims, num: qd.Numerics, alpha, ema, cap):
    """One side-agent decode step through one layer. x [1, d]."""
    K, W = st["lm_k"].shape[0], st["win_k"].shape[0]
    q, k, v = qd.qkv(w, dims, num, x, pos[None])
    k1, v1 = k[0], v[0]

    # graduation of the window's oldest entry
    slot = st["win_count"] % W
    full = st["win_count"] >= W
    lm_valid = jnp.arange(K) < st["lm_count"]
    g_k, g_v, g_score = st["win_k"][slot], st["win_v"][slot], st["win_score"][slot]
    d = jnp.where(lm_valid, _dist(_pool(st["lm_k"]), _pool(g_k)), jnp.inf)
    min_d = jnp.min(d)
    cov = jnp.minimum(jnp.where(jnp.isfinite(min_d), min_d, cap), cap) / cap
    one_minus = max(1.0 - ema, 1e-6)
    resid = jnp.clip(st["win_count"].astype(jnp.float32), 1.0, float(W))
    g_rate = g_score / resid
    lm_rate = st["lm_score"] * one_minus
    min_rate = jnp.min(jnp.where(lm_valid, lm_rate, jnp.inf))
    mean_rate = jnp.sum(jnp.where(lm_valid, lm_rate, 0.0)) / jnp.maximum(
        st["lm_count"].astype(jnp.float32), 1.0)
    hybrid = alpha * g_rate + (1 - alpha) * cov * jnp.maximum(mean_rate, g_rate)
    has_room = st["lm_count"] < K
    target = jnp.where(has_room, st["lm_count"],
                       jnp.argmin(jnp.where(lm_valid, lm_rate, jnp.inf)))
    promote = full & (has_room | (hybrid > min_rate))
    hit = (jnp.arange(K) == target) & promote
    lm_k = jnp.where(hit[:, None, None], g_k[None], st["lm_k"])
    lm_v = jnp.where(hit[:, None, None], g_v[None], st["lm_v"])
    lm_score = jnp.where(hit, hybrid / one_minus, st["lm_score"])
    lm_count = jnp.where(promote, jnp.minimum(st["lm_count"] + 1, K), st["lm_count"])

    # the new token takes the oldest window slot
    at = jnp.arange(W) == slot
    win_k = jnp.where(at[:, None, None], k1[None], st["win_k"])
    win_v = jnp.where(at[:, None, None], v1[None], st["win_v"])
    win_score = jnp.where(at, 0.0, st["win_score"])

    keys = jnp.concatenate([lm_k, win_k, st["inj_k"]])
    vals = jnp.concatenate([lm_v, win_v, st["inj_v"]])
    J = st["inj_k"].shape[0]
    visible = jnp.concatenate([
        jnp.arange(K) < lm_count,
        jnp.arange(W) < jnp.minimum(st["win_count"] + 1, W),
        jnp.arange(J) < st["inj_count"],
    ])
    out, p = qd.attend(q, keys, vals, visible[None, :])
    mass = p[0].sum(axis=0)  # [K+W+J]: summed over heads
    x = qd.finish_layer(w, dims, num, x, out)
    new = dict(st, lm_k=lm_k, lm_v=lm_v, lm_count=lm_count,
               lm_score=lm_score * ema + mass[:K],
               win_k=win_k, win_v=win_v, win_score=win_score * ema + mass[K:K + W],
               win_count=st["win_count"] + 1)
    return x, new


@partial(jax.jit, static_argnames=("dims", "num", "alpha", "ema", "cap"))
def _side_run(params, state, tokens, pos, *, dims, num, alpha, ema, cap):
    """Decode ``tokens`` [n] at ``pos`` [n] from ``state``; returns the
    final hidden state [n, d] (before the final norm) of every step."""
    g = params["groups"][0]

    def step(st, tp):
        tok, p = tp
        x = qd.embed(params, num, tok[None])

        def layer(x, lw):
            w_bf, st_l = lw
            w = _upcast(w_bf, num)
            x, st_l = _layer_step(w, st_l, x, p, dims, num, alpha, ema, cap)
            return x, st_l

        x, st = jax.lax.scan(layer, x, (g, st))
        return st, x[0]

    _, xs = jax.lax.scan(step, state, (tokens, pos))
    return xs


def _upcast(g, num: qd.Numerics):
    """One layer's slice of the stacked bf16 tree as f32 weights."""
    a, m = g["attn"], g["mlp"]
    w = {"ln1": g["ln1"].astype(jnp.float32), "ln2": g["ln2"].astype(jnp.float32)}
    for n in ("wq", "wk", "wv", "wo"):
        w[n] = num.weight(a[n])
    for n in ("gate", "up", "down"):
        w[n] = num.weight(m[n])
    for n in ("bq", "bk", "bv", "q_norm", "k_norm"):
        if n in a:
            w[n] = a[n].astype(jnp.float32)
    return w


def side_logits(params, dims: qd.Dims, num: qd.Numerics, prompt_kv, syn: dict,
                inputs, start_pos: int, picks=None):
    """Logits [n, V] of a side agent's decode inputs (its task prompt, then
    its own tokens) at positions start_pos, start_pos+1, ..., the widest gap
    of its spawn selection (see ``spawn_state``) and the landmark positions
    it kept [L, K]."""
    state, gap = spawn_state(prompt_kv, syn, picks)
    lm_pos = np.asarray(state.pop("lm_pos"))
    n = len(inputs)
    # one compiled run for every side: steps past the real inputs come last
    # and change nothing before them
    N = -(-n // 64) * 64
    tokens = np.concatenate([np.asarray(inputs, np.int64), np.zeros(N - n, np.int64)])
    xs = _side_run(params, state, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(start_pos + np.arange(N), jnp.int32),
                   dims=dims, num=num, alpha=float(syn["alpha"]),
                   ema=float(syn["score_ema"]), cap=float(syn["coverage_cap"]))
    return qd.logits_of(params, dims, num, xs[:n]), gap, lm_pos
