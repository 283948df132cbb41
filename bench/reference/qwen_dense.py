"""Plain float32 reference of the dense Qwen2 / Qwen3 decoder block.

Written from the published description (Qwen2 and Qwen3 technical reports,
the Hugging Face ``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM`` block), in
straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``. No kernels, no cache, no batching: a layer takes the
hidden states of a whole token sequence and returns the next ones. It
imports nothing of the program under test.

Block, per layer: ``x += Wo·Attn(RoPE(qk_norm(Wq·n1(x)+bq)), RoPE(qk_norm(Wk·n1(x)+bk)), Wv·n1(x)+bv)``
then ``x += Wdown·(silu(Wgate·n2(x)) * Wup·n2(x))``; RMSNorm with a learned
scale; grouped-query attention (query head h reads kv head h // G); RoPE
in the rotate-half form over the whole head; logits = final-norm(x)·E^T for
a tied head. Departures from the published block:

* ``rms_norm_eps`` is taken from the configuration file (both models
  publish 1e-6);
* attention may read extra key/value slots that are not tokens of the
  sequence (a side agent's thought injected into a river, see
  ``river_logits``): the mask is by slot order, which is what the
  Warp-Cortex merge defines.

``Numerics`` selects the arithmetic of the linear layers: ``"f32"`` is the
reference; ``"fp8"`` quantizes weights (per tensor) and activations (per
row) to float8 e4m3 before each product, the control one precision step
below the bfloat16 the configurations state.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis`` (None: whole
    tensor), and back to f32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-12) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@dataclass(frozen=True)
class Numerics:
    mode: str = "f32"  # "f32" | "fp8"

    def weight(self, w):
        w = jnp.asarray(w, jnp.float32)
        return _q8(w, None) if self.mode == "fp8" else w

    def dot(self, x, w):
        """x [..., K] · w [K, N] with w already passed through ``weight``."""
        if self.mode == "fp8":
            x = _q8(x, -1)
        return jnp.matmul(x, w, precision=HI)


F32 = Numerics("f32")
FP8 = Numerics("fp8")


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    qk_norm: bool
    tie_embeddings: bool

    @staticmethod
    def of(model: dict) -> "Dims":
        return Dims(**{k: model[k] for k in Dims.__dataclass_fields__})


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, heads, D], pos [S] -> rotate-half RoPE."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_weights(params, l: int, num: Numerics):
    """Layer ``l``'s weights as f32 (through ``num``), from the bf16 tree."""
    g = params["groups"][0]
    a, m = g["attn"], g["mlp"]
    w = {
        "ln1": g["ln1"][l].astype(jnp.float32), "ln2": g["ln2"][l].astype(jnp.float32),
        "wq": num.weight(a["wq"][l]), "wk": num.weight(a["wk"][l]),
        "wv": num.weight(a["wv"][l]), "wo": num.weight(a["wo"][l]),
        "gate": num.weight(m["gate"][l]), "up": num.weight(m["up"][l]),
        "down": num.weight(m["down"][l]),
    }
    for b in ("bq", "bk", "bv"):
        if b in a:
            w[b] = a[b][l].astype(jnp.float32)
    for n in ("q_norm", "k_norm"):
        if n in a:
            w[n] = a[n][l].astype(jnp.float32)
    return w


def qkv(w, dims: Dims, num: Numerics, x, pos):
    """Pre-norm, projections, biases, per-head qk norm and RoPE of the
    tokens ``x`` [S, d] at ``pos`` [S]. Returns q [S,H,D], k/v [S,Hkv,D]."""
    S = x.shape[0]
    H, Hkv, D = dims.n_heads, dims.n_kv_heads, dims.d_head
    h = rms_norm(x, w["ln1"], dims.norm_eps)
    q, k, v = num.dot(h, w["wq"]), num.dot(h, w["wk"]), num.dot(h, w["wv"])
    if dims.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = q.reshape(S, H, D), k.reshape(S, Hkv, D), v.reshape(S, Hkv, D)
    if dims.qk_norm:
        q = rms_norm(q, w["q_norm"], dims.norm_eps)
        k = rms_norm(k, w["k_norm"], dims.norm_eps)
    return rope(q, pos, dims.rope_theta), rope(k, pos, dims.rope_theta), v


def attend(q, k, v, visible):
    """q [S,H,D]; k/v [T,Hkv,D]; visible [S,T] bool -> (out [S,H,D],
    probabilities [S,H,T])."""
    S, H, D = q.shape
    G = H // k.shape[1]
    kh = jnp.repeat(k, G, axis=1)  # head h reads kv head h // G
    vh = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("shd,thd->sht", q, kh, precision=HI) / np.sqrt(D)
    s = jnp.where(visible[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sht,thd->shd", p, vh, precision=HI), p


def finish_layer(w, dims: Dims, num: Numerics, x, att):
    """Output projection, residual, and the SwiGLU MLP with its residual."""
    x = x + num.dot(att.reshape(att.shape[0], -1), w["wo"])
    h = rms_norm(x, w["ln2"], dims.norm_eps)
    return x + num.dot(jax.nn.silu(num.dot(h, w["gate"])) * num.dot(h, w["up"]), w["down"])


def embed(params, num: Numerics, tokens):
    table = params["embed"]
    rows = table[tokens].astype(jnp.float32)
    if num.mode == "fp8":  # the table quantized as a whole, then looked up
        amax = jnp.max(jnp.abs(table)).astype(jnp.float32)
        scale = jnp.maximum(amax, 1e-12) / F8_MAX
        rows = (rows / scale).astype(F8).astype(jnp.float32) * scale
    return rows


def head_weight(params, dims: Dims, num: Numerics):
    w = params["embed"].T if dims.tie_embeddings else params["head"]
    return num.weight(w)


def final_hidden(params, dims: Dims, x):
    return rms_norm(x, params["final_norm"].astype(jnp.float32), dims.norm_eps)


@partial(jax.jit, static_argnames=("dims", "num"))
def _layer(w, x, pos, x_slot, k_extra, v_extra, extra_slot, *, dims, num):
    """One layer over a token sequence that may also see extra key/value
    slots. A token at slot s sees every token and extra slot whose slot
    number is at most s."""
    q, k, v = qkv(w, dims, num, x, pos)
    keys = jnp.concatenate([k, k_extra], axis=0)
    vals = jnp.concatenate([v, v_extra], axis=0)
    kslot = jnp.concatenate([x_slot, extra_slot], axis=0)
    out, _ = attend(q, keys, vals, kslot[None, :] <= x_slot[:, None])
    return finish_layer(w, dims, num, x, out), k, v


@partial(jax.jit, static_argnames=("dims", "num"))
def _logits(params, x, *, dims, num):
    return num.dot(final_hidden(params, dims, x), head_weight(params, dims, num))


def _bucket(n: int, size: int) -> int:
    return max(size, -(-n // size) * size)


def sequence_forward(params, dims: Dims, num: Numerics, tokens, pos, slots,
                     extras=None):
    """Full forward of one token sequence, layer by layer.

    tokens/pos/slots: [S] int arrays. ``extras``: optional
    ``(per_layer_kv, extra_slots)``: per layer (k [E,Hkv,D], v [E,Hkv,D])
    that the tokens also attend to, at the given slot numbers. Returns
    (final hidden x [S, d] before the final norm, per-layer (k, v) of the
    tokens).

    Sequences are padded at the end to a few fixed lengths so that one
    compiled layer serves many requests; a padded token comes after every
    real one, so no real token sees it."""
    S = len(tokens)
    Sp = _bucket(S, 256)
    pad = Sp - S
    tokens = np.concatenate([np.asarray(tokens), np.zeros(pad, np.int64)])
    pos = np.concatenate([np.asarray(pos), np.zeros(pad, np.int64)])
    slots = np.concatenate([np.asarray(slots), np.iinfo(np.int32).max - np.arange(pad)[::-1]])
    x = embed(params, num, jnp.asarray(tokens))
    pos, slots = jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32)
    Hkv, D = dims.n_kv_heads, dims.d_head
    E = 0 if extras is None else len(extras[1])
    Ep = _bucket(E, 128)
    # padded extra slots are never visible: their slot number is above all
    es = np.full(Ep, np.iinfo(np.int32).max, np.int64)
    if E:
        es[:E] = extras[1]
    es = jnp.asarray(es, jnp.int32)
    kv = []
    for l in range(dims.n_layers):
        w = layer_weights(params, l, num)
        ke = jnp.zeros((Ep, Hkv, D), jnp.float32)
        ve = ke
        if E:
            ke = ke.at[:E].set(extras[0][l][0])
            ve = ve.at[:E].set(extras[0][l][1])
        x, k, v = _layer(w, x, pos, slots, ke, ve, es, dims=dims, num=num)
        kv.append((k[:S], v[:S]))
    return x[:S], kv


def logits_of(params, dims: Dims, num: Numerics, x, block: int = 256):
    """Logits [S, V] f32 of final hidden states, in blocks of rows."""
    S = x.shape[0]
    x = jnp.concatenate([x, jnp.zeros((_bucket(S, block) - S, x.shape[1]), x.dtype)])
    outs = [_logits(params, x[i:i + block], dims=dims, num=num)
            for i in range(0, x.shape[0], block)]
    return jnp.concatenate(outs, axis=0)[:S]


def river_logits(params, dims: Dims, num: Numerics, prompt_ids, inputs, merges):
    """Logits of a river's decode inputs, with side thoughts injected.

    ``prompt_ids``: the prompt's token ids (P of them, positions 0..P-1).
    ``inputs``: the decode inputs, in order; input j sits at position P+j.
    ``merges``: list of (k, thought_ids): after the first k decode inputs a
    thought of len(thought_ids) tokens was encoded on its own, causally, at
    positions P+k, P+k+1, ... and its keys and values appended to the river
    as extra slots that every later input sees.

    Returns (logits [len(inputs), V] of the decode inputs, per-layer prompt
    keys/values [(k [P,Hkv,D], v)] for side-agent spawns)."""
    P, n = len(prompt_ids), len(inputs)
    # thoughts first: each is an independent causal forward of its own
    per_layer = [[] for _ in range(dims.n_layers)]
    extra_slots = []
    merges = sorted(merges, key=lambda m: m[0])  # stable: same-k merges keep order
    n_before = 0  # extra slots already placed before the current merge
    for k, th in merges:
        T = len(th)
        _, kv = sequence_forward(params, dims, num, np.asarray(th), P + k + np.arange(T),
                                 np.arange(T))
        for l in range(dims.n_layers):
            per_layer[l].append(kv[l])
        # slot numbers: the prompt, then k decode inputs, then earlier thoughts
        extra_slots.extend(P + k + n_before + i for i in range(T))
        n_before += T
    tokens = np.concatenate([np.asarray(prompt_ids), np.asarray(inputs, np.int64)])
    pos = np.arange(P + n)
    # slot of each token: its own index plus the thought slots placed before it
    slots = np.arange(P + n).copy()
    n_extra = 0
    for k, th in merges:
        slots[P + k:] += len(th)
        n_extra += len(th)
    extras = None
    if merges:
        kv_cat = [
            (jnp.concatenate([a for a, _ in per_layer[l]], axis=0),
             jnp.concatenate([b for _, b in per_layer[l]], axis=0))
            for l in range(dims.n_layers)
        ]
        extras = (kv_cat, np.asarray(extra_slots))
    x, kv = sequence_forward(params, dims, num, tokens, pos, slots, extras)
    logits = logits_of(params, dims, num, x[P:])
    prompt_kv = [(k[:P], v[:P]) for k, v in kv]
    return logits, prompt_kv
