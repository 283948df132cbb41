"""Unified model: one forward/prefill/decode covering all assigned archs.

The layer stack is executed as a sequence of *segments*: each segment is a
``lax.scan`` over a homogeneous slice of stacked per-layer params, optionally
followed by a shared-attention invocation (zamba2 hybrid). This keeps HLO
size independent of depth (80-layer models on 512 devices) while allowing
heterogeneous patterns without cond-in-scan.

Cache layout mirrors the segments: per-group stacked cache pytrees (leading
layer axis) consumed as scan xs/ys, plus per-invocation shared-attn caches.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import synapse as synapse_lib
from repro.models import attention, cache as cache_lib, mamba2, mla, moe, rwkv6
from repro.models.config import LayerGroup, ModelConfig
from repro.models.layers import dense_init, embed_init, rms_norm, rms_norm_init, swiglu, swiglu_init


# ---------------------------------------------------------------------------
# cache configuration (runtime, not architecture)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CacheSpec:
    kind: str = "full"            # full | synapse
    capacity: int = 4096          # full-cache slots (>= prompt + decode budget)
    n_landmarks: int = 64         # synapse: K
    window: int = 128             # synapse: W
    n_inject: int = 8             # synapse: J (referential-injection slots)
    policy: synapse_lib.SynapsePolicy = field(default_factory=synapse_lib.SynapsePolicy)


@dataclass
class ModelCaches:
    """Decode state for the whole stack."""

    groups: tuple          # per layer-group stacked cache pytree
    shared: Any            # zamba2: stacked per-invocation attn caches (or None)


jax.tree_util.register_dataclass(ModelCaches, data_fields=["groups", "shared"], meta_fields=[])


# ---------------------------------------------------------------------------
# segment plan
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Segment:
    group: int        # index into layer groups / params["groups"]
    start: int        # start layer within the group's stacked params
    count: int
    shared_after: int  # shared-attn invocation index after this segment, or -1


def build_segments(cfg: ModelConfig) -> list[Segment]:
    segs: list[Segment] = []
    groups = cfg.layer_groups()
    if cfg.shared_attn_every > 0:
        assert len(groups) == 1
        every, total = cfg.shared_attn_every, groups[0].count
        start = inv = 0
        while start < total:
            count = min(every, total - start)
            has_inv = (start + count) % every == 0 and (start + count) <= total and inv < cfg.n_shared_attn_invocations
            segs.append(Segment(0, start, count, inv if has_inv else -1))
            if has_inv:
                inv += 1
            start += count
        return segs
    return [Segment(g, 0, grp.count, -1) for g, grp in enumerate(groups)]


# ---------------------------------------------------------------------------
# per-layer block init / apply
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ModelConfig, grp: LayerGroup, dtype):
    ks = jax.random.split(key, 4)
    if grp.kind == "attn":
        p = {"ln1": rms_norm_init(cfg.d_model, dtype), "ln2": rms_norm_init(cfg.d_model, dtype)}
        if cfg.attn_kind == "mla":
            p["attn"] = mla.mla_init(ks[0], cfg, dtype)
        else:
            p["attn"] = attention.attn_init(ks[0], cfg, dtype)
        if grp.mlp == "moe":
            p["mlp"] = moe.moe_init(ks[1], cfg, dtype)
        else:
            # dense MLP; inside a MoE model (first_k_dense) it uses dense_d_ff
            dff = cfg.d_ff if not cfg.is_moe else (cfg.dense_d_ff or cfg.d_ff * cfg.experts_per_token)
            p["mlp"] = swiglu_init(ks[1], cfg.d_model, dff, dtype)
        return p
    if grp.kind == "mamba2":
        return {"ln": rms_norm_init(cfg.d_model, dtype), "mixer": mamba2.mamba2_init(ks[0], cfg, dtype)}
    if grp.kind == "rwkv6":
        return {
            "ln1": rms_norm_init(cfg.d_model, dtype),
            "tmix": rwkv6.rwkv6_tmix_init(ks[0], cfg, dtype),
            "ln2": rms_norm_init(cfg.d_model, dtype),
            "cmix": rwkv6.rwkv6_cmix_init(ks[1], cfg, dtype),
        }
    raise ValueError(grp.kind)


def _shared_attn_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rms_norm_init(cfg.d_model, dtype),
        "attn": attention.attn_init(k1, cfg, dtype, n_lora=cfg.n_shared_attn_invocations),
        "ln2": rms_norm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    groups = cfg.layer_groups()
    params: dict = {}
    if cfg.embed_inputs or not cfg.is_encoder_only:
        params["embed"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)
    stacked = []
    for g, grp in enumerate(groups):
        layer_keys = jax.random.split(keys[1 + g % 4], grp.count)
        stacked.append(jax.vmap(lambda k: _block_init(k, cfg, grp, dtype))(layer_keys))
    params["groups"] = stacked
    if cfg.shared_attn_every > 0:
        params["shared_attn"] = _shared_attn_init(keys[5], cfg, dtype)
    params["final_norm"] = rms_norm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[6], cfg.d_model, cfg.vocab_size, dtype)
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of params — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# full-sequence forward (training / prefill trunk)
# ---------------------------------------------------------------------------

# Optional activation PartitionSpec (batch axes), set by launch/ entry points
# before tracing under a mesh. GSPMD propagates well from these anchors.
_ACT_SPEC = None


def set_activation_sharding(spec):
    """spec: PartitionSpec for [B, S, d] activations (or None to disable)."""
    global _ACT_SPEC
    _ACT_SPEC = spec


def _constrain(x):
    if _ACT_SPEC is None:
        return x
    import jax.sharding as jsh
    spec = _ACT_SPEC
    if x.ndim == 2:  # [B, d] decode stream
        spec = jsh.PartitionSpec(spec[0])
    return jax.lax.with_sharding_constraint(x, spec)


def _radd(x, y):
    """Residual add keeping the stream dtype (params may be fp32)."""
    return x + y.astype(x.dtype)


def cast_params(params, cfg: ModelConfig):
    """Cast float params to compute dtype at entry (fp32 masters stay with
    the optimizer). Keeps matmul FLOPs in bf16 on TPU.

    Already-cast trees (the engine pre-casts once and calls decode_step
    twice per virtual tick of the scanned macro window) short-circuit at
    trace time — no per-leaf astype graph building inside the scan body."""
    compute = jnp.dtype(cfg.compute_dtype)
    if all(
        not jnp.issubdtype(a.dtype, jnp.floating) or a.dtype == compute
        for a in jax.tree.leaves(params)
    ):
        return params
    return jax.tree.map(
        lambda a: a.astype(compute) if jnp.issubdtype(a.dtype, jnp.floating) else a, params
    )

def _attn_block_fwd(p, cfg: ModelConfig, grp_mlp: str, x, positions, *, lora_idx=None, chunk=1024):
    """Returns (x_out, aux, kv) — kv is (k_rot, v) or (ckv, krope) for MLA."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        y, kv = mla.mla_forward(p["attn"], cfg, h, positions, chunk=chunk)
    else:
        y, kv = attention.attention_forward(p["attn"], cfg, h, positions, lora_idx=lora_idx, chunk=chunk)
    x = _radd(x, y)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if grp_mlp == "moe":
        y, aux = moe.moe_forward(p["mlp"], cfg, h)
    else:
        y, aux = swiglu(p["mlp"], h), {"lb_loss": jnp.zeros((), jnp.float32), "drop_frac": jnp.zeros((), jnp.float32)}
    return _radd(x, y), aux, kv


def _zero_aux():
    return {"lb_loss": jnp.zeros((), jnp.float32), "drop_frac": jnp.zeros((), jnp.float32)}


def _block_fwd(p, cfg: ModelConfig, grp: LayerGroup, x, positions, chunk):
    if grp.kind == "attn":
        out, aux, _ = _attn_block_fwd(p, cfg, grp.mlp, x, positions, chunk=chunk)
        return out, aux
    if grp.kind == "mamba2":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        return _radd(x, mamba2.mamba2_forward(p["mixer"], cfg, h)), _zero_aux()
    if grp.kind == "rwkv6":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, _ = rwkv6.rwkv6_tmix_forward(p["tmix"], cfg, h)
        x = _radd(x, y)
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = rwkv6.rwkv6_cmix_forward(p["cmix"], cfg, h)
        return _radd(x, y), _zero_aux()
    raise ValueError(grp.kind)


def _shared_attn_fwd(p, cfg: ModelConfig, x, positions, lora_idx, chunk):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    y, kv = attention.attention_forward(p["attn"], cfg, h, positions, lora_idx=lora_idx, chunk=chunk)
    x = _radd(x, y)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return _radd(x, swiglu(p["mlp"], h)), kv


def _scan_stack(body, carry, xs, count: int, use_scan: bool):
    """lax.scan or python-unrolled equivalent (roofline probes unroll so
    cost_analysis sees every layer instead of one while body)."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(count):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys else None
    return carry, stacked


def _slice_group(params_g, start: int, count: int):
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + count, axis=0), params_g)


def forward(params, cfg: ModelConfig, inputs: dict, *, chunk: int = 1024):
    """Training/eval forward.

    inputs: {"tokens": [B,S] int32} or {"embeds": [B,S,d]}, optional
    "positions" ([B,S] or [B,3,S] for mrope).
    Returns (logits [B,S,V], aux).
    """
    params = cast_params(params, cfg)
    if "embeds" in inputs:
        x = inputs["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        B, S = x.shape[:2]
    else:
        tokens = inputs["tokens"]
        B, S = tokens.shape
        x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if "positions" in inputs:
        positions = inputs["positions"]
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        positions = jnp.broadcast_to(pos[:, None, :], (B, 3, S)) if cfg.rope_kind == "mrope" else pos

    groups = cfg.layer_groups()
    aux_total = _zero_aux()

    def make_body(grp):
        def body(carry, p_layer):
            out, aux = _block_fwd(p_layer, cfg, grp, _constrain(carry), positions, chunk)
            return _constrain(out), aux
        if not cfg.remat:
            return body
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            return jax.checkpoint(body, policy=policy)
        return jax.checkpoint(body)

    for seg in build_segments(cfg):
        grp = groups[seg.group]
        p_seg = _slice_group(params["groups"][seg.group], seg.start, seg.count)
        x, auxs = _scan_stack(make_body(grp), x, p_seg, seg.count, cfg.scan_layers)
        aux_total = jax.tree.map(lambda t, a: t + a.sum(), aux_total, auxs)
        if seg.shared_after >= 0:
            x, _ = _shared_attn_fwd(params["shared_attn"], cfg, x, positions, seg.shared_after, chunk)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    aux_total["hidden_last"] = x[:, -1, :]
    return logits, aux_total


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _stack(tree, n: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), tree)


def init_caches(cfg: ModelConfig, batch: int, spec: CacheSpec) -> ModelCaches:
    dtype = jnp.dtype(cfg.compute_dtype)
    groups = cfg.layer_groups()
    out = []
    for grp in groups:
        if grp.kind == "attn":
            if cfg.attn_kind == "mla":
                c = cache_lib.init_mla_cache(cfg, batch, spec.capacity, dtype)
            elif spec.kind == "synapse":
                c = cache_lib.init_synapse_cache(cfg, batch, spec.n_landmarks, spec.window, spec.n_inject, dtype)
            else:
                c = cache_lib.init_full_cache(cfg, batch, spec.capacity, dtype)
        elif grp.kind == "mamba2":
            c = cache_lib.init_mamba2_state(cfg, batch, dtype)
        elif grp.kind == "rwkv6":
            c = cache_lib.init_rwkv6_state(cfg, batch, dtype)
        out.append(_stack(c, grp.count))
    shared = None
    if cfg.shared_attn_every > 0:
        if spec.kind == "synapse":
            c = cache_lib.init_synapse_cache(cfg, batch, spec.n_landmarks, spec.window, spec.n_inject, dtype)
        else:
            c = cache_lib.init_full_cache(cfg, batch, spec.capacity, dtype)
        shared = _stack(c, cfg.n_shared_attn_invocations)
    return ModelCaches(groups=tuple(out), shared=shared)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------
def _fill_full_cache(cache: cache_lib.FullCache, k, v, positions, length, score=None):
    """Write [B,S,...] prefix into a FullCache."""
    new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), 0, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), 0, axis=1)
    new_pos = jax.lax.dynamic_update_slice_in_dim(cache.pos, positions, 0, axis=1)
    new_score = cache.score
    if score is not None:
        new_score = jax.lax.dynamic_update_slice_in_dim(cache.score, score, 0, axis=1)
    return cache_lib.FullCache(new_k, new_v, new_pos, new_score, length)


def prefill(params, cfg: ModelConfig, inputs: dict, caches: ModelCaches, *, spec: CacheSpec, chunk: int = 1024):
    """Run the prompt through the stack, filling caches.

    For spec.kind == "synapse", each attention layer's full prompt KV is
    compressed on the fly via hybrid landmark selection (never materializing
    a persistent full cache) — the last-token query is the paper's Q_t.
    Returns (logits_last [B,V], hidden_last [B,d], new_caches).
    """
    params = cast_params(params, cfg)
    assert not cfg.is_encoder_only, "encoder-only archs have no decode/prefill"
    if "embeds" in inputs:
        x = inputs["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        B, S = x.shape[:2]
    else:
        tokens = inputs["tokens"]
        B, S = tokens.shape
        x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if "positions" in inputs:
        positions = inputs["positions"]
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        positions = jnp.broadcast_to(pos[:, None, :], (B, 3, S)) if cfg.rope_kind == "mrope" else pos
    pos_scalar = positions[:, 0, :] if cfg.rope_kind == "mrope" else positions
    lengths = jnp.full((B,), S, jnp.int32)

    groups = cfg.layer_groups()

    def attn_body(grp):
        def body(carry, xs):
            p_layer, cache = xs
            carry = _constrain(carry)
            out, _, kv = _attn_block_fwd(p_layer, cfg, grp.mlp, carry, positions, chunk=chunk)
            if cfg.attn_kind == "mla":
                ckv, krope = kv
                new_cache = cache_lib.MLACache(
                    jax.lax.dynamic_update_slice_in_dim(cache.ckv, ckv.astype(cache.ckv.dtype), 0, 1),
                    jax.lax.dynamic_update_slice_in_dim(cache.krope, krope.astype(cache.krope.dtype), 0, 1),
                    cache.score,
                    lengths,
                )
            elif spec.kind == "synapse":
                k_rot, v = kv
                full = cache_lib.FullCache(
                    k_rot.astype(cache.lm_k.dtype), v.astype(cache.lm_v.dtype),
                    pos_scalar, jnp.zeros(pos_scalar.shape, jnp.float32), lengths,
                )
                # paper's Q_t: last-token query of this layer
                q_last = _last_query(p_layer, cfg, carry, positions)
                new_cache = synapse_lib.compress(
                    cfg, full, q_last, cache.n_landmarks, cache.window, cache.n_inject, spec.policy
                )
            else:
                k_rot, v = kv
                q_last = _last_query(p_layer, cfg, carry, positions)
                dens = synapse_lib.attention_density(
                    q_last, k_rot.astype(cache.k.dtype),
                    jnp.ones(k_rot.shape[:2], bool),
                )
                new_cache = _fill_full_cache(cache, k_rot, v, pos_scalar, lengths, score=dens)
            return out, new_cache
        return body

    def ssm_body(grp):
        def body(carry, xs):
            p_layer, _ = xs  # prior state ignored: prefill starts fresh
            carry = _constrain(carry)
            if grp.kind == "mamba2":
                out, new_cache = _mamba2_fwd_state(p_layer, cfg, carry)
            else:
                out, new_cache = _rwkv6_fwd_state(p_layer, cfg, carry)
            return out, new_cache
        return body

    x_cur = x
    seg_caches = list(caches.groups)
    shared_cache = caches.shared
    for seg in build_segments(cfg):
        grp = groups[seg.group]
        p_seg = _slice_group(params["groups"][seg.group], seg.start, seg.count)
        c_seg = _slice_group(seg_caches[seg.group], seg.start, seg.count)
        body = attn_body(grp) if grp.kind == "attn" else ssm_body(grp)
        x_cur, new_c = _scan_stack(body, x_cur, (p_seg, c_seg), seg.count, cfg.scan_layers)
        # write back the updated slice
        seg_caches[seg.group] = jax.tree.map(
            lambda full, part: jax.lax.dynamic_update_slice_in_dim(full, part, seg.start, axis=0),
            seg_caches[seg.group],
            new_c,
        )
        if seg.shared_after >= 0:
            x_before = x_cur
            x_cur, kv = _shared_attn_fwd(params["shared_attn"], cfg, x_cur, positions, seg.shared_after, chunk)
            k_rot, v = kv
            inv_cache = jax.tree.map(lambda a: a[seg.shared_after], shared_cache)
            if spec.kind == "synapse":
                full = cache_lib.FullCache(
                    k_rot.astype(inv_cache.lm_k.dtype), v.astype(inv_cache.lm_v.dtype),
                    pos_scalar, jnp.zeros(pos_scalar.shape, jnp.float32), lengths,
                )
                q_last = _last_query(params["shared_attn"], cfg, x_before, positions, lora_idx=seg.shared_after)
                new_inv = synapse_lib.compress(cfg, full, q_last, inv_cache.n_landmarks, inv_cache.window, inv_cache.n_inject, spec.policy)
            else:
                new_inv = _fill_full_cache(inv_cache, k_rot, v, pos_scalar, lengths)
            shared_cache = jax.tree.map(
                lambda full, part: full.at[seg.shared_after].set(part), shared_cache, new_inv
            )

    x_last = rms_norm(x_cur[:, -1, :], params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x_last @ head.astype(x_last.dtype)).astype(jnp.float32)
    return logits, x_last, ModelCaches(groups=tuple(seg_caches), shared=shared_cache)


def prefill_lane(params, cfg: ModelConfig, inputs: dict, caches: ModelCaches, lane, *, spec: CacheSpec, chunk: int = 1024):
    """Prefill ONE lane of a batched cache, in place.

    Runs the prompt through a fresh single-lane cache (allocated inside the
    trace — fused away by XLA) and scatters the result into ``caches`` at
    batch index ``lane`` (a traced scalar: one compilation serves all lanes).
    Jit this with the batched caches donated and admission costs one dispatch
    and zero extra cache copies — the engine's continuous-batching admit path.
    Returns (logits_last [1,V], hidden_last [1,d], updated caches).
    """
    lane_caches = init_caches(cfg, 1, spec)
    logits, hidden, lane_caches = prefill(params, cfg, inputs, lane_caches, spec=spec, chunk=chunk)
    new_caches = jax.tree.map(
        lambda full, part: jax.lax.dynamic_update_slice_in_dim(
            full, part.astype(full.dtype), lane, axis=1
        ),
        caches,
        lane_caches,
    )
    return logits, hidden, new_caches


def _last_query(block_params, cfg: ModelConfig, x_in, positions, lora_idx=None):
    """Recompute the last position's rotated query [B,H,D] (cheap: one token).

    block_params: a block dict with "ln1" + "attn"; x_in: the block's input.
    """
    h = rms_norm(x_in[:, -1:, :], block_params["ln1"], cfg.norm_eps)
    q, _, _ = attention._project_qkv(block_params["attn"], cfg, h, lora_idx)
    if cfg.rope_kind == "mrope":
        q = attention._rotate(cfg, q, positions[:, :, -1:])
    else:
        q = attention._rotate(cfg, q, positions[:, -1:])
    return q[:, 0]


def _mamba2_fwd_state(p_layer, cfg: ModelConfig, x):
    """Mamba2 layer forward that also returns the terminal decode state."""
    h = rms_norm(x, p_layer["ln"], cfg.norm_eps)
    y, state = mamba2.mamba2_forward(p_layer["mixer"], cfg, h, return_state=True)
    return _radd(x, y), state


def _rwkv6_fwd_state(p_layer, cfg: ModelConfig, x):
    h = rms_norm(x, p_layer["ln1"], cfg.norm_eps)
    y, (shift_tm, wkv) = rwkv6.rwkv6_tmix_forward(p_layer["tmix"], cfg, h)
    x = _radd(x, y)
    h2 = rms_norm(x, p_layer["ln2"], cfg.norm_eps)
    y2, shift_cm = rwkv6.rwkv6_cmix_forward(p_layer["cmix"], cfg, h2)
    state = cache_lib.RWKV6State(shift_tm=shift_tm, shift_cm=shift_cm, wkv=wkv)
    return _radd(x, y2), state


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_step(params, cfg: ModelConfig, inputs: dict, caches: ModelCaches, *, spec: CacheSpec):
    """One-token decode. inputs: {"tokens": [B] int32} or {"embeds": [B,d]},
    plus "positions": [B] (or [B,3]). Returns (logits [B,V], hidden [B,d], caches').
    """
    params = cast_params(params, cfg)
    assert not cfg.is_encoder_only
    if "embeds" in inputs:
        x = inputs["embeds"][:, None, :].astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = params["embed"][inputs["tokens"]][:, None, :].astype(jnp.dtype(cfg.compute_dtype))
    B = x.shape[0]
    positions = inputs["positions"]

    groups = cfg.layer_groups()
    # a synapse group's caches ride the layer scan's carry, each layer
    # reading and writing its own slice of the stack in place (the attend
    # kernel reads the stacked K/V at the layer's index); other caches
    # pass through the scan's inputs and outputs, one layer's slice each
    stacked = spec.kind == "synapse" and cfg.attn_kind != "mla"

    def attn_block(p_layer, x_c, attend, grp):
        h = rms_norm(x_c, p_layer["ln1"], cfg.norm_eps)
        y, new_cache = attend(h)
        x_c = _radd(x_c, y)
        h = rms_norm(x_c, p_layer["ln2"], cfg.norm_eps)
        if grp.mlp == "moe":
            y, _ = moe.moe_forward(p_layer["mlp"], cfg, h)
        else:
            y = swiglu(p_layer["mlp"], h)
        return _radd(x_c, y), new_cache

    def synapse_body(grp):
        def body(carry, xs):
            x_c, stack = carry
            p_layer, layer = xs
            attend = lambda h: synapse_lib.synapse_decode(
                p_layer["attn"], cfg, h, stack, positions, spec.policy, layer=layer)[:2]
            return attn_block(p_layer, x_c, attend, grp), None
        return body

    def block_body(grp):
        def body(carry, xs):
            p_layer, cache = xs
            x_c = carry
            if grp.kind == "attn":
                if cfg.attn_kind == "mla":
                    attend = lambda h: mla.mla_decode(p_layer["attn"], cfg, h, cache, positions)[:2]
                else:
                    attend = lambda h: attention.attention_decode_full(p_layer["attn"], cfg, h, cache, positions)[:2]
                return attn_block(p_layer, x_c, attend, grp)
            if grp.kind == "mamba2":
                h = rms_norm(x_c, p_layer["ln"], cfg.norm_eps)
                y, new_cache = mamba2.mamba2_decode(p_layer["mixer"], cfg, h, cache)
                return _radd(x_c, y), new_cache
            # rwkv6
            h = rms_norm(x_c, p_layer["ln1"], cfg.norm_eps)
            y, new_cache = rwkv6.rwkv6_tmix_decode(p_layer["tmix"], cfg, h, cache)
            x_c = _radd(x_c, y)
            h = rms_norm(x_c, p_layer["ln2"], cfg.norm_eps)
            y, new_cache = rwkv6.rwkv6_cmix_decode(p_layer["cmix"], cfg, h, new_cache)
            return _radd(x_c, y), new_cache
        return body

    seg_caches = list(caches.groups)
    shared_cache = caches.shared
    x_cur = x
    for seg in build_segments(cfg):
        grp = groups[seg.group]
        p_seg = _slice_group(params["groups"][seg.group], seg.start, seg.count)
        if grp.kind == "attn" and stacked:
            layers = seg.start + jnp.arange(seg.count, dtype=jnp.int32)
            (x_cur, seg_caches[seg.group]), _ = _scan_stack(
                synapse_body(grp), (x_cur, seg_caches[seg.group]), (p_seg, layers),
                seg.count, cfg.scan_layers)
        else:
            c_seg = _slice_group(seg_caches[seg.group], seg.start, seg.count)
            x_cur, new_c = _scan_stack(block_body(grp), x_cur, (p_seg, c_seg), seg.count, cfg.scan_layers)
            seg_caches[seg.group] = jax.tree.map(
                lambda full, part: jax.lax.dynamic_update_slice_in_dim(full, part, seg.start, axis=0),
                seg_caches[seg.group],
                new_c,
            )
        if seg.shared_after >= 0:
            h = rms_norm(x_cur, params["shared_attn"]["ln1"], cfg.norm_eps)
            if spec.kind == "synapse":
                y, shared_cache, _ = synapse_lib.synapse_decode(
                    params["shared_attn"]["attn"], cfg, h, shared_cache, positions, spec.policy,
                    layer=seg.shared_after)
            else:
                inv_cache = jax.tree.map(lambda a: a[seg.shared_after], shared_cache)
                y, new_inv, _ = attention.attention_decode_full(params["shared_attn"]["attn"], cfg, h, inv_cache, positions)
                shared_cache = jax.tree.map(lambda full, part: full.at[seg.shared_after].set(part), shared_cache, new_inv)
            x_cur = _radd(x_cur, y)
            h = rms_norm(x_cur, params["shared_attn"]["ln2"], cfg.norm_eps)
            x_cur = _radd(x_cur, swiglu(params["shared_attn"]["mlp"], h))

    hidden = rms_norm(x_cur[:, 0, :], params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)
    return logits, hidden, ModelCaches(groups=tuple(seg_caches), shared=shared_cache)
