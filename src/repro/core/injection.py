"""Referential Injection (paper §3.6).

A side agent's accepted thought is encoded by a forward pass (shared
weights — the Prism) and its per-layer K/V are appended to the main agent's
caches at *virtual* RoPE positions, so the main stream's token sequence and
positions are untouched: the model "remembers" the thought without reading
it. Static-shape adaptation (DESIGN.md §3): caches are pre-allocated; full
caches receive injected K/V at the write cursor, synapse caches in their
dedicated ``inj_*`` slots.

For attention-free layers (RWKV6 / Mamba2 state), injection is re-expressed
as a *state blend*: the thought is run forward and its terminal recurrent
state is mixed into the main state (beta-weighted). This is the closest
TPU/SSM-idiomatic equivalent — documented as an adaptation in DESIGN.md §4.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import gate as gate_lib
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models.config import ModelConfig


def encode_thought_kv(params, cfg: ModelConfig, thought_tokens, virtual_pos):
    """Run a forward pass over the thought and capture per-layer K/V.

    thought_tokens: [B, T] int32; virtual_pos: [B] — the virtual positional
    index assigned to the thought (paper: "auxiliary context").
    Returns the ModelCaches of a throwaway prefill with capacity == T, whose
    full caches hold exactly the rotated K/V of the thought, plus the
    terminal hidden state [B, d] (used by the Validation Gate).
    """
    B, T = thought_tokens.shape
    positions = virtual_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.rope_kind == "mrope":
        positions = jnp.broadcast_to(positions[:, None, :], (B, 3, T))
    spec = model_lib.CacheSpec(kind="full", capacity=T)
    caches = model_lib.init_caches(cfg, B, spec)
    logits, hidden, caches = model_lib.prefill(
        params, cfg, {"tokens": thought_tokens, "positions": positions}, caches, spec=spec
    )
    return caches, hidden


def _placement(parent, accept, cursor, width: int, last: int):
    """Where each row's block of ``width`` slots starts, and what it writes.

    parent [K] (lane of each row), accept [K], cursor [B] (each lane's next
    free slot). The j-th accepted row of a lane starts ``width * j`` after
    the lane's cursor, clamped to ``last`` exactly as a lone
    ``dynamic_update_slice`` clamps its start, so a batch lands where the
    same merges made one by one would. Where clamped blocks of a lane
    overlap, each slot takes its value from the last row to cover it, in
    every block that covers it: the blocks agree, so the order in which a
    scatter writes them does not matter. Returns (start [K], source [K,
    width]: the flat ``row * width + token`` each slot copies, count [B]:
    accepted rows per lane).
    """
    K, B = parent.shape[0], cursor.shape[0]
    same = (parent[:, None] == parent[None, :]) & accept[None, :]  # [k, j]
    before = jnp.sum(jnp.tril(same, -1), axis=1)                   # accepted rows earlier
    start = jnp.clip(cursor[parent] + width * before, 0, last)
    slot = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]  # [K, W]
    # starts grow with the row among a lane's accepted rows, so the last row
    # that starts at or before a slot is the last one to cover it
    covers = same[:, None, :] & (start[None, None, :] <= slot[:, :, None])  # [k, t, j]
    writer = jnp.max(jnp.where(covers, jnp.arange(K, dtype=jnp.int32), 0), axis=2)
    source = writer * width + slot - start[writer]
    count = jnp.zeros((B,), jnp.int32).at[parent].add(accept.astype(jnp.int32))
    return start, source, count


def _write_blocks(dst, src, lane, start, source):
    """dst [L, B, S, ...] <- one block of W slots per row of src [L, K, W,
    ...], row k's at (lane[k], start[k]), each slot's value taken from the
    flat row ``source[k, t]`` of src. ONE scatter into the (donated)
    destination; rows with a lane past the last are dropped."""
    L, K, W = src.shape[:3]
    rows = src.reshape((L, K * W) + src.shape[3:])[:, source.reshape(-1)]
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(0,) + tuple(range(2, dst.ndim)),
        inserted_window_dims=(1,),
        scatter_dims_to_operand_dims=(1, 2),
    )
    return jax.lax.scatter(
        dst, jnp.stack([lane, start], axis=-1), rows.reshape(src.shape).astype(dst.dtype),
        dims, mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
    )


# the per-slot leaves of the caches a thought is appended to
_FULL_ROWS = ("k", "v", "pos", "score")
_MLA_ROWS = ("ckv", "krope", "score")


def inject_append(main, thought, parent, accept, fields):
    """Append each accepted thought's rows to its parent lane of a stacked
    FullCache or MLACache group, at the lane's write cursor.

    main.<field>: [L, B, S, ...]; thought.<field>: [L, K, T, ...]; parent,
    accept: [K]. The injected slots get the thought's (virtual) positions;
    a lane's length grows by T per accepted thought (past the capacity at a
    full cache, whose last T slots then hold the lane's last accepted
    thought).
    """
    B, S = getattr(main, fields[0]).shape[1:3]
    T = getattr(thought, fields[0]).shape[2]
    start, source, count = _placement(parent, accept, main.length[0], T, S - T)
    lane = jnp.where(accept, parent, B)
    return dataclasses.replace(
        main,
        **{f: _write_blocks(getattr(main, f), getattr(thought, f), lane, start, source)
           for f in fields},
        length=main.length + T * count,
    )


def inject_synapse(main: cache_lib.SynapseCache, thought: cache_lib.FullCache, parent, accept):
    """Write each accepted thought's K/V into its parent lane's dedicated
    injection slots.

    Thought tokens beyond the J slots are dropped oldest-first (the slots are
    a ring). thought.*: [L, K, T, ...] from encode_thought_kv.
    """
    B, J = main.inj_k.shape[1], main.inj_k.shape[2]
    T = thought.k.shape[2]
    take = min(T, J)
    lane_rows = lambda a: a.reshape(a.shape[:3] + (-1,))  # the synapse's [.., Hkv*D] rows
    start, source, count = _placement(parent, accept, main.inj_count[0], take, J - take)
    lane = jnp.where(accept, parent, B)
    put = lambda d, s: _write_blocks(d, s, lane, start, source)
    return dataclasses.replace(
        main,
        inj_k=put(main.inj_k, lane_rows(thought.k[:, :, -take:])),
        inj_v=put(main.inj_v, lane_rows(thought.v[:, :, -take:])),
        inj_pos=put(main.inj_pos, thought.pos[:, :, -take:]),
        inj_count=jnp.minimum(main.inj_count + take * count, J),
    )


def blend_state(main_state, thought_state, parent, accept, beta: float = 0.3):
    """SSM adaptation: mix each accepted thought's terminal recurrent state
    into its parent lane's state. main: stacked [L, B, ...], thought:
    [L, K, ...] state pytrees. A blend depends on the ones before it, so the
    rows fold in batch order."""
    def mix(m, t):
        def one(k, m):
            cur = jax.lax.dynamic_index_in_dim(m, parent[k], axis=1, keepdims=False)
            blended = (1.0 - beta) * cur.astype(jnp.float32) + beta * t[:, k].astype(jnp.float32)
            new = jnp.where(accept[k], blended.astype(m.dtype), cur)
            return jax.lax.dynamic_update_index_in_dim(m, new, parent[k], axis=1)
        return jax.lax.fori_loop(0, parent.shape[0], one, m)
    return jax.tree.map(mix, main_state, thought_state)


def merge_thoughts(
    params,
    cfg: ModelConfig,
    main_caches,
    main_hidden,
    thought_tokens,
    parent,
    virtual_pos,
    valid,
    theta: float,
    beta: float = 0.3,
):
    """Encode + Validation Gate + Referential Injection of a batch of
    thoughts as ONE program, with the main caches donated.

    thought_tokens [K, T]; parent [K] (river lane of each thought);
    virtual_pos [K] (its parent's position); valid [K] (false on padding
    rows). The K thoughts are encoded in one prefill, each gated against its
    parent's hidden state, and every accepted one written with one scatter
    per cache leaf: the j-th accepted thought of a lane lands after the
    j - 1 before it, as the same merges made one by one in batch order
    would. Rejected and padding rows write nothing. The gate decision is a
    traced value, so every row is encoded -- a rejected merge is cheaper in
    dispatches, not in FLOPs.
    Returns (new_main_caches, accept [K] bool, score [K] f32).
    """
    thought_caches, t_hidden = encode_thought_kv(params, cfg, thought_tokens, virtual_pos)
    accept, score = gate_lib.validate(main_hidden[parent], t_hidden, theta)
    accept = accept & valid
    new_caches = inject(cfg, main_caches, thought_caches, accept, beta, parent=parent)
    return new_caches, accept, score


def merge_thought(
    params,
    cfg: ModelConfig,
    main_caches,
    main_hidden,
    thought_tokens,
    virtual_pos,
    lane_mask,
    theta: float,
    beta: float = 0.3,
):
    """One thought per main lane (row ``b`` merges into lane ``b``), for the
    lanes in ``lane_mask``: :func:`merge_thoughts` with ``parent`` the lanes
    themselves. thought_tokens [B, T]; virtual_pos, lane_mask [B].
    Returns (new_main_caches, accept [B] bool, score [B] f32).
    """
    parent = jnp.arange(thought_tokens.shape[0], dtype=jnp.int32)
    return merge_thoughts(params, cfg, main_caches, main_hidden, thought_tokens, parent,
                          virtual_pos, lane_mask, theta, beta)


def inject(cfg: ModelConfig, main_caches, thought_caches, accept, beta: float = 0.3, parent=None):
    """Dispatch injection across the whole stack: row ``k`` of the thought
    caches goes to lane ``parent[k]`` (lane ``k`` when ``parent`` is None).
    Both cache trees must come from the same cfg (same group structure)."""
    if parent is None:
        parent = jnp.arange(accept.shape[0], dtype=jnp.int32)
    new_groups = []
    for grp, m, t in zip(cfg.layer_groups(), main_caches.groups, thought_caches.groups):
        if grp.kind == "attn":
            if isinstance(m, cache_lib.MLACache):
                new_groups.append(inject_append(m, t, parent, accept, _MLA_ROWS))
            elif isinstance(m, cache_lib.SynapseCache):
                new_groups.append(inject_synapse(m, t, parent, accept))
            else:
                new_groups.append(inject_append(m, t, parent, accept, _FULL_ROWS))
        else:
            new_groups.append(blend_state(m, t, parent, accept, beta))
    shared = main_caches.shared
    if shared is not None and thought_caches.shared is not None:
        if isinstance(shared, cache_lib.SynapseCache):
            shared = inject_synapse(shared, thought_caches.shared, parent, accept)
        else:
            shared = inject_append(shared, thought_caches.shared, parent, accept, _FULL_ROWS)
    return model_lib.ModelCaches(groups=tuple(new_groups), shared=shared)
