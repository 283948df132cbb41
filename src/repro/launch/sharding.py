"""Sharding rules: PartitionSpec trees for params, optimizer state, batches
and caches, with divisibility-aware fallback.

Baseline scheme (hillclimbed in EXPERIMENTS.md §Perf):
  * FSDP over the ("pod","data") axes on the input dim of every matrix,
  * tensor parallel over "model" on the heads/ffn/expert dim,
  * experts sharded over "model" (expert parallelism),
  * batch over ("pod","data"); full-KV capacity dim over "model" when the
    kv-head count does not divide the model axis.
Any axis that does not divide a dimension is dropped (replicated) — the spec
builder never produces an invalid sharding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh: Mesh, dim: int, axes):
    """Return `axes` if it divides dim, trying progressively smaller subsets."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    for k in range(len(axes), 0, -1):
        cand = axes[-k:]  # prefer keeping the last (usually 'data'/'model')
        if dim % _axis_size(mesh, cand) == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def _spec(mesh: Mesh, shape, axes_per_dim) -> P:
    out = []
    for dim, ax in zip(shape, axes_per_dim):
        out.append(_fit(mesh, dim, ax))
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules (path- and shape-based)
# ---------------------------------------------------------------------------
_IN_OUT = {"wq", "wk", "wv", "gate", "up", "w_in", "wuq", "wuk", "wuv", "wdkv",
           "wdq", "head", "wr", "wg", "embed_proj"}
_OUT_IN = {"wo", "down", "w_out"}


def _param_rule(path_keys: list[str], shape, fsdp, tp):
    name = path_keys[-1]
    nd = len(shape)
    stacked = "groups" in path_keys  # leading layer-stack dim
    off = 1 if stacked and nd >= 2 else 0
    lead = [None] * off
    body = shape[off:]
    bnd = len(body)

    if name == "embed":
        return lead + [tp, None]
    if bnd == 0 or bnd == 1:
        return lead + [None] * bnd
    if name in ("experts_gate", "experts_up"):  # [E, dm, ff]
        return lead + [tp, fsdp, None]
    if name in ("experts_down",):               # [E, ff, dm]
        return lead + [tp, None, fsdp]
    if name == "router":
        return lead + [fsdp, None]
    if name == "lora_a":                        # [n_inv, dm, r]
        return lead + [None, fsdp, None]
    if name == "lora_b":                        # [n_inv, r, out]
        return lead + [None, None, tp]
    if name == "conv_w":                        # [W, channels]
        return lead + [None, tp]
    if name == "u":                             # [h, hs]
        return lead + [tp, None]
    if name in ("mu", "mix_a", "mix_b"):        # rwkv stacked small
        return lead + [None] * bnd
    if name in _OUT_IN and bnd == 2:
        return lead + [tp, fsdp]
    if bnd == 2:
        # default in->out matrices (_IN_OUT + decay_a/decay_b/cmix wk ...)
        return lead + [fsdp, tp]
    return lead + [None] * bnd


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
    return names


def param_specs(abstract_params, cfg: ModelConfig, mesh: Mesh, *, fsdp_on: bool = True):
    """PartitionSpec tree matching any params/opt-state pytree.

    fsdp_on=False: pure tensor-parallel weights (replicated over pod/data) —
    the serving-optimized mode (§Perf: kills per-step weight all-gathers).
    """
    fsdp = tuple(a for a in mesh.axis_names if a in ("pod", "data")) if fsdp_on else ()
    tp = "model"

    def one(path, leaf):
        names = _path_names(path)
        # disambiguate expert weights (experts/{gate,up,down})
        if len(names) >= 2 and names[-2] == "experts":
            names = names[:-1] + [f"experts_{names[-1]}"]
        axes = _param_rule(names, leaf.shape, fsdp, tp)
        return _spec(mesh, leaf.shape, axes)

    return jax.tree_util.tree_map_with_path(one, abstract_params)


def fit_spec(mesh: Mesh, shape, axes_per_dim) -> P:
    """Public divisibility-aware spec builder."""
    return _spec(mesh, shape, axes_per_dim)


def shardings_for(tree_of_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# lane-sharded engine state (ISSUE 6: the cortex macro tick under shard_map)
# ---------------------------------------------------------------------------
LANE_AXIS = "lane"


def tick_state_specs(state, mesh: Mesh, *, axis: str = LANE_AXIS):
    """PartitionSpec tree for the engine's :class:`TickState` on a lane mesh.

    Placement rule (the whole refactor in one function): every ``side_*``
    leaf shards its LANE dim over ``axis`` — dim 1 for the stacked
    ``side_caches`` ([L, S, ...]), dim 0 for everything else ([S] budgets,
    [S, R] token rings, [S, P] prompt buffers, [S, d] hidden, the
    LaneSampling arrays) — while main-stream state, the PRNG key, and the
    ring cursor replicate (every device runs the river redundantly; the
    paper's one-river/many-streams topology makes the river the cheap
    part). A lane count the axis does not divide replicates that leaf
    instead of producing an invalid sharding (same ``_fit`` contract as
    the param rules).
    """
    size = mesh.shape[axis]

    def one(path, leaf):
        names = _path_names(path)
        field = names[0] if names else ""
        if not field.startswith("side_"):
            return P()
        lane_dim = 1 if field == "side_caches" else 0
        ndim = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", ())
        axes = [None] * ndim
        if ndim > lane_dim and shape[lane_dim] % size == 0:
            axes[lane_dim] = axis
        return P(*axes)

    return jax.tree_util.tree_map_with_path(one, state)


def lane_cache_specs(caches, mesh: Mesh, *, axis: str = LANE_AXIS):
    """Stacked [L, B, ...] cache tree with the BATCH dim (dim 1) sharded
    over the lane axis — the BatchServer's lane placement (one KV lane per
    request, lanes spread across the mesh). Non-divisible lane counts
    replicate, like everywhere else."""
    size = mesh.shape[axis]

    def one(leaf):
        ndim = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", ())
        axes = [None] * ndim
        if ndim > 1 and shape[1] % size == 0:
            axes[1] = axis
        return P(*axes)

    return jax.tree.map(one, caches)


# ---------------------------------------------------------------------------
# lane gather/scatter (ISSUE 7: hibernate/wake one lane of a sharded state)
# ---------------------------------------------------------------------------
def lane_gather(tree, lane, *, axis: int = 1):
    """Slice ONE lane (keepdim) out of every leaf of a stacked cache tree.

    The demote half of hibernation: under jit with replicated
    ``out_shardings`` this is the gather that pulls a lane's leaves off a
    lane-sharded mesh (GSPMD inserts the collective); on one device it is
    a plain dynamic slice. `lane` may be traced.
    """
    def one(a):
        return jax.lax.dynamic_slice_in_dim(a, lane, 1, axis=axis)

    return jax.tree.map(one, tree)


def lane_scatter(tree, part, lane, *, axis: int = 1):
    """Write a one-lane slice (from :func:`lane_gather`) back into the full
    stacked tree at `lane` — the promote half of a wake. Casts each leaf to
    the destination dtype (snapshots are stored bitwise in the compute
    dtype, so this is a no-op cast in practice) and, under jit with the
    state's ``out_shardings``, re-shards onto the lane mesh."""
    def one(full, piece):
        return jax.lax.dynamic_update_slice_in_dim(
            full, piece.astype(full.dtype), lane, axis=axis
        )

    return jax.tree.map(one, tree, part)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------
def batch_specs(batch_abstract, cfg: ModelConfig, mesh: Mesh):
    """tokens/labels [B,S] and embeds [B,S,d] shard batch over (pod, data)."""
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    def one(path, leaf):
        axes = [dp] + [None] * (leaf.ndim - 1)
        return _spec(mesh, leaf.shape, axes)

    return jax.tree_util.tree_map_with_path(one, batch_abstract)


def cache_specs(caches_abstract, cfg: ModelConfig, mesh: Mesh, *, synapse_token_shard: bool = True):
    """Stacked caches [L, B, T, Hkv, D], synapse K/V [L, B, T, Hkv*D] (or
    state trees [L, B, ...]).

    Batch over (pod, data). For 4D+ cache leaves: try kv-heads over "model";
    if not divisible the _fit fallback replicates, and instead the token/
    capacity dim takes "model" (flash-decode style sharded KV).

    synapse_token_shard=False: landmark/window/inject buffers replicate their
    token dim (they are O(K+W+J) small; sharding it forces a per-step
    all-gather of every synapse buffer — §Perf hillclimb finding).
    """
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = "model"
    tp_size = mesh.shape[tp]

    def one(path, leaf):
        nd = leaf.ndim
        shape = leaf.shape
        names = _path_names(path)
        is_synapse_buf = any(
            str(n).startswith(("lm_", "win_", "inj_")) for n in names
        )
        if is_synapse_buf and nd == 4:
            # lane-dense K/V [L, B, T, Hkv*D]: whole kv heads over model when
            # they divide, else (token shard) the token dim, else replicate
            axes = [None, dp, None, None]
            if cfg.n_kv_heads % tp_size == 0:
                axes[3] = tp
            elif synapse_token_shard and shape[2] % tp_size == 0:
                axes[2] = tp
            return _spec(mesh, shape, axes)
        if is_synapse_buf and not synapse_token_shard:
            return _spec(mesh, shape, ([None, dp] + [None] * max(nd - 2, 0))[:nd])
        if nd <= 1:
            return P()
        if nd == 2:  # [L, B] lengths/counts
            return _spec(mesh, shape, [None, dp])
        if nd == 3:  # [L, B, T] pos/score  or [L, B, d] shift states
            return _spec(mesh, shape, [None, dp, None])
        if nd >= 4:
            # [L, B, T, Hkv, D] kv   | [L, B, nh, dh, ds] ssm | [L,B,H,hs,hs]
            head_dim_idx = 3 if nd == 5 else 2
            head = shape[head_dim_idx] if nd == 5 else shape[2]
            axes = [None, dp] + [None] * (nd - 2)
            if nd == 5 and shape[3] % tp_size == 0:
                axes[3] = tp            # kv heads over model
            elif nd == 5 and shape[2] % tp_size == 0:
                axes[2] = tp            # capacity over model (flash-decode)
            elif nd == 4 and shape[2] % tp_size == 0:
                axes[2] = tp            # latent capacity / ssm heads over model
            elif nd == 4 and shape[3] % tp_size == 0:
                axes[3] = tp            # channels over model (conv tails etc.)
            return _spec(mesh, shape, axes)
        return P()

    return jax.tree_util.tree_map_with_path(one, caches_abstract)
