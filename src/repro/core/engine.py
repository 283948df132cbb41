"""The Cortex Engine — fused-tick River & Stream topology (DESIGN.md §3).

The paper runs the main agent ("River") and side agents ("Streams") on
concurrent CUDA streams. The TPU-native equivalent is a *device-resident
scheduler hot loop*:

* ONE Prism (shared weights) — no per-agent copies (paper §3.2).
* ONE jitted dispatch per tick: ``fused_tick`` advances the main-lane batch
  (full caches), the side-lane batch (synapse caches), and the on-device
  samplers in a single donated call over a :class:`TickState` pytree. Cache
  buffers are donated, so a tick updates them in place instead of doubling
  peak memory.
* ZERO blocking host syncs per tick: sampled tokens are written into small
  on-device ring buffers and drained to the host only every ``sync_every``
  ticks (or lazily via :meth:`CortexEngine.drain` / ``memory_report``). The
  router scan, spawn, and merge logic run against the drained buffer at that
  boundary — host-side control at 1/sync_every the rate of device steps.
* MACRO TICKS: since nothing leaves the device between drains, the whole
  ``sync_every`` window is ONE dispatch — ``fused_tick(n_ticks=W)`` scans
  the per-tick body over the window inside a single jitted, donated program,
  emitting the token rings for the full window. :meth:`CortexEngine.run(n)`
  therefore issues ``ceil(n / sync_every)`` dispatches instead of ``n``.
* PIPELINED DRAINS (two-deep pipeline): ``run(n)`` fetches window *t*'s
  rings (the ONE blocking transfer per window), then — when a cheap
  conservative gate on the raw ring bytes proves window *t* cannot carry a
  router trigger or side completion — dispatches window *t+1* BEFORE doing
  window *t*'s host post-processing, so UTF-8 decoding, router regex scans,
  and bookkeeping overlap the device's execution of the next window. The
  gate never misses a control event (triggers need a ``[``/``]`` byte pair,
  side step budgets are host-computable), so spawn/merge timing — and hence
  every token — is bitwise identical to the serial dispatch→drain→dispatch
  order. A failed gate simply falls back to that serial order for one
  window; user-facing control calls (``submit``/``retire_side``/``drain``)
  flush the in-flight window before mutating state.
* ADAPTIVE WINDOWS: :class:`AdaptiveWindow` lengthens the scan window
  (``sync_every`` × {1, 2, 4, …} up to ``max_window`` — a small fixed set of
  lazily jit-cached scan lengths) while drains stay quiet, and snaps back to
  the base window on any trigger, spawn, merge, or admission. Windows are
  capped exactly at the serial-path boundary where an active side's step
  budget completes, and the router's :meth:`~repro.core.router.CortexRouter.
  plausible` hint (an unclosed ``[`` near the stream end) forces a short
  window — so control ops land on the same virtual tick as the pinned-window
  engine.
* Per-lane sampling: temperature/top-k/top-p live as stacked device arrays
  (:class:`repro.serving.sampler.LaneSampling`) inside ``TickState``, so a
  greedy river can coexist with exploratory streams in the same dispatch and
  admission-time changes never recompile the tick.
* Side-agent prompts are teacher-forced from an on-device prompt buffer
  (``side_prompt``/``side_plen``/``side_step``), so a freshly spawned stream
  needs no host involvement until its next drain.
* Spawn = hybrid landmark compression of the *parent lane only* (paper
  §3.3), via the fused ``kernels.ops.landmark_score`` sweep; merge =
  Validation Gate (§3.5) + Referential Injection (§3.6) fused into one
  dispatch per drain (``injection.merge_thoughts``: every side that
  finished in the drain, in chunks of at most ``MERGE_BATCH``).

Performance invariants (asserted by tests/test_fused_tick.py,
tests/test_macro_tick.py, and tests/test_adaptive_pipeline.py):
  * ``tick()`` issues exactly ONE jitted dispatch;
  * ``run(n)`` issues exactly ``ceil(n / sync_every)`` jitted dispatches
    with a pinned window, and **at most** that many with adaptation on;
  * no blocking host transfer happens outside ``drain()``/``_fetch_rings``;
  * each drain performs exactly one device→host pull of the token rings,
    and the overlapped post-processing region issues ZERO transfers (it
    runs under ``jax.transfer_guard("disallow")`` in the tests);
  * greedy lanes are bitwise identical between the pipelined/adaptive path,
    the serial macro path, and the single-tick path, across spawn/merge
    interleavings, and unaffected by other lanes' sampling params.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import injection
from repro.core import synapse as synapse_lib
from repro.core import synapse_sharded as sharded_lib
from repro.core.prism import Prism, tree_bytes
from repro.core.router import CortexRouter
from repro.data.tokenizer import ByteTokenizer
from repro.kernels.ops import ring_append
from repro.launch.sharding import lane_gather, lane_scatter
from repro.memory import (
    ACTIVE,
    HIBERNATED,
    LOST,
    REGISTERED,
    AgentRegistry,
    SnapshotLostError,
    SynapseStore,
)
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.serving.sampler import (
    LaneSampling, SamplingParams, cat_lanes, lane_params, lane_values,
    sample_lanes, static_flags,
)

# Host spans of the control plane. With a profiler running they land in its
# host plane, on the clock the device events are placed on; with none, a
# span costs about a microsecond. Keyword ids are ones the code already holds.
_span = jax.profiler.TraceAnnotation

# most thoughts one merge program takes: a drain's merges run in chunks of
# this many, each padded to a power of two ({1, 2, 4, ..., MERGE_BATCH})
MERGE_BATCH = 32


def _lane_slice(tree, lane: int):
    """Select batch lane (axis 1 — axis 0 is the stacked layer dim)."""
    return jax.tree.map(lambda a: a[:, lane], tree)


def spawn_caches(cfg: ModelConfig, main_caches: model_lib.ModelCaches, spec: model_lib.CacheSpec):
    """Compress a main agent's caches into fresh side-agent synapse caches.

    Attention groups: hybrid landmark compression with the density term from
    the fused ``kernels.ops.landmark_score`` sweep. The paper's Q_t (the
    parent's current query) is approximated by the most recent resident key,
    pooled over kv heads and broadcast to the query heads — q and k of the
    newest token are projections of the same hidden state, so its key is the
    best per-layer stand-in available post-hoc. The stacked layer axis is
    folded into the batch axis, so all layers compress in ONE kernel sweep
    instead of a vmap of L separate passes.

    SSM groups: the state is already O(1) — the side agent receives a copy
    (zero marginal context, noted in DESIGN.md). MLA: latent landmark
    selection is future work; sides receive the latent cache as-is.
    """
    groups = []
    for grp, c in zip(cfg.layer_groups(), main_caches.groups):
        if grp.kind == "attn" and isinstance(c, cache_lib.FullCache):
            groups.append(_compress_stacked(cfg, c, spec))
        else:
            groups.append(c)
    shared = main_caches.shared
    if shared is not None and isinstance(shared, cache_lib.FullCache):
        shared = _compress_stacked(cfg, shared, spec)
    return model_lib.ModelCaches(groups=tuple(groups), shared=shared)


def _compress_stacked(cfg: ModelConfig, c: cache_lib.FullCache, spec: model_lib.CacheSpec):
    """[L, B, ...] FullCache -> [L, B, ...] SynapseCache, layers folded into
    the batch axis (one fused scoring sweep for the whole stack)."""
    L, B = c.pos.shape[:2]
    flat = jax.tree.map(lambda a: a.reshape((L * B,) + a.shape[2:]), c)
    last = jnp.clip(flat.length - 1, 0, flat.k.shape[1] - 1)
    k_last = jnp.take_along_axis(flat.k, last[:, None, None, None], axis=1)[:, 0]  # [LB, Hkv, D]
    g = cfg.n_heads // k_last.shape[1]
    q_proxy = jnp.repeat(k_last, g, axis=1)  # [LB, H, D] — Q_t ~ K_t proxy
    comp = synapse_lib.compress(
        cfg, flat, q_proxy, spec.n_landmarks, spec.window, spec.n_inject, spec.policy
    )
    return jax.tree.map(lambda a: a.reshape((L, B) + a.shape[1:]), comp)


# ---------------------------------------------------------------------------
# device-resident tick state
# ---------------------------------------------------------------------------
@dataclass
class TickState:
    """Everything ``fused_tick`` reads and writes — one donated pytree."""

    key: jax.Array          # PRNG state
    cursor: jax.Array       # [] int32 — ring write index (ticks since drain)
    # river lanes
    main_tok: jax.Array     # [M] int32 — last token per lane
    main_pos: jax.Array     # [M] int32 — next rope position
    main_active: jax.Array  # [M] bool
    main_hidden: jax.Array  # [M, d] f32 — gate input
    main_ring: jax.Array    # [M, R] int32 — sampled tokens awaiting drain (-1 = none)
    main_samp: LaneSampling  # [M] per-lane temperature/top-k/top-p
    main_caches: model_lib.ModelCaches
    # stream lanes
    side_tok: jax.Array     # [S] int32
    side_pos: jax.Array     # [S] int32
    side_active: jax.Array  # [S] bool
    side_step: jax.Array    # [S] int32 — ticks since spawn
    side_plen: jax.Array    # [S] int32 — teacher-forced prompt length
    side_prompt: jax.Array  # [S, P] int32 — on-device prompt buffer
    side_hidden: jax.Array  # [S, d] f32
    side_ring: jax.Array    # [S, R] int32
    side_samp: LaneSampling  # [S] per-lane temperature/top-k/top-p
    side_caches: model_lib.ModelCaches


jax.tree_util.register_dataclass(
    TickState, data_fields=[f for f in TickState.__dataclass_fields__], meta_fields=[]
)


def init_tick_state(
    cfg: ModelConfig,
    *,
    n_main: int,
    max_side: int,
    main_spec: model_lib.CacheSpec,
    side_spec: model_lib.CacheSpec,
    ring_capacity: int,
    side_prompt_cap: int,
    main_sampling: SamplingParams,
    side_sampling: SamplingParams,
    seed: int = 0,
) -> TickState:
    """Fresh TickState for an engine (module-level so launch tooling can
    ``jax.eval_shape`` the exact state the engine would build — the dry-run
    lowers the 1024-lane macro tick without materializing 1024 caches)."""
    d = cfg.d_model
    M, S, R, P = n_main, max_side, ring_capacity, side_prompt_cap
    return TickState(
        key=jax.random.key(seed, impl="rbg"),  # cheap per-tick key chain on CPU
        cursor=jnp.zeros((), jnp.int32),
        main_tok=jnp.zeros((M,), jnp.int32),
        main_pos=jnp.zeros((M,), jnp.int32),
        main_active=jnp.zeros((M,), bool),
        main_hidden=jnp.zeros((M, d), jnp.float32),
        main_ring=jnp.full((M, R), -1, jnp.int32),
        main_samp=lane_params(main_sampling, M),
        main_caches=model_lib.init_caches(cfg, M, main_spec),
        side_tok=jnp.zeros((S,), jnp.int32),
        side_pos=jnp.zeros((S,), jnp.int32),
        side_active=jnp.zeros((S,), bool),
        side_step=jnp.zeros((S,), jnp.int32),
        side_plen=jnp.zeros((S,), jnp.int32),
        side_prompt=jnp.zeros((S, P), jnp.int32),
        side_hidden=jnp.zeros((S, d), jnp.float32),
        side_ring=jnp.full((S, R), -1, jnp.int32),
        side_samp=lane_params(side_sampling, S),
        side_caches=model_lib.init_caches(cfg, S, side_spec),
    )


def _one_tick(
    params,
    state: TickState,
    *,
    cfg: ModelConfig,
    main_spec: model_lib.CacheSpec,
    side_spec: model_lib.CacheSpec,
    step_sides: bool = True,
    use_filters: bool = True,
    any_greedy: bool = True,
) -> TickState:
    """One scheduler tick, entirely on device: main-lane decode, side-lane
    decode (synapse caches, Pallas attend), per-lane sampling, ring append.

    Inactive lanes decode garbage harmlessly (their cursors are frozen and
    their caches are fully rewritten on admission) — concurrency through
    batching, priority through the active masks. ``step_sides=False``
    compiles the river-only variant the engine uses while no stream is
    active (side activity only changes at drain boundaries, so the host
    knows which variant applies without reading device state).
    """
    key, k_tick = jax.random.split(state.key)
    m_act = state.main_active
    s_act = state.side_active
    M = m_act.shape[0]

    # ---- river step ----
    logits_m, hidden_m, main_caches = model_lib.decode_step(
        params, cfg, {"tokens": state.main_tok, "positions": state.main_pos},
        state.main_caches, spec=main_spec,
    )

    if step_sides:
        # teacher-force the on-device task prompt, then free-run from the
        # last sampled token; the sampled token "counts" from the last
        # forced step on.
        forced = state.side_step < state.side_plen
        pidx = jnp.clip(state.side_step, 0, state.side_prompt.shape[1] - 1)
        prompt_tok = jnp.take_along_axis(state.side_prompt, pidx[:, None], axis=1)[:, 0]
        in_tok = jnp.where(s_act, jnp.where(forced, prompt_tok, state.side_tok), 0)
        in_pos = jnp.where(s_act, state.side_pos, 0)
        logits_s, hidden_s, side_caches = model_lib.decode_step(
            params, cfg, {"tokens": in_tok, "positions": in_pos},
            state.side_caches, spec=side_spec,
        )
        # one per-lane sampling pass over all lanes (one key chain per tick)
        samp = sample_lanes(
            k_tick, jnp.concatenate([logits_m, logits_s], axis=0),
            cat_lanes(state.main_samp, state.side_samp),
            use_filters=use_filters, any_greedy=any_greedy,
        )
        samp_m, samp_s = samp[:M], samp[M:]
    else:
        samp_m = sample_lanes(
            k_tick, logits_m, state.main_samp,
            use_filters=use_filters, any_greedy=any_greedy,
        )

    # river-lane state transition (shared by both variants)
    ring_m = jnp.where(m_act, samp_m, -1)
    new_state = dataclasses.replace(
        state,
        key=key,
        cursor=state.cursor + 1,
        main_tok=jnp.where(m_act, samp_m, state.main_tok),
        main_pos=state.main_pos + m_act.astype(jnp.int32),
        main_hidden=hidden_m.astype(jnp.float32),
        main_ring=ring_append(state.main_ring, ring_m, state.cursor),
        main_caches=main_caches,
    )
    if not step_sides:
        return new_state

    keep = s_act & (state.side_step >= state.side_plen - 1)
    ring_s = jnp.where(keep, samp_s, -1)
    return dataclasses.replace(
        new_state,
        side_tok=jnp.where(keep, samp_s, state.side_tok),
        side_pos=state.side_pos + s_act.astype(jnp.int32),
        side_step=state.side_step + s_act.astype(jnp.int32),
        side_hidden=hidden_s.astype(jnp.float32),
        side_ring=ring_append(state.side_ring, ring_s, state.cursor),
        side_caches=side_caches,
    )


def fused_tick(
    params,
    state: TickState,
    *,
    cfg: ModelConfig,
    main_spec: model_lib.CacheSpec,
    side_spec: model_lib.CacheSpec,
    step_sides: bool = True,
    use_filters: bool = True,
    any_greedy: bool = True,
    n_ticks: int = 1,
) -> TickState:
    """``n_ticks`` scheduler ticks in ONE device program.

    ``n_ticks == 1`` is the classic fused tick. ``n_ticks > 1`` is the
    macro tick: a ``jax.lax.scan`` of the per-tick body over the whole
    ``sync_every`` window, so the host re-enters XLA once per window
    instead of once per virtual tick. The PRNG key splits once per virtual
    tick inside the scan — the exact chain of the single-tick path — so
    token streams are bitwise identical regardless of how ticks are grouped
    into dispatches. The ring cursor is part of the carry; the rings must
    have capacity for ``state.cursor + n_ticks`` entries.
    """
    step = partial(
        _one_tick, params, cfg=cfg, main_spec=main_spec, side_spec=side_spec,
        step_sides=step_sides, use_filters=use_filters, any_greedy=any_greedy,
    )
    if n_ticks == 1:
        return step(state)

    def body(st, _):
        return step(st), None

    out, _ = jax.lax.scan(body, state, None, length=n_ticks)
    return out


# ---------------------------------------------------------------------------
# small donated state-transition helpers (drain-time only). They take ONLY
# the small per-lane field arrays — never the cache trees, whose buffers may
# already be donated to the prefill/spawn/merge dispatch of the same event.
# ---------------------------------------------------------------------------
def engine_admit_main(tok_a, pos_a, act_a, hid_a, samp_a, lane, tok, pos, hidden, temp, tk, tp):
    return (
        tok_a.at[lane].set(tok),
        pos_a.at[lane].set(pos),
        act_a.at[lane].set(True),
        hid_a.at[lane].set(hidden.astype(hid_a.dtype)),
        _set_lane_samp(samp_a, lane, temp, tk, tp),
    )


def engine_admit_side(prompt_a, plen_a, step_a, tok_a, pos_a, act_a, samp_a, lane, prompt, plen, step, last_tok, pos, temp, tk, tp):
    # ``step`` is 0 on a fresh spawn; a wake passes the hibernated snapshot's
    # step so the teacher-forcing cursor resumes exactly where it stopped
    return (
        prompt_a.at[lane].set(prompt),
        plen_a.at[lane].set(plen),
        step_a.at[lane].set(step),
        tok_a.at[lane].set(last_tok),
        pos_a.at[lane].set(pos),
        act_a.at[lane].set(True),
        _set_lane_samp(samp_a, lane, temp, tk, tp),
    )


def engine_retire_main(act_a, lane):
    return act_a.at[lane].set(False)


def engine_retire_side(act_a, lane):
    return act_a.at[lane].set(False)


def _set_lane_samp(samp_a: LaneSampling, lane, temp, tk, tp) -> LaneSampling:
    return LaneSampling(
        temperature=samp_a.temperature.at[lane].set(temp),
        top_k=samp_a.top_k.at[lane].set(tk),
        top_p=samp_a.top_p.at[lane].set(tp),
    )


def spawn_program(cfg: ModelConfig, side_spec, mesh=None):
    """The spawn program ``engine_spawn(main_caches, side_caches,
    parent_lane, side_lane)``: compress ONE parent lane and scatter it into
    ONE side lane — no all-lane vmap, no full-tree copies (the legacy path
    compressed every main lane to use one).

    On a lane ``mesh`` the compression runs under ``shard_map`` with every
    operand replicated: GSPMD cannot partition a Pallas kernel, so each
    device compresses the replicated parent lane itself, and only the
    scatter into the lane-sharded side caches is left to GSPMD."""
    def compress(main_caches, parent_lane):
        parent = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, parent_lane, 1, axis=1), main_caches
        )
        return spawn_caches(cfg, parent, side_spec)

    if mesh is not None:
        rep = jax.sharding.PartitionSpec()
        compress = sharded_lib.shard_map_nocheck(compress, mesh, in_specs=(rep, rep), out_specs=rep)

    def engine_spawn(main_caches, side_caches, parent_lane, side_lane):
        comp = compress(main_caches, parent_lane)
        return jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice_in_dim(d, s.astype(d.dtype), side_lane, axis=1),
            side_caches,
            comp,
        )

    return engine_spawn


# ---------------------------------------------------------------------------
# hibernation snapshots (ISSUE 7): one lane's device state, gathered into a
# replicated dict pytree the SynapseStore can park on the host. Greedy decode
# depends only on a lane's own cache/token/position, so restoring these exact
# bytes into ANY free lane reproduces the agent's token stream bitwise.
# ---------------------------------------------------------------------------
def engine_gather_main(state: TickState, lane):
    return {
        "caches": lane_gather(state.main_caches, lane, axis=1),
        "tok": state.main_tok[lane],
        "pos": state.main_pos[lane],
        "hidden": state.main_hidden[lane],
    }


def engine_gather_side(state: TickState, lane):
    return {
        "caches": lane_gather(state.side_caches, lane, axis=1),
        "tok": state.side_tok[lane],
        "pos": state.side_pos[lane],
        "step": state.side_step[lane],
        "plen": state.side_plen[lane],
        "prompt": state.side_prompt[lane],
        "hidden": state.side_hidden[lane],
    }


def engine_wake_main_caches(c, part, lane):
    return lane_scatter(c, part, lane, axis=1)


def engine_wake_side_caches(c, part, lane):
    return lane_scatter(c, part, lane, axis=1)


def engine_set_side_hidden(hid_a, lane, h):
    return hid_a.at[lane].set(h.astype(hid_a.dtype))


# byte values the conservative drain gate inspects on the raw token rings
# (ByteTokenizer: ids 0..255 are raw bytes; every router tag needs them both)
_OPEN_BRACKET, _CLOSE_BRACKET = ord("["), ord("]")


class AdaptiveWindow:
    """Window-length policy: lengthen ``sync_every`` while drains are quiet.

    Proposals come from a small fixed ladder ``base * {1, 2, 4, ...}`` capped
    at ``max_window``, so the engine's lazily jit-cached scan-length variants
    stay bounded (one compile per rung, ever). The policy climbs one rung per
    quiet drain — no router trigger, no spawn/merge/completion, no admission
    — and snaps back to the base window on any such event, restoring the
    trigger-reaction latency of the pinned engine the moment control traffic
    reappears. ``max_window == base`` degenerates to the pinned policy.
    """

    def __init__(self, base: int, max_window: int | None = None):
        self.base = max(1, base)
        requested = max(self.base, max_window or self.base)
        # every rung must be base * 2^k: the engine's boundary math (side
        # budget caps, drain alignment with the pinned reference) assumes
        # windows are base multiples, so a max_window that is not on the
        # ladder rounds DOWN to the largest rung below it
        ladder = [self.base]
        while ladder[-1] * 2 <= requested:
            ladder.append(ladder[-1] * 2)
        self.ladder = tuple(ladder)
        self.max_window = ladder[-1]
        self._rung = 0

    @property
    def adaptive(self) -> bool:
        return len(self.ladder) > 1

    def propose(self) -> int:
        return self.ladder[self._rung]

    def on_quiet_drain(self):
        self._rung = min(self._rung + 1, len(self.ladder) - 1)

    def on_event(self):
        self._rung = 0


@dataclass
class AgentView:
    """Host-side bookkeeping for one agent lane (refreshed at drain time)."""

    agent_id: str
    lane: int
    kind: str                  # "main" | "side"
    parent_lane: int = -1
    task: str = ""
    text: str = ""
    tokens: list = field(default_factory=list)
    position: int = 0          # next rope position (drain-time mirror)
    active: bool = False
    steps: int = 0
    prompt_len: int = 0


# the durable subset of AgentView: what crash recovery needs to rebuild the
# host-side view of a hibernated agent (lane/active are rebuilt at wake)
_VIEW_META_FIELDS = (
    "agent_id", "kind", "parent_lane", "task", "text", "tokens",
    "position", "steps", "prompt_len",
)


def _view_to_meta(view: "AgentView") -> dict:
    out = {f: getattr(view, f) for f in _VIEW_META_FIELDS}
    out["tokens"] = [int(t) for t in out["tokens"]]
    return out


def _view_from_meta(meta: dict) -> "AgentView":
    view = AgentView(meta["agent_id"], -1, meta["kind"])
    for f in _VIEW_META_FIELDS[2:]:
        setattr(view, f, meta[f])
    view.tokens = list(meta["tokens"])
    view.active = False
    return view


class CortexEngine:
    def __init__(
        self,
        prism: Prism,
        tokenizer: ByteTokenizer,
        *,
        n_main: int = 1,
        max_side: int = 8,
        main_capacity: int = 1024,
        side_spec: model_lib.CacheSpec | None = None,
        theta: float = 0.5,
        inject_tokens: int = 16,
        side_max_steps: int = 64,
        sampling: SamplingParams = SamplingParams(temperature=0.8),
        side_sampling: SamplingParams | None = None,
        seed: int = 0,
        sync_every: int = 1,
        max_window: int | None = None,
        pipeline: bool = True,
        side_prompt_cap: int = 64,
        compute_dtype: str | None = None,
        mesh=None,
        store: SynapseStore | None = None,
        hibernate_idle_ticks: int | None = None,
        wake_deadline_s: float | None = None,
    ):
        """``mesh``: a lane mesh (see ``launch.mesh.make_lane_mesh``) shards
        every side-lane TickState leaf over its ``lane`` axis and runs the
        macro tick under ``shard_map`` — side agents scale with the mesh
        while main-stream state stays replicated (each device steps the
        river redundantly; rivers are the cheap part of the topology).
        ``max_side`` must be a multiple of the lane-axis size. Greedy token
        streams are bitwise identical to the ``mesh=None`` engine; every
        dispatch/donation/zero-sync invariant holds unchanged."""
        self.prism = prism
        cfg = prism.cfg
        # Serving dtype policy: CPU has no native bf16 — XLA emulates it with
        # up/down converts on every op, strictly slower than f32. Auto-pick
        # f32 there; accelerator backends keep the configured dtype.
        if compute_dtype is None and cfg.compute_dtype == "bfloat16" and jax.default_backend() == "cpu":
            compute_dtype = "float32"
        if compute_dtype is not None:
            cfg = dataclasses.replace(cfg, compute_dtype=compute_dtype)
        self.cfg = cfg
        self.tok = tokenizer
        self.theta = theta
        self.inject_tokens = inject_tokens
        self.side_max_steps = side_max_steps
        self.sampling = sampling
        self.side_sampling = side_sampling if side_sampling is not None else sampling
        self.sync_every = max(1, sync_every)
        self.side_prompt_cap = side_prompt_cap
        # Adaptive windows: ``run`` may scan up to max_window virtual ticks
        # per dispatch while drains stay quiet (max_window=None pins the
        # window at sync_every; off-ladder values round DOWN to base*2^k).
        # ``pipeline=False`` keeps the serial PR 4 dispatch→drain→dispatch
        # order — the parity reference in tests — whose windows stay pinned,
        # so adaptation is dropped there rather than paying max_window-sized
        # rings and router tail for a policy that never engages.
        self.window = AdaptiveWindow(
            self.sync_every, max_window if pipeline else None
        )
        self.max_window = self.window.max_window
        self.pipeline = pipeline
        # macro windows mean bigger drain chunks: size the router's overlap
        # tail so a tag split across window boundaries still matches. The
        # tail must cover (a) the longest tag the engine round-trips — a
        # side_prompt_cap-byte task payload plus '[TASK: ]' framing — and
        # (b) a full drain window of text (8 bytes/token bounds the worst
        # UTF-8 replacement expansion). tests/test_router.py pins this.
        self.router = CortexRouter(
            tail=max(256, 8 * self.max_window, side_prompt_cap + 16)
        )

        # lane mesh: detect the axis up front — the side spec's attend policy
        # depends on it (threaded through the CacheSpec, not a module global)
        self.mesh = mesh
        self.lane_axis = None
        if mesh is not None and "lane" in getattr(mesh, "axis_names", ()):
            self.lane_axis = "lane"
            lanes = mesh.shape["lane"]
            if max_side % lanes != 0:
                raise ValueError(
                    f"max_side={max_side} must be a multiple of the lane-axis "
                    f"size {lanes} (every side leaf shards the same lane dim)"
                )

        self.main_spec = model_lib.CacheSpec(kind="full", capacity=main_capacity)
        base_side_spec = side_spec or model_lib.CacheSpec(
            kind="synapse", n_landmarks=64, window=64, n_inject=inject_tokens
        )
        if self.lane_axis is not None and base_side_spec.policy.attend_impl == "pallas":
            # under the lane shard_map each device attends over its LOCAL
            # lanes: route through piece_attend, whose local path is the
            # same fused kernels.ops attend — bitwise parity preserved
            base_side_spec = dataclasses.replace(
                base_side_spec,
                policy=dataclasses.replace(base_side_spec.policy, attend_impl="piece"),
            )
        self.side_spec = base_side_spec
        self.n_main, self.max_side = n_main, max_side
        self.mains = [AgentView(f"main{i}", i, "main") for i in range(n_main)]
        self.sides = [AgentView(f"side{i}", i, "side") for i in range(max_side)]
        # tiered memory (ISSUE 7): agents outlive lane slots — hibernated
        # contexts park in the store (warm host RAM / cold zstd disk), the
        # registry owns identity + LRU bookkeeping, and wakes land via the
        # async prefetch tickets committed at window boundaries in run()
        self.store = store if store is not None else SynapseStore()
        self.registry = AgentRegistry()
        self.hibernate_idle_ticks = hibernate_idle_ticks
        # default promotion deadline (seconds) applied to every wake unless
        # overridden per call — bounds how long a stuck prefetch can hold an
        # agent in limbo before it degrades to a failed wake
        self.wake_deadline_s = wake_deadline_s
        self._agent_seq = 0
        self._wake_tickets: dict[str, object] = {}
        self._pending_wakes: list[str] = []
        # (kind, lane) pairs woken between a ring fetch and that window's
        # post-processing: they were NOT on device for the fetched window,
        # so the mirror advancement in _postprocess must skip them
        self._fresh_wakes: set[tuple[str, int]] = set()
        # host mirrors of the per-lane device sampling arrays: they pick the
        # STATIC sampler fast path (skip the sort when no live lane filters,
        # skip the argmax select when none is greedy) without device reads
        self._main_sp: list[SamplingParams] = [self.sampling] * n_main
        self._side_sp: list[SamplingParams] = [self.side_sampling] * max_side
        # per-agent stateful UTF-8 decoders (ISSUE 9 bugfix): drain chunks
        # decode incrementally, so a codepoint split across a window
        # boundary never becomes U+FFFD in the agent's `text`. Keyed by
        # agent_id — the state survives hibernate/wake in-process, and its
        # pending bytes ride the hibernation metadata for crash recovery.
        self._decoders: dict[str, object] = {}
        # serving front-end hooks (ISSUE 9): ``stream_tap(view, chunk,
        # toks)`` fires during drain post-processing for every lane that
        # received tokens (chunks are incremental-decoder output — their
        # concatenation is the bitwise text stream); ``admission_hook`` runs
        # with the window-boundary control plane in :meth:`_boundary_ops`,
        # so front-end admissions never flush a pipelined window.
        self.stream_tap = None
        self.admission_hook = None
        self.history: list[dict] = []
        self.stats = {
            "ticks": 0, "tick_dispatches": 0, "macro_dispatches": 0,
            "aux_dispatches": 0, "host_syncs": 0, "drains": 0,
            # pipeline/adaptive telemetry: drains whose host post-processing
            # overlapped the next window's device execution, and a histogram
            # of dispatched window lengths (window_hist[w] = count)
            "overlapped_drains": 0, "window_hist": {},
            # sides started, and [TASK] triggers refused for want of a lane
            "spawns": 0, "spawns_dropped": 0,
            # sides merged back, merges whose thought the gate let in, and
            # the batched merge programs that ran them
            "merges": 0, "merges_accepted": 0, "merge_dispatches": 0,
            # tiered-memory telemetry
            "hibernates": 0, "wakes": 0,
            # resilience telemetry (ISSUE 8): wake_failures = transient
            # (snapshot intact, agent stays HIBERNATED, retryable);
            # lost_agents = permanent (snapshot unrecoverable, agent LOST);
            # recoveries = hibernated agents re-adopted after a restart
            "wake_failures": 0, "lost_agents": 0, "recoveries": 0,
        }
        self._pending = 0  # ticks since last drain (== device ring cursor)
        self._merge_widths_ready = False  # every merge program width compiled

        cfg = self.cfg
        # Serving-dtype weights, cast ONCE (the per-dispatch cast_params
        # inside decode becomes an identity XLA elides). The Prism's master
        # copy stays authoritative for accounting/training.
        self._params = model_lib.cast_params(prism.params, cfg)
        # rings must hold the longest adaptive window, not just sync_every
        self.state = init_tick_state(
            cfg, n_main=n_main, max_side=max_side, main_spec=self.main_spec,
            side_spec=self.side_spec, ring_capacity=self.max_window,
            side_prompt_cap=side_prompt_cap, main_sampling=self.sampling,
            side_sampling=self.side_sampling, seed=seed,
        )

        # lane placement: side leaves shard over the mesh, main/key/cursor
        # and the weights replicate. Committing everything up front keeps
        # the macro dispatch transfer-free (the zero-host-sync invariant).
        self._rep_sharding = None
        self._state_specs = None
        if self.lane_axis is not None:
            from repro.launch import sharding as shard_rules

            self._state_specs = shard_rules.tick_state_specs(self.state, mesh)
            self._state_shardings = shard_rules.shardings_for(self._state_specs, mesh)
            self._rep_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()
            )
            self.state = jax.device_put(self.state, self._state_shardings)
            self._params = jax.device_put(self._params, self._rep_sharding)

        # Small stacks trace faster through lax.scan but *run* faster
        # unrolled on CPU (no while-loop thunks, cross-layer fusion); deep
        # stacks keep scan so HLO size stays depth-independent.
        jcfg = dataclasses.replace(cfg, scan_layers=cfg.scan_layers and cfg.n_layers > 8)

        # ONE fused dispatch per tick (or per macro window: fused_tick with
        # n_ticks > 1 scans the tick body); the whole TickState is donated,
        # so caches (the dominant buffers) update in place. The river-only
        # variant is dispatched while no stream lane is live. Window-length
        # variants (full windows + the trailing partial window of a run)
        # compile lazily, cached by (n_ticks, step_sides, sampler flags).
        self._jcfg = jcfg
        self._jit_macro: dict[tuple[int, bool, bool, bool], object] = {}

        # drain-time jits. On a lane mesh every output sharding is pinned
        # explicitly (replicated or the TickState leaf's lane spec) so the
        # donated buffers alias and the next macro dispatch sees exactly the
        # shardings it compiled for — GSPMD would otherwise be free to pick
        # different output shardings and break donation or force resharding.
        rep, ssh = self._rep_sharding, getattr(self, "_state_shardings", None)

        def _jit(fn, donate, out=None):
            if self.lane_axis is not None and out is not None:
                return jax.jit(fn, donate_argnums=donate, out_shardings=out)
            return jax.jit(fn, donate_argnums=donate)

        # every auxiliary program is a named function, so each compiles to
        # a module of its own name (``jit_engine_spawn``, ...) in a trace
        def engine_prefill(p, toks, c, lane):
            return model_lib.prefill_lane(p, jcfg, {"tokens": toks}, c, lane, spec=self.main_spec)

        def engine_merge(p, mc, mh, toks, parent, vpos, valid):
            return injection.merge_thoughts(p, jcfg, mc, mh, toks, parent, vpos, valid, self.theta)

        self._jit_prefill_lane = _jit(
            engine_prefill, (2,), (rep, rep, ssh.main_caches) if ssh else None,
        )
        self._jit_spawn = _jit(
            spawn_program(jcfg, self.side_spec, mesh=self.mesh if ssh else None), (1,),
            ssh.side_caches if ssh else None,
        )
        self._jit_merge = _jit(
            engine_merge, (1,), (ssh.main_caches, rep, rep) if ssh else None,
        )
        self._jit_admit_main = _jit(
            engine_admit_main, (0, 1, 2, 3, 4),
            (ssh.main_tok, ssh.main_pos, ssh.main_active, ssh.main_hidden,
             ssh.main_samp) if ssh else None,
        )
        self._jit_admit_side = _jit(
            engine_admit_side, (0, 1, 2, 3, 4, 5, 6),
            (ssh.side_prompt, ssh.side_plen, ssh.side_step, ssh.side_tok,
             ssh.side_pos, ssh.side_active, ssh.side_samp) if ssh else None,
        )
        self._jit_retire_side = _jit(
            engine_retire_side, (0,), ssh.side_active if ssh else None,
        )
        self._jit_retire_main = _jit(
            engine_retire_main, (0,), ssh.main_active if ssh else None,
        )
        # hibernate/wake lane transfer jits. Gathers replicate their outputs
        # (on a mesh GSPMD inserts the collective pulling a sharded side
        # lane's leaves together); scatters donate the full cache tree and
        # pin its lane sharding so the next macro dispatch aliases cleanly.
        self._jit_gather_main = _jit(engine_gather_main, (), rep if ssh else None)
        self._jit_gather_side = _jit(engine_gather_side, (), rep if ssh else None)
        self._jit_wake_main_caches = _jit(
            engine_wake_main_caches, (0,), ssh.main_caches if ssh else None,
        )
        self._jit_wake_side_caches = _jit(
            engine_wake_side_caches, (0,), ssh.side_caches if ssh else None,
        )
        self._jit_set_side_hidden = _jit(
            engine_set_side_hidden, (0,), ssh.side_hidden if ssh else None,
        )

    def _macro_fn(self, n_ticks: int, step_sides: bool, use_filters: bool, any_greedy: bool):
        """Jitted fused_tick variant for an ``n_ticks``-long window.

        On a lane mesh the whole window body runs under ``shard_map``: each
        device scans its local side-lane shard (caches, rings, sampling
        arrays, budgets) while stepping the replicated river redundantly —
        still ONE donated dispatch, still zero host syncs. The PRNG key is a
        replicated carry, so greedy lanes stay bitwise identical to the
        single-device engine no matter how lanes are placed."""
        key = (n_ticks, step_sides, use_filters, any_greedy)
        if key not in self._jit_macro:
            fn = partial(
                fused_tick, cfg=self._jcfg, main_spec=self.main_spec,
                side_spec=self.side_spec, step_sides=step_sides,
                use_filters=use_filters, any_greedy=any_greedy,
                n_ticks=n_ticks,
            )
            if self.lane_axis is not None:
                fn = sharded_lib.shard_map_nocheck(
                    fn, self.mesh,
                    in_specs=(jax.sharding.PartitionSpec(), self._state_specs),
                    out_specs=self._state_specs,
                )
            self._jit_macro[key] = jax.jit(fn, donate_argnums=(1,))
        return self._jit_macro[key]

    def _sampler_flags(self, step_sides: bool) -> tuple[bool, bool]:
        """(use_filters, any_greedy) over the lanes the dispatch samples.

        Derived purely from the host mirrors, so the flags — and thus the
        chosen program — only change when lane params or activity change,
        which happens at drain boundaries: macro and single-tick paths pick
        identical variants (stochastic draws differ bitwise between
        variants, so this invariance is what keeps parity exact)."""
        ps = [self._main_sp[m.lane] for m in self.mains if m.active]
        if step_sides:
            ps += [self._side_sp[s.lane] for s in self.sides if s.active]
        return static_flags(ps)

    @property
    def lane_mesh_shape(self) -> tuple[int, ...] | None:
        """Device-mesh shape when lane-sharded (recorded by the benches)."""
        if self.mesh is None:
            return None
        return tuple(int(s) for s in self.mesh.devices.shape)

    # -- per-agent incremental UTF-8 decode --------------------------------
    def _decoder(self, agent_id: str):
        dec = self._decoders.get(agent_id)
        if dec is None:
            dec = self._decoders[agent_id] = self.tok.stream_decoder()
        return dec

    def agent_text(self, agent_id: str) -> str:
        """The agent's full decoded text as of the last drain, INCLUDING the
        would-be flush of a codepoint left incomplete at the window
        boundary — i.e. exactly what ``tok.decode(tokens)`` yields for the
        same stream. Non-destructive: the decoder keeps buffering, so the
        live stream stays bitwise when the missing bytes arrive."""
        for v in (*self.mains, *self.sides):
            if v.agent_id == agent_id:
                dec = self._decoders.get(agent_id)
                return v.text + (dec.tail() if dec is not None else "")
        rec = self.registry.get(agent_id)
        view = rec.saved["view"] if rec.saved else None
        if view is None:
            raise KeyError(agent_id)
        dec = self._decoders.get(agent_id)
        return view.text + (dec.tail() if dec is not None else "")

    # -- legacy views over the device state --------------------------------
    @property
    def main_caches(self):
        return self.state.main_caches

    @property
    def side_caches(self):
        return self.state.side_caches

    @property
    def main_hidden(self):
        return self.state.main_hidden

    @property
    def side_hidden(self):
        return self.state.side_hidden

    # ------------------------------------------------------------------
    def submit(self, prompt: str, lane: int = 0, sampling: SamplingParams | None = None,
               agent_id: str | None = None):
        """Start (or restart) a main agent on `lane` with `prompt`.

        Prefills directly into the batched cache at `lane` (one dispatch,
        donated caches — no gather/scatter round-trip of the full tree).
        ``sampling`` overrides the engine default for THIS lane only (e.g. a
        greedy river among exploratory lanes); restarting a lane resets it.
        ``agent_id`` names the agent in the registry (it can later
        :meth:`hibernate` and :meth:`wake` into a different lane); omitted,
        the classic per-lane identity ``main{lane}`` is used when free."""
        self.drain()  # align host mirrors to a window boundary
        self.window.on_event()  # admission: back to the base window
        aid = self._claim_main_identity(lane, agent_id)
        with _span("engine.submit", agent=aid):
            ids = self.tok.encode(prompt, bos=True)
            toks = jnp.asarray([ids], jnp.int32)
            logits, hidden, new_caches = self._jit_prefill_lane(
                self._params, toks, self.state.main_caches, lane
            )
            self._main_sp[lane] = sampling if sampling is not None else self.sampling
            temp, tk, tp = lane_values(self._main_sp[lane])
            tok_a, pos_a, act_a, hid_a, samp_a = self._jit_admit_main(
                self.state.main_tok, self.state.main_pos, self.state.main_active,
                self.state.main_hidden, self.state.main_samp,
                lane, ids[-1], len(ids), hidden[0], temp, tk, tp,
            )
            self.state = dataclasses.replace(
                self.state, main_caches=new_caches, main_tok=tok_a, main_pos=pos_a,
                main_active=act_a, main_hidden=hid_a, main_samp=samp_a,
            )
            self.stats["aux_dispatches"] += 2
            m = AgentView(aid, lane, "main")
            self.mains[lane] = m
            m.text, m.tokens = prompt, list(ids)
            m.position, m.active, m.steps = len(ids), True, 0
            m.prompt_len = len(ids)
            self._decoders[aid] = self.tok.stream_decoder()  # fresh byte stream
            self.prism.acquire(m.agent_id)
            rec = self.registry.bind(aid, lane)
            rec.bound_tick = self.stats["ticks"]
            self.router.reset(m.agent_id)  # lane may be restarting
            # triggers already present in the prompt spawn immediately
            for tr in self.router.feed(m.agent_id, prompt):
                if tr.kind == "task":
                    self._spawn_side(m, tr.payload)
            return m

    def _claim_main_identity(self, lane: int, agent_id: str | None) -> str:
        """Resolve the agent_id a main-lane submit binds, evicting the lane's
        previous occupant from the registry (its context is overwritten)."""
        cur = self.mains[lane]
        if cur.active:
            # whoever held the lane loses its device context
            self.prism.release(cur.agent_id)
            self.registry.release(cur.agent_id)
            self.router.reset(cur.agent_id)
            self._decoders.pop(cur.agent_id, None)
        if agent_id is None:
            agent_id = f"main{lane}"
            if agent_id in self.registry and (
                self.registry.get(agent_id).status == HIBERNATED
                or (self.registry.get(agent_id).status == ACTIVE
                    and self.registry.get(agent_id).lane != lane)
            ):
                # the classic identity is alive elsewhere (parked or woken
                # into another lane): mint a fresh one instead of clobbering
                agent_id = f"main{lane}.{self._agent_seq}"
                self._agent_seq += 1
        else:
            if agent_id in self.registry:
                rec = self.registry.get(agent_id)
                if rec.status == ACTIVE and rec.lane != lane:
                    raise ValueError(
                        f"agent {agent_id!r} is already active on lane {rec.lane}"
                    )
                if rec.status == HIBERNATED:
                    # re-submitting replaces the parked context outright
                    self.store.drop(agent_id)
                    self._wake_tickets.pop(agent_id, None)
                    if agent_id in self._pending_wakes:
                        self._pending_wakes.remove(agent_id)
        self.registry.register(agent_id, "main")
        return agent_id

    def submit_agent(self, prompt: str, agent_id: str | None = None,
                     sampling: SamplingParams | None = None):
        """Lane-less submit: place a (new or registered) agent on any free
        main lane, hibernating the least-recently-touched resident if the
        river lanes are full — "max lanes" becomes "max *active* agents"."""
        lane = self._free_main_lane()
        if lane < 0:
            evicted = self._evict_lru_main()
            if evicted is None:
                raise RuntimeError(
                    "no free main lane and no evictable resident "
                    "(all mains have live side streams)"
                )
            lane = self._free_main_lane()
            assert lane >= 0
        if agent_id is None:
            agent_id = f"agent{self._agent_seq}"
            self._agent_seq += 1
        return self.submit(prompt, lane=lane, sampling=sampling, agent_id=agent_id)

    # ------------------------------------------------------------------
    def _any_active(self) -> bool:
        return any(m.active for m in self.mains) or any(s.active for s in self.sides)

    def tick(self):
        """One scheduler tick: exactly one jitted dispatch, no host sync.

        Spawns/merges/router triggers are handled at drain boundaries —
        every `sync_every` ticks. Side activity only changes at those
        boundaries, so the host picks the right tick variant for free."""
        if not self._any_active():
            self.stats["ticks"] += 1
            return  # idle engine: nothing to decode, nothing to drain
        self._dispatch_window(1)
        if self._pending >= self.sync_every:
            self.drain()

    def macro_tick(self):
        """One macro tick: `sync_every` virtual ticks in ONE jitted, donated
        dispatch (a lax.scan over the fused tick body), then the window
        drains. The device never syncs with the host inside the window."""
        if not self._any_active():
            self.stats["ticks"] += self.sync_every
            return
        if self._pending:
            self.drain()  # align the ring cursor to a window boundary
        self._dispatch_window(self.sync_every)
        self.drain()

    def _dispatch_window(self, n: int):
        """Advance ``n <= max_window - pending`` virtual ticks in one
        dispatch. No drain, no host sync — callers close the window."""
        assert self._pending + n <= self.max_window
        with _span("engine.dispatch", n=n):
            step_sides = any(s.active for s in self.sides)
            fn = self._macro_fn(n, step_sides, *self._sampler_flags(step_sides))
            self.state = fn(self._params, self.state)
        self.stats["ticks"] += n
        self.stats["tick_dispatches"] += 1
        if n > 1:
            self.stats["macro_dispatches"] += 1
        hist = self.stats["window_hist"]
        hist[n] = hist.get(n, 0) + 1
        self._pending += n

    def _next_window(self, remaining: int, pending=None) -> int:
        """Length of the next scan window: the adaptive proposal, capped (a)
        exactly at the serial-path boundary where any active side's step
        budget completes — a multiple of the base window, so the merge lands
        on the same virtual tick as the pinned engine — and (b) to the base
        window whenever the router's retained tail holds an unclosed ``[``
        (a tag may be completing: keep reaction latency at one base window).
        Every cap keeps the window a multiple of the base except the run's
        trailing partial window (``remaining``).

        ``pending=(rings, n)`` is the overlapped-branch correction: window
        *t* has been fetched but NOT yet post-processed, so the side views'
        ``tokens``/``steps`` are one window stale — the budget cap must
        count window *t*'s recorded ring tokens or the boundary lands one
        window late and the merge drifts off the serial tick (the router
        tail, by contrast, is provably unchanged by a gate-approved window:
        no ``[`` entered it and no pending ``[`` was closed)."""
        base = self.sync_every
        w = self.window.propose()
        if w > base:
            for s in self.sides:
                if not s.active:
                    continue
                generated = len(s.tokens) - s.prompt_len
                steps = s.steps
                if pending is not None:
                    rings, p_n = pending
                    toks = rings[1][s.lane, :p_n]
                    generated += int((toks >= 0).sum())
                    steps += p_n
                forced_left = max(0, (s.prompt_len - 1) - steps)
                t_budget = forced_left + max(1, self.side_max_steps - generated)
                boundary = base * -(-t_budget // base)  # ceil to base multiple
                w = min(w, boundary)
            if any(
                self.router.plausible(a.agent_id)
                for a in (*self.mains, *self.sides) if a.active
            ):
                w = base
        return min(w, remaining)

    def _gate(self, rings, n: int) -> bool:
        """May window ``t+1`` be dispatched BEFORE window ``t``'s host
        post-processing? True only when that post-processing provably issues
        no control op (spawn/merge/completion) — i.e. it is pure host
        bookkeeping. Conservative, byte-level, and cheap (numpy on the
        already-fetched rings):

        * any ``[`` in a lane's new tokens could open (and close) a tag —
          unsafe;
        * a ``]`` completes a tag only if the retained router tail has an
          unclosed ``[`` (:meth:`CortexRouter.plausible`) — unsafe;
        * a side lane reaching its step budget this window merges — exact
          host arithmetic, unsafe.

        False negatives are impossible (every trigger needs those bytes;
        budgets are deterministic), so a True verdict guarantees bitwise
        parity with the serial drain order."""
        main_ring, side_ring = rings
        for m in self.mains:
            if not m.active:
                continue
            toks = main_ring[m.lane, :n]
            toks = toks[toks >= 0]
            if (toks == _OPEN_BRACKET).any():
                return False
            if (toks == _CLOSE_BRACKET).any() and self.router.plausible(m.agent_id):
                return False
        for s in self.sides:
            if not s.active:
                continue
            toks = side_ring[s.lane, :n]
            toks = toks[toks >= 0]
            if (toks == _OPEN_BRACKET).any():
                return False
            if (toks == _CLOSE_BRACKET).any() and self.router.plausible(s.agent_id):
                return False
            if len(s.tokens) - s.prompt_len + toks.size >= self.side_max_steps:
                return False
        return True

    def run(self, n_ticks: int):
        """Advance ``n_ticks`` virtual ticks in at most
        ``ceil(n_ticks/sync_every)`` dispatches (exactly that many with a
        pinned window; adaptive windows need fewer).

        Pipelined (default): after fetching window *t*'s rings — the one
        blocking sync per window — the conservative :meth:`_gate` decides
        whether window *t+1* is dispatched before window *t*'s host
        post-processing, overlapping router/decode work with device compute.
        ``pipeline=False`` keeps the serial PR 4 loop (the parity reference).
        """
        if not self.pipeline:
            return self._run_serial(n_ticks)
        remaining = n_ticks
        # close a partially-filled window (tick() interleavings) exactly
        # like the serial path before entering the pipeline at a boundary
        while 0 < remaining and self._pending and self._any_active():
            w = min(self.sync_every - self._pending, remaining)
            self._dispatch_window(w)
            remaining -= w
            if self._pending >= self.sync_every:
                self.drain()
        if self._pending:
            self.drain()

        inflight = 0  # virtual ticks of the window currently on the device
        while remaining or inflight:
            if not inflight:
                # window boundary, nothing in flight: the tiered-memory
                # control plane runs here (idle-tick demotions + ready wake
                # commits; a fully idle engine blocks on its prefetch
                # tickets so a wake-only run still makes progress)
                self._boundary_ops(wait=not self._any_active())
                if not self._any_active():
                    self.stats["ticks"] += remaining
                    return
                inflight = self._next_window(remaining)
                self._dispatch_window(inflight)
                self._prefetch_rings()
                remaining -= inflight
                continue
            rings, nwin = self._fetch_rings(), inflight
            inflight = 0
            # ready wakes commit between the ring fetch and the next
            # dispatch: the prefetched buffers are already on device, so the
            # scatter joins window t+1 without flushing the pipeline. (No
            # demotions here — window t's host mirrors are still stale.)
            self._commit_ready_wakes(mark_fresh=True)
            if remaining and self._any_active() and self._gate(rings, nwin):
                # overlap: the device starts window t+1 while the host does
                # window t's decoding/router work (guaranteed control-free);
                # the window policy must see window t's still-unprocessed
                # ring tokens or its budget caps run one window stale
                inflight = self._next_window(remaining, pending=(rings, nwin))
                self._dispatch_window(inflight)
                self._prefetch_rings()
                remaining -= inflight
                self._postprocess(rings, nwin, overlapped=True)
                self.stats["overlapped_drains"] += 1
            else:
                self._postprocess(rings, nwin)
        self._boundary_ops()

    def _run_serial(self, n_ticks: int):
        """The PR 4 lockstep loop: dispatch → drain → dispatch, pinned
        ``sync_every`` windows. Kept as the bitwise parity reference."""
        remaining = n_ticks
        while remaining > 0:
            if self._pending == 0:
                self._boundary_ops(wait=not self._any_active())
            if not self._any_active():
                self.stats["ticks"] += remaining
                break
            w = min(self.sync_every - self._pending, remaining)
            if w <= 1:
                self.tick()  # drains itself when the window closes
                remaining -= 1
                continue
            self._dispatch_window(w)
            remaining -= w
            if self._pending >= self.sync_every:
                self.drain()
        self.drain()
        self._boundary_ops()

    # ------------------------------------------------------------------
    def drain(self):
        """Flush the device token rings to the host (ONE blocking transfer),
        update agent views, and run the router/spawn/merge control plane."""
        n = self._pending
        if n == 0:
            return
        self._postprocess(self._fetch_rings(), n)

    def _prefetch_rings(self):
        """Start the device→host ring copies as soon as the in-flight
        window's compute finishes, so the ``_fetch_rings`` that follows the
        overlapped host work blocks only on the residue. Only worth issuing
        where a fetch is known to follow — the pipelined ``run`` loop; the
        single-tick path overwrites the rings before any fetch."""
        self.state.main_ring.copy_to_host_async()
        self.state.side_ring.copy_to_host_async()

    def _fetch_rings(self):
        """The pipeline's sync point: ONE blocking device→host pull of the
        token rings (host numpy copies), then reset the ring cursor so the
        next dispatch — which donates the ring buffers — starts a fresh
        window immediately."""
        with _span("engine.fetch"):
            rings = jax.device_get((self.state.main_ring, self.state.side_ring))
        self.stats["host_syncs"] += 1
        self._pending = 0
        zero = jnp.zeros((), jnp.int32)
        if self._rep_sharding is not None:
            # a FRESH committed replicated zero each drain: the previous one
            # was donated to the last macro dispatch, and an uncommitted
            # scalar would trip the window's transfer guard at dispatch time
            zero = jax.device_put(zero, self._rep_sharding)
        self.state = dataclasses.replace(self.state, cursor=zero)
        return rings

    def _postprocess(self, rings, n: int, *, overlapped: bool = False):
        """Window ``t``'s host-side control plane over the fetched rings:
        decode text, feed the router, complete/merge sides, spawn rivers'
        tasks. With ``overlapped=True`` the next window is already on the
        device, so any control op here would be a gate violation — asserted,
        and by the gate's conservativeness unreachable."""
        with _span("engine.postprocess", overlapped=overlapped):
            main_ring, side_ring = rings
            self.stats["drains"] += 1
            quiet = True

            # 1. rivers: append the window's tokens. Decode is INCREMENTAL
            # (ISSUE 9 bugfix): a multi-byte codepoint split across the drain
            # boundary stays buffered in the agent's decoder instead of
            # becoming U+FFFD — m.text is always a bitwise prefix of the
            # one-shot decode, and agent_text() exposes the exact final form.
            main_chunks: dict[int, str] = {}
            for m in self.mains:
                if not m.active:
                    continue
                if ("main", m.lane) in self._fresh_wakes:
                    continue  # woke after this window ran: not on device for it
                toks = [int(t) for t in main_ring[m.lane, :n] if t >= 0]
                chunk = self._decoder(m.agent_id).feed(toks)
                m.tokens.extend(toks)
                m.text += chunk
                m.position += len(toks)
                m.steps += len(toks)
                main_chunks[m.lane] = chunk
                if self.stream_tap is not None and toks:
                    self.stream_tap(m, chunk, toks)

            # 2. streams: append, detect completion (trigger or step budget)
            finished = []
            for s in self.sides:
                if not s.active:
                    continue
                if ("side", s.lane) in self._fresh_wakes:
                    continue  # woke after this window ran: not on device for it
                s.steps += n
                s.position += n
                raw = [int(t) for t in side_ring[s.lane, :n] if t >= 0]
                allowed = max(0, self.side_max_steps - (len(s.tokens) - s.prompt_len))
                raw = raw[:allowed]
                s.tokens.extend(raw)
                # incremental decode (ISSUE 9 bugfix): same contract as the
                # rivers — a codepoint split across windows never corrupts
                # s.text or the thought handed to the merge gate
                chunk = self._decoder(s.agent_id).feed(raw)
                s.text += chunk
                if self.stream_tap is not None and raw:
                    self.stream_tap(s, chunk, raw)
                all_trig = self.router.feed(s.agent_id, chunk)
                quiet = quiet and not all_trig
                trig = [t for t in all_trig if t.kind in ("done", "answer")]
                generated = len(s.tokens) - s.prompt_len
                if trig or generated >= self.side_max_steps:
                    # end of this stream: flush the decoder so s.text equals
                    # the one-shot decode bitwise (an incomplete trailing
                    # codepoint replaces, exactly as decode(tokens) would)
                    s.text += self._decoder(s.agent_id).flush()
                    answer = next((t.payload for t in trig if t.kind == "answer"), None)
                    if answer is not None:
                        thought = answer
                    elif trig:
                        # feed() spans are absolute offsets into the generated
                        # stream (== s.text): cut the free-running tokens the
                        # lane produced between the trigger and this drain
                        thought = s.text[: trig[0].span[1]]
                    else:
                        thought = s.text
                    finished.append((s, thought))

            # 3. merges (free lanes before new spawns claim them)
            assert not (overlapped and finished), "pipeline gate violated: merge"
            if finished:
                self._merge_sides(finished)
            quiet = quiet and not finished

            # 4. river triggers spawn new streams
            for m in self.mains:
                if not m.active or m.lane not in main_chunks:
                    continue
                for tr in self.router.feed(m.agent_id, main_chunks[m.lane]):
                    quiet = False
                    assert not overlapped, "pipeline gate violated: trigger"
                    if tr.kind == "task":
                        self._spawn_side(m, tr.payload)

            # 5. window policy: quiet drains earn longer windows, any control
            # event snaps back to the base window
            if quiet:
                self.window.on_quiet_drain()
            else:
                self.window.on_event()
            self._fresh_wakes.clear()  # next window has the woken lanes on device

    # ------------------------------------------------------------------
    def _free_side_lane(self) -> int:
        for s in self.sides:
            if not s.active:
                return s.lane
        return -1

    def _spawn_side(self, parent: AgentView, task: str, sampling: SamplingParams | None = None):
        with _span("engine.spawn", parent=parent.agent_id) as sp:
            lane = self._free_side_lane()
            if lane < 0:
                # admission policy: drop when streams are saturated (counted)
                self.stats["spawns_dropped"] += 1
                return None
            new_side_caches = self._jit_spawn(
                self.state.main_caches, self.state.side_caches, parent.lane, lane
            )
            # keep the HEAD on overflow and close the frame: the '[TASK: ... ]'
            # framing is what conditions the stream; an over-long task loses its
            # tail, never its framing
            ids = self.tok.encode(f"[TASK: {task}]")
            truncated = len(ids) > self.side_prompt_cap
            if truncated:
                close = self.tok.encode("]")
                ids = ids[: self.side_prompt_cap - len(close)] + close
            padded = ids + [0] * (self.side_prompt_cap - len(ids))
            self._side_sp[lane] = sampling if sampling is not None else self.side_sampling
            temp, tk, tp = lane_values(self._side_sp[lane])
            prompt_a, plen_a, step_a, tok_a, pos_a, act_a, samp_a = self._jit_admit_side(
                self.state.side_prompt, self.state.side_plen, self.state.side_step,
                self.state.side_tok, self.state.side_pos, self.state.side_active,
                self.state.side_samp,
                lane, jnp.asarray(padded, jnp.int32), len(ids), 0, ids[-1], parent.position,
                temp, tk, tp,
            )
            self.state = dataclasses.replace(
                self.state, side_caches=new_side_caches, side_prompt=prompt_a,
                side_plen=plen_a, side_step=step_a, side_tok=tok_a,
                side_pos=pos_a, side_active=act_a, side_samp=samp_a,
            )
            self.stats["aux_dispatches"] += 2
            self.stats["spawns"] += 1
            s = self.sides[lane]
            if s.agent_id in self.registry and self.registry.get(s.agent_id).status != REGISTERED:
                # the classic per-lane identity is still alive (hibernated, or
                # woken into another lane): mint a fresh one for this spawn
                s = AgentView(f"side{lane}.{self._agent_seq}", lane, "side")
                self._agent_seq += 1
                self.sides[lane] = s
            sp.set_metadata(agent=s.agent_id)
            s.task, s.text = task, ""
            self._decoders[s.agent_id] = self.tok.stream_decoder()
            s.parent_lane = parent.lane
            s.tokens = list(ids)
            s.position = parent.position  # continues the stream's positional frame
            s.active, s.steps = True, 0
            s.prompt_len = len(ids)
            self.prism.acquire(s.agent_id)
            self.registry.register(s.agent_id, "side")
            rec = self.registry.bind(s.agent_id, lane)
            rec.bound_tick = self.stats["ticks"]
            self.history.append(
                {"event": "spawn", "agent": s.agent_id, "task": task, "task_truncated": truncated}
            )
            return s

    # ------------------------------------------------------------------
    def retire_side(self, lane: int):
        """Cancel a stream without merging its thought (drops the lane at the
        next window boundary; its caches are rewritten on the next spawn)."""
        s = self.sides[lane]
        if not s.active:
            return
        self.drain()
        self.window.on_event()  # composition change: back to the base window
        act_a = self._jit_retire_side(self.state.side_active, lane)
        self.state = dataclasses.replace(self.state, side_active=act_a)
        self.stats["aux_dispatches"] += 1
        self.router.reset(s.agent_id)
        self.prism.release(s.agent_id)
        self.registry.release(s.agent_id)
        self._decoders.pop(s.agent_id, None)
        s.active = False
        self.history.append({"event": "retire", "agent": s.agent_id})

    def retire_main(self, lane: int):
        """Retire a river lane without replacing it (ISSUE 9: the serving
        front-end completes a request by freeing its lane for the next
        admission). Boundary op — drains first; refuses while side streams
        still target the lane for their merge (same identity-corruption
        hazard :meth:`hibernate` guards against)."""
        m = self.mains[lane]
        if not m.active:
            return
        if lane in self._lanes_with_children():
            raise ValueError(
                f"cannot retire main lane {lane}: side streams still "
                f"target it for their merge"
            )
        self.drain()
        self.window.on_event()  # composition change: back to the base window
        act_a = self._jit_retire_main(self.state.main_active, lane)
        self.state = dataclasses.replace(self.state, main_active=act_a)
        self.stats["aux_dispatches"] += 1
        m.text += self._decoder(m.agent_id).flush()  # final text == decode(tokens)
        self.router.reset(m.agent_id)
        self.prism.release(m.agent_id)
        self.registry.release(m.agent_id)
        self._decoders.pop(m.agent_id, None)
        m.active = False
        self.history.append({"event": "retire", "agent": m.agent_id})

    # ------------------------------------------------------------------
    # tiered memory (ISSUE 7): hibernate parks an agent's lane in the
    # SynapseStore (device → warm host RAM → cold zstd disk); wake prefetches
    # it back asynchronously and commits at a window boundary in run().
    # ------------------------------------------------------------------
    def _free_main_lane(self) -> int:
        for m in self.mains:
            if not m.active:
                return m.lane
        return -1

    def _lanes_with_children(self) -> set[int]:
        """Main lanes some side stream (live OR hibernated) will merge into.
        Hibernating such a main would let another agent claim the lane and
        receive the child's injection — identity corruption, so forbidden."""
        lanes = {s.parent_lane for s in self.sides if s.active}
        for rec in self.registry.with_status(HIBERNATED, "side"):
            lanes.add(rec.saved["view"].parent_lane)
        return lanes

    def _evict_lru_main(self) -> str | None:
        blocked = self._lanes_with_children()
        cands = [
            r for r in self.registry.with_status(ACTIVE, "main")
            if r.lane not in blocked
        ]
        if not cands:
            return None
        rec = min(cands, key=lambda r: r.last_event)
        self.hibernate(rec.agent_id)
        return rec.agent_id

    def hibernate(self, agent_id: str):
        """Demote an agent's lane off the device: gather its cache slice +
        per-lane scalars (ONE explicit host sync, at a drain boundary —
        never mid-window), park them in the store's warm tier, and free the
        lane. The router's retained tail for the agent survives on the host,
        so a tag split across hibernation still matches after wake."""
        rec = self.registry.get(agent_id)
        if rec.status != ACTIVE:
            raise ValueError(f"agent {agent_id!r} is not active (status={rec.status})")
        lane, kind = rec.lane, rec.kind
        view = (self.mains if kind == "main" else self.sides)[lane]
        assert view.agent_id == agent_id
        if kind == "main" and lane in self._lanes_with_children():
            raise ValueError(
                f"cannot hibernate {agent_id!r}: side streams still target "
                f"main lane {lane} for their merge"
            )
        self.drain()  # boundary-align: no mid-window host syncs
        self.window.on_event()
        if kind == "main":
            snap = self._jit_gather_main(self.state, lane)
            act_a = self._jit_retire_main(self.state.main_active, lane)
            self.state = dataclasses.replace(self.state, main_active=act_a)
            sp = self._main_sp[lane]
            self.mains[lane] = AgentView(f"main{lane}", lane, "main")
        else:
            snap = self._jit_gather_side(self.state, lane)
            act_a = self._jit_retire_side(self.state.side_active, lane)
            self.state = dataclasses.replace(self.state, side_active=act_a)
            sp = self._side_sp[lane]
            self.sides[lane] = AgentView(f"side{lane}", lane, "side")
        # durable bookkeeping rides the snapshot into the store (and, on
        # demotion, into the cold blob's frame metadata): everything needed
        # to re-adopt this agent after a process crash — the host-side view,
        # sampling params, and the router's retained tag tail
        meta = {
            "kind": kind,
            "view": _view_to_meta(view),
            "sampling": dataclasses.asdict(sp),
            "router": self.router.export_state(agent_id),
            "hibernate_tick": self.stats["ticks"],
            # a codepoint may be split across the hibernation boundary: the
            # decoder's buffered bytes ride the snapshot so the text stream
            # resumes bitwise even across a process crash (ISSUE 9)
            "utf8_pending": list(self._decoder(agent_id).pending),
        }
        self.store.put(agent_id, snap, meta=meta)  # device_get inside: the one sync
        self.stats["aux_dispatches"] += 2
        self.stats["host_syncs"] += 1
        self.stats["hibernates"] += 1
        view.active, view.lane = False, -1
        self.registry.hibernate(agent_id, {"view": view, "sampling": sp})
        self.prism.release(agent_id)
        self.history.append({"event": "hibernate", "agent": agent_id, "kind": kind})

    def wake(self, agent_id: str, *, wait: bool = False,
             deadline_s: float | None = None):
        """Promote a hibernated agent back toward a lane. Returns
        immediately after starting the async prefetch (a daemon thread pulls
        warm/cold bytes and lands them on device); the wake *commits* — the
        scatter into a free lane — at the next window boundary inside
        :meth:`run`, overlapping the in-flight window instead of flushing
        the pipeline. ``wait=True`` blocks until the agent is live.

        Failure semantics (ISSUE 8): transient prefetch failures retry with
        backoff inside the store; ``deadline_s`` (default: the engine's
        ``wake_deadline_s``) bounds the whole promotion. A wake that fails
        with the snapshot intact leaves the agent HIBERNATED (re-wakeable,
        counted in ``stats['wake_failures']``); permanent snapshot loss
        marks it LOST, frees no lane, and the engine keeps ticking."""
        rec = self.registry.get(agent_id)
        if rec.status == ACTIVE:
            return (self.mains if rec.kind == "main" else self.sides)[rec.lane]
        if rec.status != HIBERNATED:
            raise ValueError(
                f"agent {agent_id!r} has no hibernated context "
                f"(status={rec.status})"
            )
        if agent_id not in self._wake_tickets:
            sharding = self._rep_sharding

            def put_fn(host, _s=sharding):
                # runs on the prefetch thread; transfer_guard is thread-local
                # so these explicit copies never trip the engine's guard
                return jax.device_put(host, _s) if _s is not None else jax.device_put(host)

            self._wake_tickets[agent_id] = self.store.prefetch(
                agent_id, put_fn,
                deadline_s=self.wake_deadline_s if deadline_s is None else deadline_s,
            )
            self._pending_wakes.append(agent_id)
        if wait:
            self.flush_wakes()
            rec = self.registry.get(agent_id)
            if rec.status != ACTIVE:
                if rec.status == LOST:
                    raise SnapshotLostError(
                        agent_id, "context permanently lost during wake"
                    )
                raise RuntimeError(
                    f"wake of {agent_id!r} did not land "
                    f"(status={rec.status}: lane-starved or wake failed)"
                )
            return (self.mains if rec.kind == "main" else self.sides)[rec.lane]
        return rec

    def flush_wakes(self):
        """Block until every pending wake has committed (or is lane-starved)."""
        self.drain()
        self._commit_ready_wakes(wait=True)

    def _commit_ready_wakes(self, *, wait: bool = False, mark_fresh: bool = False) -> int:
        """Land prefetched wakes whose device buffers are ready. Callers
        guarantee a window boundary (ring cursor 0, no partial window): the
        scatter dispatches here are boundary ops, outside any overlap
        region, so the zero-transfer invariant of overlapped post-processing
        is untouched."""
        if not self._pending_wakes:
            return 0
        assert self._pending == 0, "wake commit must happen at a window boundary"
        # supervision: a dead prefetch thread is detected here (its in-flight
        # ticket fails instead of hanging a waiter) and respawned for the
        # still-queued tickets
        self.store.heal_worker()
        committed, still = 0, []
        for aid in self._pending_wakes:
            ticket = self._wake_tickets[aid]
            ticket.expire()  # host-side deadline: a stuck worker can't block this
            if not ticket.failed() and not (wait or ticket.ready()):
                still.append(aid)
                continue
            if wait and not ticket.ready():
                try:
                    ticket.result(timeout=ticket.remaining())
                except Exception:
                    pass  # terminal state is recorded on the ticket itself
                ticket.expire()
            if ticket.failed():
                self._fail_wake(aid, ticket.error)
                continue  # degraded, not pending: engine keeps ticking
            if self._commit_wake(aid, ticket, mark_fresh=mark_fresh):
                committed += 1
            else:
                still.append(aid)  # lane-starved: stays pending
        self._pending_wakes = still
        return committed

    def _fail_wake(self, agent_id: str, err: BaseException | None) -> None:
        """A wake ticket reached the terminal failed state. Degrade, never
        crash: a KeyError-family failure (quarantined blob, vanished file,
        dropped snapshot) means the context is unrecoverable — mark the
        agent LOST and move on; anything else (deadline, dead worker,
        exhausted transient retries) leaves the snapshot intact, so the
        agent stays HIBERNATED and a later wake() may succeed."""
        self._wake_tickets.pop(agent_id, None)
        if isinstance(err, KeyError) or agent_id not in self.store:
            self.registry.mark_lost(agent_id)
            self.store.drop(agent_id)
            self.router.reset(agent_id)
            self._decoders.pop(agent_id, None)
            self.stats["lost_agents"] += 1
            self.history.append(
                {"event": "lost", "agent": agent_id, "error": repr(err)}
            )
        else:
            self.stats["wake_failures"] += 1
            self.history.append(
                {"event": "wake_failed", "agent": agent_id, "error": repr(err)}
            )

    def _commit_wake(self, agent_id: str, ticket, *, mark_fresh: bool = False) -> bool:
        rec = self.registry.get(agent_id)
        kind = rec.kind
        lane = self._free_main_lane() if kind == "main" else self._free_side_lane()
        if lane < 0:
            return False
        part = ticket.result()  # device pytree (prefetch thread did the put)
        del self._wake_tickets[agent_id]
        saved = rec.saved
        view, sp = saved["view"], saved["sampling"]
        temp, tk, tp = lane_values(sp)
        if kind == "main":
            self._main_sp[lane] = sp
            caches = self._jit_wake_main_caches(self.state.main_caches, part["caches"], lane)
            tok_a, pos_a, act_a, hid_a, samp_a = self._jit_admit_main(
                self.state.main_tok, self.state.main_pos, self.state.main_active,
                self.state.main_hidden, self.state.main_samp,
                lane, part["tok"], part["pos"], part["hidden"], temp, tk, tp,
            )
            self.state = dataclasses.replace(
                self.state, main_caches=caches, main_tok=tok_a, main_pos=pos_a,
                main_active=act_a, main_hidden=hid_a, main_samp=samp_a,
            )
            self.mains[lane] = view
        else:
            self._side_sp[lane] = sp
            caches = self._jit_wake_side_caches(self.state.side_caches, part["caches"], lane)
            prompt_a, plen_a, step_a, tok_a, pos_a, act_a, samp_a = self._jit_admit_side(
                self.state.side_prompt, self.state.side_plen, self.state.side_step,
                self.state.side_tok, self.state.side_pos, self.state.side_active,
                self.state.side_samp,
                lane, part["prompt"], part["plen"], part["step"], part["tok"],
                part["pos"], temp, tk, tp,
            )
            hid_a = self._jit_set_side_hidden(self.state.side_hidden, lane, part["hidden"])
            self.state = dataclasses.replace(
                self.state, side_caches=caches, side_prompt=prompt_a,
                side_plen=plen_a, side_step=step_a, side_tok=tok_a,
                side_pos=pos_a, side_active=act_a, side_samp=samp_a,
                side_hidden=hid_a,
            )
            self.sides[lane] = view
        view.lane, view.active = lane, True
        self.stats["aux_dispatches"] += 2 if kind == "main" else 3
        self.stats["wakes"] += 1
        self.prism.acquire(agent_id)
        bound = self.registry.bind(agent_id, lane)
        bound.bound_tick = self.stats["ticks"]
        self.store.drop(agent_id)
        self.window.on_event()
        if mark_fresh:
            # a fetched-but-unprocessed window exists: this lane was not on
            # device for it, so its mirror advancement must be skipped once
            self._fresh_wakes.add((kind, lane))
        self.history.append({"event": "wake", "agent": agent_id, "lane": lane})
        return True

    def adopt_hibernated(self, *, kinds=("main", "side")) -> list[str]:
        """Crash-recovery re-adoption (ISSUE 8): after ``store.recover()``
        rebuilt the cold index from disk, re-register every snapshot whose
        durable metadata names an agent this engine does not already hold,
        restoring the host-side view, sampling params, and the router's
        retained tag tail. Adopted agents come back HIBERNATED — a normal
        :meth:`wake` makes them live, and their greedy streams replay
        bitwise as if the process never died. Returns the adopted ids."""
        adopted = []
        for key in self.store.keys():
            meta = self.store.meta_of(key)
            if not isinstance(meta, dict) or meta.get("kind") not in kinds:
                continue
            if key in self.registry and self.registry.get(key).status in (
                ACTIVE, HIBERNATED,
            ):
                continue  # a live identity wins over its stale snapshot
            view = _view_from_meta(meta["view"])
            sp = SamplingParams(**meta["sampling"])
            self.registry.register(key, meta["kind"])
            self.registry.hibernate(key, {"view": view, "sampling": sp})
            if meta.get("router"):
                self.router.restore_state(key, meta["router"])
            if meta.get("utf8_pending"):
                # resume mid-codepoint: the decoder picks the byte stream
                # back up exactly where the dead process left it
                self._decoder(key).restore(bytes(meta["utf8_pending"]))
            self.stats["recoveries"] += 1
            self.history.append({"event": "adopt", "agent": key})
            adopted.append(key)
        return adopted

    def _auto_hibernate(self) -> int:
        """Idle-ticks demotion policy: mains whose last control event
        (submit/wake) is more than ``hibernate_idle_ticks`` virtual ticks
        old spill to the warm tier. Runs only at fully-synced boundaries
        (views current, nothing in flight)."""
        if self.hibernate_idle_ticks is None:
            return 0
        blocked = self._lanes_with_children()
        due = [
            r for r in self.registry.with_status(ACTIVE, "main")
            if self.stats["ticks"] - r.bound_tick >= self.hibernate_idle_ticks
            and r.lane not in blocked
        ]
        for r in due:
            self.hibernate(r.agent_id)
        return len(due)

    def _boundary_ops(self, *, wait: bool = False, hibernate_ok: bool = True) -> int:
        """Window-boundary control plane: idle-ticks demotions, then wake
        commits. ``wait=True`` blocks on outstanding prefetch tickets — used
        when the engine is otherwise idle so a wake-only run makes progress."""
        with _span("engine.boundary"):
            did = 0
            if hibernate_ok:
                did += self._auto_hibernate()
            did += self._commit_ready_wakes(wait=wait and bool(self._pending_wakes))
            if self.admission_hook is not None:
                # front-end admission control (ISSUE 9): retire finished
                # request lanes and admit queued work — all boundary ops, so
                # the pipelined window is never flushed by an admission
                did += bool(self.admission_hook())
            return did

    # ------------------------------------------------------------------
    def _merge_sides(self, finished: list):
        """Merge a drain's finished sides: one batched merge program and one
        retire of their lanes per chunk of at most ``MERGE_BATCH`` thoughts,
        then ONE host read of every gate. History entries, releases and
        router resets follow in ``finished`` order."""
        parents = dict.fromkeys(self.mains[s.parent_lane].agent_id for s, _ in finished)
        with _span("engine.merge", n=len(finished), parent=",".join(parents)):
            if not self._merge_widths_ready:
                # how many sides finish in one drain varies with their task
                # lengths: compile every width a drain can need now, with
                # programs that merge nothing, so none compiles mid-stream
                top = 1 << (min(self.max_side, MERGE_BATCH) - 1).bit_length()
                for i in range(top.bit_length()):
                    self._merge_program([], 1 << i, finished[0][0].lane)
                self._merge_widths_ready = True
            results = []
            for c in range(0, len(finished), MERGE_BATCH):
                chunk = finished[c:c + MERGE_BATCH]
                width = 1 << (len(chunk) - 1).bit_length()
                results.append(self._merge_program(chunk, width, chunk[0][0].lane))
                self.stats["merge_dispatches"] += 1
            results = jax.device_get(results)  # drain-time sync: every gate at once
            self.stats["host_syncs"] += 1
            # chunks are full but the last, whose padding rows come at its end
            accepts = np.concatenate([a for a, _ in results])
            scores = np.concatenate([sc for _, sc in results])
            for k, (s, thought) in enumerate(finished):
                accepted = bool(accepts[k])
                self.stats["merges"] += 1
                self.stats["merges_accepted"] += int(accepted)
                self.history.append(
                    {
                        "event": "merge",
                        "agent": s.agent_id,
                        "accepted": accepted,
                        "gate_score": float(scores[k]),
                        "thought": thought[:80],
                    }
                )
                self.router.reset(s.agent_id)
                self.prism.release(s.agent_id)
                self.registry.release(s.agent_id)
                self._decoders.pop(s.agent_id, None)
                s.active = False

    def _merge_program(self, rows: list, width: int, pad_lane: int):
        """Dispatch the merge program over ``rows`` ([(side, thought)]),
        padded to ``width`` rows that merge nothing, and the retire of the
        rows' side lanes, whose padding repeats ``pad_lane`` (a lane being
        retired anyway). Returns the program's device (accept, score)."""
        T = self.inject_tokens
        toks = np.full((width, T), self.tok.pad_id, np.int32)
        parent = np.zeros(width, np.int32)
        vpos = np.zeros(width, np.int32)  # virtual index: the parent's position
        valid = np.zeros(width, bool)
        lanes = np.full(width, pad_lane, np.int32)
        for i, (s, thought) in enumerate(rows):
            ids = self.tok.encode(thought)[-T:]
            toks[i, :len(ids)] = ids
            parent[i], vpos[i] = s.parent_lane, self.mains[s.parent_lane].position
            valid[i], lanes[i] = True, s.lane
        new_caches, accept, score = self._jit_merge(
            self._params, self.state.main_caches, self.state.main_hidden,
            toks, parent, vpos, valid,
        )
        act_a = self._jit_retire_side(self.state.side_active, lanes)
        self.state = dataclasses.replace(self.state, main_caches=new_caches, side_active=act_a)
        self.stats["aux_dispatches"] += 2
        return accept, score

    # ------------------------------------------------------------------
    def memory_report(self) -> dict:
        self.drain()  # lazy flush: reporting is a natural sync boundary
        per_agent = {}
        for m in self.mains:
            if m.active:
                per_agent[m.agent_id] = tree_bytes(_lane_slice(self.state.main_caches, m.lane))
        for s in self.sides:
            if s.active:
                per_agent[s.agent_id] = tree_bytes(_lane_slice(self.state.side_caches, s.lane))
        # hibernated agents are absent from per_agent by construction: their
        # device contribution is exactly the zero bytes the tiers promise
        rep = self.prism.memory_report(
            per_agent,
            store_report=self.store.report(),
            agents=self.registry.counts(),
        )
        rep["per_agent_bytes"] = dict(per_agent)
        # the serving-dtype weight cast is a REAL resident copy on backends
        # where compute dtype != param dtype (identity casts alias, cost 0);
        # Eq. 1 accounting must include it
        cast_extra = sum(
            b.size * b.dtype.itemsize
            for a, b in zip(jax.tree.leaves(self.prism.params), jax.tree.leaves(self._params))
            if b is not a
        )
        rep["serving_weight_bytes"] = cast_extra
        rep["total_bytes"] += cast_extra
        return rep
