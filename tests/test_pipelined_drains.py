"""Pipelined drains over ``sync_every`` macro windows.

The contract this suite pins down:

* CHURN PARITY — sequences of submit/spawn/merge/retire interleaved with
  ``run(n)`` produce bitwise-identical greedy token streams (main AND side)
  and identical control-plane histories on the pipelined engine vs the
  serial reference (``pipeline=False``), including partial windows and
  lane restarts;
* DISPATCH ACCOUNTING — ``run(n)`` from a boundary issues exactly
  ``ceil(n / sync_every)`` dispatches and counts every tick it advanced;
* OVERLAP — the pipelined drain's post-processing region (router scan,
  UTF-8 decode, bookkeeping) issues ZERO device transfers while the next
  window executes — enforced with ``jax.transfer_guard("disallow")``, not
  just the engine's self-reported counters;
* SERVER — BatchServer's pipelined decode matches its serial loop bitwise,
  and a recycled lane never inherits the previous request's sampling params
  (the samp cache invalidates on every composition change).
"""
import dataclasses
import math

import jax
import pytest

from conftest import hypothesis_tools
from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.data.tokenizer import ByteTokenizer
from repro.models import model as model_lib
from repro.serving.sampler import SamplingParams
from repro.serving.server import BatchServer


def _cfg():
    return dataclasses.replace(
        get_config("qwen2.5-0.5b", reduced=True), compute_dtype="float32"
    )


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, *, pipeline, sync_every=4,
            side_max_steps=6, sampling=SamplingParams(greedy=True),
            side_sampling=None):
    prism = Prism(params, cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    return CortexEngine(
        prism, tok, n_main=1, max_side=2, main_capacity=128,
        side_max_steps=side_max_steps, inject_tokens=8, theta=-1.0,
        sampling=sampling, side_sampling=side_sampling,
        sync_every=sync_every, pipeline=pipeline,
    )


def _apply(eng, ops):
    """One churn script, engine-agnostic: the same op sequence must drive
    every engine variant through identical control-plane decisions."""
    deltas = []  # (op, n, tick_dispatches delta) for run ops
    for op in ops:
        if op[0] == "submit":
            eng.submit(op[1], lane=0)
        elif op[0] == "run":
            d0 = eng.stats["tick_dispatches"]
            eng.run(op[1])
            deltas.append((op[1], eng.stats["tick_dispatches"] - d0))
        elif op[0] == "spawn":
            # drain-boundary spawn, bypassing the router (direct churn)
            eng._spawn_side(eng.mains[0], op[1])
        elif op[0] == "retire":
            eng.retire_side(op[1])
    return deltas


def _streams(eng):
    return (
        list(eng.mains[0].tokens),
        [list(s.tokens) for s in eng.sides],
        [(e["event"], e.get("accepted")) for e in eng.history],
    )


CHURN_SCRIPT = [
    ("submit", "hello [TASK: go] world"),
    ("run", 7),               # partial trailing window
    ("spawn", "second probe"),
    ("run", 9),               # budget completions -> merges mid-script
    ("retire", 0),
    ("retire", 1),
    ("run", 5),
    ("submit", "calm text with no tags at all"),  # lane restart
    ("run", 24),              # trigger-free stretch: drains overlap
    ("run", 3),
]


@pytest.fixture(scope="module")
def churn(setup):
    cfg, params = setup
    engines = {
        "serial": _engine(cfg, params, pipeline=False),
        "pinned": _engine(cfg, params, pipeline=True),
    }
    deltas = {k: _apply(e, CHURN_SCRIPT) for k, e in engines.items()}
    return engines, deltas


def test_churn_parity_bitwise(churn):
    """Pipelined == serial path, token-for-token and event-for-event,
    across spawn/merge/retire churn."""
    engines, _ = churn
    ref = _streams(engines["serial"])
    assert _streams(engines["pinned"]) == ref
    # the script actually exercised the control plane
    events = [e for e, _ in ref[2]]
    assert "spawn" in events and "merge" in events and "retire" in events


def test_churn_dispatch_accounting(churn):
    """Per run(n) from a boundary: the pipelined engine issues exactly
    ceil(n/sync_every) dispatches."""
    engines, deltas = churn
    for n, d in deltas["pinned"]:
        assert d == math.ceil(n / 4), (n, d)
    # serial and pinned agree exactly (pipelining reorders host work only)
    assert deltas["serial"] == deltas["pinned"]


def test_churn_accounts_every_tick(churn):
    engines, _ = churn
    asked = sum(op[1] for op in CHURN_SCRIPT if op[0] == "run")
    for eng in engines.values():
        assert eng.stats["ticks"] == asked
    assert engines["serial"].stats["overlapped_drains"] == 0
    assert engines["pinned"].stats["overlapped_drains"] > 0


def test_overlapped_budget_cap_sees_pending_window(setup):
    """In the overlapped branch the gate runs BEFORE window t's
    post-processing, so its side step-budget check must count window t's
    still-unprocessed ring tokens. An odd budget over a 2-tick window ends
    mid-window: the merge must land on the serial engine's tick, or the
    main stream diverges."""
    cfg, params = setup
    kw = dict(sync_every=2, side_max_steps=9)
    serial = _engine(cfg, params, pipeline=False, **kw)
    pinned = _engine(cfg, params, pipeline=True, **kw)
    for eng in (serial, pinned):
        eng.submit("hello [TASK: go] world", lane=0)
        eng.run(48)
    assert _streams(pinned) == _streams(serial)
    assert any(e == "merge" for e, _ in _streams(serial)[2])
    assert pinned.stats["overlapped_drains"] > 0


def test_overlap_region_issues_no_transfers(setup):
    """The heart of the pipeline: with window t's rings fetched and the
    gate green, dispatching window t+1 AND doing window t's full host
    post-processing must not touch the device<->host boundary (the fetch
    itself, outside the guard, is the one blocking sync per window)."""
    cfg, params = setup
    eng = _engine(cfg, params, pipeline=True)
    m = eng.submit("transfer guard probe, no tags", lane=0)
    eng.run(8)  # warm the scanned dispatch + drain paths
    base = dict(eng.stats)
    n_tok = len(m.tokens)
    eng._dispatch_window(4)                  # window t
    rings = eng._fetch_rings()               # pipeline sync point
    with jax.transfer_guard("disallow"):
        assert eng._gate(rings, 4)
        eng._dispatch_window(4)              # window t+1 on the device
        eng._postprocess(rings, 4, overlapped=True)  # overlapped host work
    assert len(m.tokens) == n_tok + 4        # window t fully accounted
    assert eng.stats["host_syncs"] == base["host_syncs"] + 1
    eng.drain()                              # pipeline tail
    assert len(m.tokens) == n_tok + 8
    assert eng.stats["host_syncs"] == base["host_syncs"] + 2


def test_gate_is_conservative_on_trigger_bytes(setup):
    """Windows whose raw tokens could complete a tag, or whose sides reach
    their budget, must NOT overlap: the gate inspects ring bytes + the
    router's plausibility hint before the next dispatch is allowed."""
    cfg, params = setup
    eng = _engine(cfg, params, pipeline=True)
    eng.submit("x [TASK: go] y", lane=0)
    n0 = eng.stats["host_syncs"]
    eng._dispatch_window(4)
    rings = eng._fetch_rings()
    assert eng.stats["host_syncs"] == n0 + 1
    # forge a '[' into the main lane's window: gate must refuse to overlap
    forged = (rings[0].copy(), rings[1].copy())
    forged[0][0, 1] = ord("[")
    assert not eng._gate(forged, 4)
    # a ']' alone is only unsafe while the router tail holds an open '['
    forged2 = (rings[0].copy(), rings[1].copy())
    forged2[0][0, 1] = ord("]")
    rid = eng.mains[0].agent_id
    eng.router._tails[rid] = ("... [TA", 0)
    assert eng.router.plausible(rid)
    assert not eng._gate(forged2, 4)
    eng.router._tails[rid] = ("... [TASK: x] b", 0)
    assert not eng.router.plausible(rid)  # closed tail: ']' alone is safe
    # a side one token from its budget forces the serial path
    side = next(s for s in eng.sides if s.active)
    real_tokens = side.tokens
    try:
        side.tokens = real_tokens + [0] * (
            eng.side_max_steps + side.prompt_len - len(real_tokens)
        )
        assert not eng._gate(rings, 4)
    finally:
        side.tokens = real_tokens
    eng.drain()


def test_mixed_sampling_lanes_inside_macro_windows(setup):
    """Greedy river + filtered stochastic streams sharing one scanned
    window: every lane's draws — greedy AND filtered — are bitwise
    identical to the serial reference (the PRNG splits per virtual tick
    and the static sampler flags only change at drains, so the pipelined
    order of host work cannot move a draw)."""
    cfg, params = setup
    kw = dict(side_max_steps=12,
              side_sampling=SamplingParams(temperature=1.1, top_k=12))
    serial = _engine(cfg, params, pipeline=False, **kw)
    pinned = _engine(cfg, params, pipeline=True, **kw)
    for eng in (serial, pinned):
        eng.submit("mixed [TASK: explore] lanes", lane=0)
        eng.run(28)
    assert _streams(pinned) == _streams(serial)
    side = next(s for s in pinned.sides if s.tokens)
    assert len(side.tokens) > side.prompt_len       # stochastic lane ran
    assert any(e == "merge" for e, _ in _streams(pinned)[2])


def test_batchserver_pipeline_matches_serial(setup):
    """BatchServer's speculative pipelined decode == serial tick() loop,
    bitwise, across lane recycling (more requests than lanes)."""
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    reqs = [
        ("first request", 6, SamplingParams(greedy=True)),
        ("second request", 9, SamplingParams(temperature=0.9, top_k=8)),
        ("third request", 5, None),
        ("fourth request", 7, SamplingParams(temperature=1.2, top_p=0.9)),
    ]
    outs = []
    for pipeline in (True, False):
        srv = BatchServer(params, cfg, tok, n_lanes=2, capacity=64,
                          sampling=SamplingParams(temperature=1.0), seed=7)
        for prompt, mnt, sp in reqs:
            srv.submit(prompt, max_new_tokens=mnt, sampling=sp)
        done = srv.run_until_done(max_ticks=200, pipeline=pipeline)
        outs.append(sorted((r.rid, tuple(r.tokens)) for r in done))
        if pipeline:
            assert srv.stats["overlapped"] > 0
    assert outs[0] == outs[1]


def test_recycled_lane_never_inherits_sampling(setup):
    """Regression (ISSUE 5): after a greedy request completes, the lane's
    stacked sampling row must be rebuilt for the next occupant — admission,
    completion, and mid-flight cancel all invalidate the samp cache."""
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    srv = BatchServer(params, cfg, tok, n_lanes=1, capacity=64,
                      sampling=SamplingParams(temperature=1.0))
    srv.submit("greedy req", max_new_tokens=3, sampling=SamplingParams(greedy=True))
    srv.run_until_done(max_ticks=50)
    assert not srv._samp_cache.valid          # completion invalidated
    srv.submit("default req", max_new_tokens=3)
    srv._admit()
    lanes_samp, use_filters, any_greedy = srv._samp_cache.get(srv._lane_params)
    assert float(lanes_samp.temperature[0]) == 1.0  # NOT the greedy 0.0
    assert not any_greedy and not use_filters
    # mid-flight retirement under the pipelined drain invalidates too
    rid = srv.lanes[0].rid
    assert srv.cancel(rid)
    assert not srv._samp_cache.valid
    assert srv.cancel(rid) is False            # already gone


# ---------------------------------------------------------------------------
# property-based churn stress (hypothesis optional — gated via conftest)
# ---------------------------------------------------------------------------
given, settings, st = hypothesis_tools()

_PROP = {}  # kind -> engine, reused across examples (jit caches are hot)


def _prop_engine(setup, kind):
    cfg, params = setup
    if kind not in _PROP:
        _PROP[kind] = _engine(cfg, params, pipeline=kind != "serial",
                              side_max_steps=4)
    eng = _PROP[kind]
    for s in eng.sides:  # clear streams left over from the previous example
        if s.active:
            eng.retire_side(s.lane)
    return eng


_OP = st.one_of(
    st.tuples(st.just("run"), st.integers(min_value=1, max_value=11)),
    st.tuples(st.just("spawn"), st.sampled_from(["alpha", "beta"])),
    st.tuples(st.just("retire"), st.integers(min_value=0, max_value=1)),
    st.tuples(st.just("submit"), st.sampled_from(
        ["plain words", "tagged [TASK: t] words"])),
)


@settings(max_examples=5, deadline=None)
@given(
    prompt=st.text(alphabet="abcdef ", min_size=1, max_size=10),
    with_task=st.booleans(),
    ops=st.lists(_OP, min_size=2, max_size=6),
)
def test_property_churn_parity(setup, prompt, with_task, ops):
    """Randomized lane churn: submit/spawn/merge/retire interleaved with
    run(n) — the pipelined engine must equal the serial reference
    token-for-token (main and side lanes) with the same dispatch count."""
    script = [("submit", prompt + (" [TASK: check] tail" if with_task else ""))]
    script += list(ops)
    results, deltas = {}, {}
    for kind in ("serial", "pinned"):
        eng = _prop_engine(setup, kind)
        h0 = len(eng.history)
        deltas[kind] = _apply(eng, script)
        m, sides, hist = _streams(eng)
        results[kind] = (m, sides, hist[h0:])
    assert results["pinned"] == results["serial"]
    for (n, d_pin), (_, d_ser) in zip(deltas["pinned"], deltas["serial"]):
        assert d_pin == d_ser == math.ceil(n / 4)
