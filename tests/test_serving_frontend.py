"""Serving front-end: admission, fairness, streaming, SLOs (ISSUE 9).

The contract this suite pins down:

* FAIRNESS — `FairQueue` admits in weighted-fair order: with tenants at
  4:1 weights and equal budgets, admitted token budgets track the weight
  ratio over any saturated prefix; higher priority classes preempt WFQ
  order; and NO request waits more than ``starvation_rounds`` admission
  decisions, whatever its tenant's weight or its priority (the starvation
  bound), with promotions counted;
* ADMISSION — submits past ``max_queue`` raise :class:`AdmissionError`
  and are counted per tenant (explicit back-pressure, never silent drop);
  admissions land only through the backends' boundary hooks;
* STREAMING — a request's :class:`TokenStream` accumulates text that is
  bitwise equal to the backend's final ``decode(tokens)`` — on the
  BatchServer path (per-step chunks, pipelined) and the engine path
  (per-drain chunks, flush tail delivered at retirement) — and handles
  can be consumed from another thread while the pump runs;
* CANCELLATION — queued and running requests cancel observably: the
  stream closes with status "cancelled";
* SLOs — :meth:`ServingFrontend.metrics` reports per-request TTFT /
  queue-wait / TPOT, per-tenant token shares summing to 1, fairness
  counters, and p50/p99 tick latency — the exact section
  benchmarks/bench_serving.py records into BENCH_throughput.json.
"""
import dataclasses
import threading

import jax
import pytest

from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.data.tokenizer import ByteTokenizer
from repro.models import model as model_lib
from repro.serving.frontend import (
    AdmissionError,
    FairQueue,
    FrontRequest,
    ServeStalled,
    ServingFrontend,
    TokenStream,
)
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


def _req(rid, tenant, priority=0, budget=10):
    return FrontRequest(rid, "p", tenant, priority, budget, None, TokenStream(rid))


# ---------------------------------------------------------------------------
# FairQueue units (no model)
# ---------------------------------------------------------------------------

def test_fair_queue_weighted_shares_track_weights():
    # bound high enough that aging never fires: pure WFQ order under a
    # standing backlog (the starvation bound gets its own test below)
    fq = FairQueue({"a": 4.0, "b": 1.0}, starvation_rounds=1000)
    for i in range(40):
        fq.push(_req(100 + i, "a"))
        fq.push(_req(200 + i, "b"))
    admitted = [fq.pop().tenant for _ in range(40)]
    # over any saturated prefix the 4:1 ratio holds to within one quantum
    for n in (5, 10, 20, 40):
        a = admitted[:n].count("a")
        assert abs(a / n - 0.8) <= 1 / n + 1e-9, f"prefix {n}: {a}/{n}"


def test_fair_queue_priority_preempts_wfq():
    fq = FairQueue({"a": 4.0, "b": 1.0})
    for i in range(4):
        fq.push(_req(10 + i, "a", priority=0))
    fq.push(_req(99, "b", priority=5))
    assert fq.pop().rid == 99  # high class wins despite b's 1/5 weight


def test_fair_queue_starvation_bound_holds():
    fq = FairQueue({"hog": 100.0, "tiny": 0.01}, starvation_rounds=8)
    fq.push(_req(1, "tiny", priority=-1, budget=10))
    for i in range(200):
        fq.push(_req(100 + i, "hog", priority=3, budget=10))
    waited = None
    for n in range(1, 50):
        if fq.pop().rid == 1:
            waited = n
            break
    # despite a 10000x weight disadvantage AND a lower priority class, the
    # request is admitted at EXACTLY the bound (ISSUE 10 bugfix: `rounds`
    # is incremented before the comparison, so the old `>` admitted one
    # decision late). Priority keeps normal order off `tiny` entirely, so
    # equality proves the promotion fired at the boundary and not before.
    assert waited == fq.starvation_rounds
    assert fq.starvation_promotions == 1


def test_fair_queue_starvation_boundary_exact():
    # pin the boundary from both sides: a request aged starvation_rounds - 1
    # is NOT promoted, the same request one decision later IS
    fq = FairQueue({"hog": 100.0, "tiny": 0.01}, starvation_rounds=4)
    fq.push(_req(1, "tiny", priority=-1))
    for i in range(20):
        fq.push(_req(100 + i, "hog", priority=3))
    for n in range(1, fq.starvation_rounds):
        assert fq.pop().rid != 1, f"promoted early at decision {n}"
    assert fq.starvation_promotions == 0
    assert fq.pop().rid == 1  # decision #starvation_rounds: promoted
    assert fq.starvation_promotions == 1


def test_percentile_nearest_rank_deterministic():
    from repro.serving.frontend import percentile

    # nearest-rank: rank = ceil(q/100 * n), 1-based. int(round(...)) used
    # banker's rounding, which picked rank 3 for p50 of an even-length
    # sample (round(1.5) == 2 -> index 2); the deterministic rule says 2.
    assert percentile([1, 2, 3, 4], 50) == 2.0
    assert percentile([1, 2, 3, 4], 99) == 4.0
    assert percentile([1, 2, 3, 4], 100) == 4.0
    assert percentile([1, 2], 50) == 1.0
    assert percentile([7], 99) == 7.0
    assert percentile([], 50) == 0.0
    # percentiles stay monotone in q
    s = [5, 1, 9, 3, 7, 2]
    qs = [0, 10, 25, 50, 75, 90, 99, 100]
    vals = [percentile(s, q) for q in qs]
    assert vals == sorted(vals)


def test_fair_queue_idle_tenant_banks_no_credit():
    fq = FairQueue({"a": 1.0, "b": 1.0})
    for i in range(10):
        fq.push(_req(i, "a"))
    for _ in range(10):
        fq.pop()  # a's vtime advances while b is idle
    fq.push(_req(50, "a"))
    fq.push(_req(51, "b"))
    # b returns from idle floored to the virtual floor: it gets NO credit for
    # the 10 admissions it sat out — both tenants are served within two pops
    # instead of b monopolizing ten in a row
    assert {fq.pop().rid, fq.pop().rid} == {50, 51}


def test_fair_queue_remove_and_len():
    fq = FairQueue()
    fq.push(_req(1, "t"))
    fq.push(_req(2, "t"))
    assert len(fq) == 2
    assert fq.remove(1).rid == 1
    assert fq.remove(1) is None
    assert len(fq) == 1 and fq.pop().rid == 2


# ---------------------------------------------------------------------------
# front-end over BatchServer
# ---------------------------------------------------------------------------

def _frontend(cfg, params, **kw):
    srv = BatchServer(params, cfg, ByteTokenizer(cfg.vocab_size), n_lanes=2,
                      capacity=128, sampling=SamplingParams(greedy=True))
    return ServingFrontend(srv, **kw)


def test_batch_stream_bitwise_and_slo_metrics(setup):
    cfg, params = setup
    fe = _frontend(cfg, params, tenants={"gold": 4.0, "free": 1.0})
    tok = fe.backend.tok
    streams = {}
    for i in range(4):
        tenant = "gold" if i % 2 == 0 else "free"
        streams[i] = fe.submit(f"prompt number {i} é∑", tenant=tenant,
                               max_new_tokens=16)
    fe.serve(pipeline=True)
    finished = {r.rid: r for r in fe.backend.finished}
    for s in streams.values():
        assert s.done and s.status == "ok"
        req = finished[fe.requests[s.rid].backend_id]
        # streamed chunks concatenate to the one-shot decode, bitwise
        assert s.text == req.text == tok.decode(req.tokens[req.prompt_len:])
    m = fe.metrics()
    assert m["completed"] == 4 and m["backend"] == "batch"
    for row in m["requests"]:
        assert row["ttft_s"] is not None and row["ttft_s"] >= 0
        assert row["queue_wait_s"] is not None
        assert row["tokens_out"] == 16
    shares = {t: v["token_share"] for t, v in m["tenants"].items()}
    assert abs(sum(shares.values()) - 1.0) < 1e-9
    assert m["tick_latency_s"]["n"] > 0
    assert m["tick_latency_s"]["p99"] >= m["tick_latency_s"]["p50"] > 0
    assert m["fairness"]["admission_rounds"] == 4


@pytest.mark.parametrize("backend", ["batch", "engine"])
def test_tick_latency_samples_each_step_chunk(setup, backend):
    # one sample per step() chunk: its wall seconds over the ticks it
    # advanced, so one slow chunk lifts p99 above p50 whatever its commits
    cfg, params = setup
    now = [0.0]
    if backend == "batch":
        fe = _frontend(cfg, params, clock=lambda: now[0])
        name = "run_until_done"
    else:
        eng = CortexEngine(
            Prism(params, cfg), ByteTokenizer(cfg.vocab_size), n_main=2, max_side=2,
            main_capacity=128, sampling=SamplingParams(greedy=True), sync_every=4,
        )
        fe = ServingFrontend(eng, clock=lambda: now[0])
        name = "run"
    inner, calls = getattr(fe.backend, name), []

    def timed(*a, **kw):
        out = inner(*a, **kw)
        calls.append(1)
        now[0] += 1.0 if len(calls) == 2 else 0.01  # the second chunk stalls
        return out

    setattr(fe.backend, name, timed)
    fe.submit("slow chunk one", max_new_tokens=24)
    fe.submit("slow chunk two", max_new_tokens=24)
    advanced = []
    while fe.pending():
        advanced.append(fe.step(4))
    assert len(advanced) >= 3 and all(advanced)
    lat = fe.metrics()["tick_latency_s"]
    assert lat["n"] == len(advanced)
    assert lat["p99"] == pytest.approx(1.0 / advanced[1])
    assert lat["p50"] == pytest.approx(0.01 / 4) and lat["p99"] > lat["p50"]


def test_batch_stream_consumed_from_other_thread(setup):
    cfg, params = setup
    fe = _frontend(cfg, params)
    s = fe.submit("threaded stream ∑", max_new_tokens=12)
    got = []
    t = threading.Thread(target=lambda: got.extend(s))
    t.start()
    fe.serve()
    t.join(timeout=30)
    assert not t.is_alive()
    assert "".join(got) == s.text and s.done


def test_batch_cancel_queued_and_running(setup):
    cfg, params = setup
    fe = _frontend(cfg, params)  # 2 lanes
    s = [fe.submit(f"cancel target {i}", max_new_tokens=32) for i in range(3)]
    fe._admit_batch()  # boundary hook: fills both lanes, rid 3 stays queued
    assert fe.cancel(3)  # queued: closes immediately
    assert s[2].done and s[2].status == "cancelled"
    assert fe.cancel(1)  # running: BatchServer.cancel -> tap closes stream
    assert s[0].done and s[0].status == "cancelled"
    assert not fe.cancel(1)  # already terminal
    fe.serve()
    assert s[1].done and s[1].status == "ok"
    m = fe.metrics()
    statuses = sorted(r["status"] for r in m["requests"])
    assert statuses == ["cancelled", "cancelled", "ok"]
    assert fe.backend.stats["cancelled"] == 1  # only the running one reached it


def test_admission_error_on_full_queue(setup):
    cfg, params = setup
    fe = _frontend(cfg, params, max_queue=2)
    fe.submit("a", tenant="t")
    fe.submit("b", tenant="t")
    with pytest.raises(AdmissionError):
        fe.submit("c", tenant="t")
    assert fe.metrics()["tenants"]["t"]["rejected"] == 1
    fe.serve()  # the two admitted ones still complete


def test_engine_tap_records_ttft_only_with_tokens(setup):
    # ISSUE 10 bugfix: a drain callback that delivered NO tokens for this
    # lane must not stamp t_first — TTFT means "a generated token exists"
    import types

    cfg, params = setup
    fe = _frontend(cfg, params)
    req = _req(1, "t")
    fe.requests[1] = req
    fe.live["aid"] = req
    view = types.SimpleNamespace(agent_id="aid", kind="main")
    fe._engine_tap(view, "", [])
    assert req.t_first is None and req.tokens_out == 0
    fe._engine_tap(view, "xy", [1, 2])
    assert req.t_first is not None and req.tokens_out == 2
    t0 = req.t_first
    fe._engine_tap(view, "z", [3])
    assert req.t_first == t0  # first-token time never moves


def test_stream_backlog_overflow_flags_cancel(setup):
    # a consumer that stops reading past max_buffered_chars gets its
    # request flagged; the boundary cancel retires ONLY that request
    cfg, params = setup
    fe = _frontend(cfg, params)
    stalled = fe.submit("stalled consumer", max_new_tokens=64,
                        max_buffered_chars=4)
    healthy = fe.submit("healthy consumer", max_new_tokens=16)
    fe.serve()
    assert stalled.done and stalled.status == "cancelled"
    assert stalled.overflowed
    assert healthy.done and healthy.status == "ok"
    assert fe.backend.stats["cancelled"] == 1
    req = fe.requests[healthy.rid]
    fin = {r.rid: r for r in fe.backend.finished}[req.backend_id]
    # the healthy stream is untouched by the neighbor's overflow-cancel
    assert healthy.text == fin.text == \
        fe.backend.tok.decode(fin.tokens[fin.prompt_len:])


@pytest.mark.parametrize("pipeline", [False, True])
def test_admission_under_parked_and_resuming_lanes(setup, pipeline):
    """`_admit_batch`'s free-lane computation subtracts queued prompts AND
    in-flight resume tickets: a resuming lane must not be double-booked
    (over-admission), and a parked-without-resume lane must not be
    stranded (under-admission)."""
    cfg, params = setup
    srv = BatchServer(params, cfg, ByteTokenizer(cfg.vocab_size), n_lanes=2,
                      capacity=128, sampling=SamplingParams(greedy=True))
    fe = ServingFrontend(srv, tenants={"t": 1.0})
    s1 = fe.submit("park victim one", tenant="t", max_new_tokens=24)
    s2 = fe.submit("steady stream two", tenant="t", max_new_tokens=24)
    srv._admit()  # boundary: both admitted onto the two lanes
    assert fe.metrics()["fairness"]["admission_rounds"] == 2
    rid1 = fe.requests[s1.rid].backend_id

    # --- resuming: the freed lane is reserved by the resume ticket ------
    assert srv.park(rid1)
    assert srv.unpark(rid1)  # lane 0 free, but a resume ticket holds it
    s3 = fe.submit("queued three", tenant="t", max_new_tokens=12)
    admitted = fe._admit_batch()
    assert admitted == 0, "over-admitted into a lane reserved by a resume"
    assert len(srv.queue) == 0 and len(fe.fq) == 1

    # the resume lands at the next boundary, then the queued request takes
    # whatever frees up — nobody is stranded
    fe.serve(pipeline=pipeline)
    assert s1.done and s1.status == "ok"
    assert s2.done and s2.status == "ok"
    assert s3.done and s3.status == "ok"
    assert fe.pending() == 0 and len(fe.fq) == 0

    # --- parked without resume: the freed lane is genuinely free --------
    s4 = fe.submit("park victim four", tenant="t", max_new_tokens=48)
    s5 = fe.submit("waiter five", tenant="t", max_new_tokens=8)
    srv._admit()
    rid4 = fe.requests[s4.rid].backend_id
    assert srv.park(rid4)
    s6 = fe.submit("queued six", tenant="t", max_new_tokens=8)
    admitted = fe._admit_batch()
    assert admitted == 1, "stranded a free lane while a request was parked"
    srv._admit()  # prefill the admission the hook queued
    assert all(r is not None for r in srv.lanes)
    assert srv.unpark(rid4)
    fe.serve(pipeline=pipeline)
    for s in (s4, s5, s6):
        assert s.done and s.status == "ok", (s.rid, s.status)
    assert fe.pending() == 0


# ---------------------------------------------------------------------------
# front-end over CortexEngine
# ---------------------------------------------------------------------------

def test_engine_stream_bitwise_and_window_granularity(setup):
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    eng = CortexEngine(
        Prism(params, cfg), tok, n_main=2, max_side=2, main_capacity=128,
        inject_tokens=8, theta=-1.0, sampling=SamplingParams(greedy=True),
        sync_every=4, pipeline=True,
    )
    fe = ServingFrontend(eng, tenants={"gold": 4.0, "free": 1.0})
    a = fe.submit("engine prompt é∑ one", tenant="gold", max_new_tokens=10)
    b = fe.submit("engine prompt two", tenant="free", max_new_tokens=10)
    fe.serve()
    for s, rid in ((a, 1), (b, 2)):
        assert s.done and s.status == "ok"
        req = fe.requests[rid]
        rec = eng.registry.get(req.backend_id)
        view = next(m for m in eng.mains if m.agent_id == req.backend_id)
        assert not view.active  # retired at a boundary
        gen = view.tokens[view.prompt_len:]
        # stream text == final text minus prompt == one-shot decode, bitwise
        assert s.text == view.text[len(req.prompt):] == tok.decode(gen)
        # completion is window-granular: the budget is met, and the overshoot
        # is bounded by the pipelined windows in flight per serve chunk
        assert req.max_new_tokens <= req.tokens_out
        assert req.tokens_out <= req.max_new_tokens + 8 * eng.sync_every
    m = fe.metrics()
    assert m["backend"] == "engine" and m["completed"] == 2
    assert m["tick_latency_s"]["n"] > 0
    for row in m["requests"]:
        assert row["ttft_s"] is not None and row["tpot_s"] is not None


def test_engine_admission_reuses_freed_lane(setup):
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    eng = CortexEngine(
        Prism(params, cfg), tok, n_main=2, max_side=2, main_capacity=128,
        inject_tokens=8, theta=-1.0, sampling=SamplingParams(greedy=True),
        sync_every=4, pipeline=True,
    )
    fe = ServingFrontend(eng, tenants={"t": 1.0})
    streams = [fe.submit(f"queued req {i}", tenant="t", max_new_tokens=8)
               for i in range(4)]  # 4 requests, 2 river lanes
    fe.serve()
    assert all(s.done and s.status == "ok" for s in streams)
    # every admission + retirement happened at a boundary inside run();
    # 4 requests flowed through 2 lanes with no manual lane management
    assert fe.metrics()["fairness"]["admission_rounds"] == 4
    assert fe.pending() == 0


def test_engine_cancel_running_at_boundary(setup):
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    eng = CortexEngine(
        Prism(params, cfg), tok, n_main=2, max_side=2, main_capacity=128,
        inject_tokens=8, theta=-1.0, sampling=SamplingParams(greedy=True),
        sync_every=4, pipeline=True,
    )
    fe = ServingFrontend(eng, tenants={"t": 1.0})
    s = fe.submit("long running request", tenant="t", max_new_tokens=10_000)
    eng.run(4)  # admit + first window
    assert fe.cancel(1)
    eng.run(8)  # next boundary honors the cancel
    assert s.done and s.status == "cancelled"
    assert fe.pending() == 0


def test_serve_budget_raises_on_stuck_retirement(setup):
    # ISSUE 10 bugfix regression: serve() used to treat max_ticks as a
    # per-iteration cap on an unbounded `while pending()` loop — a lane
    # whose retire_main keeps refusing (side streams target it) spun
    # forever. Now the budget is total and exhaustion raises with the
    # stuck rids.
    cfg, params = setup
    tok = ByteTokenizer(cfg.vocab_size)
    eng = CortexEngine(
        Prism(params, cfg), tok, n_main=1, max_side=2, main_capacity=128,
        inject_tokens=8, theta=-1.0, sampling=SamplingParams(greedy=True),
        sync_every=4, pipeline=True, side_max_steps=10_000,
    )
    fe = ServingFrontend(eng, tenants={"t": 1.0})
    # the [TASK:] tag spawns a side targeting lane 0 at submit; with a
    # 10k-step side budget the lane's retirement is refused at every
    # boundary long past the request's own 4-token budget
    s = fe.submit("please [TASK: keep thinking] go", tenant="t",
                  max_new_tokens=4)
    with pytest.raises(ServeStalled) as exc:
        fe.serve(max_ticks=64)
    assert exc.value.stuck == [1]
    assert not s.done  # never mis-reported as complete
    assert fe.requests[1].tokens_out >= 4  # budget met, retirement refused
