"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Roofline/dry-run artifacts
(benchmarks/artifacts/) are produced by launch/dryrun.py + launch/roofline.py
(they need 512 host devices and run as separate processes).

``--smoke`` runs one reduced throughput iteration (CI-sized: a couple of
macro windows) and checks the macro-tick dispatch accounting without
touching the recorded BENCH_throughput.json baseline. ``--lane`` adds the
lane-sharded curve (bench_lane_scale) — a subprocess, because the forced
host-device count must be set before jax imports. That child is pinned to
the CPU, since a chip belongs to the process that touched JAX first; the
full run records the curve only when it runs on the CPU itself, as
forced-host-device timings say nothing about a chip. A phase that fails
fails the run.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lane_bench(smoke: bool) -> dict:
    """Run bench_lane_scale in a forced-8-device subprocess and load its
    JSON. The parent process stays single-device (its jax backend is
    already initialized), so the lane curve cannot run in-process."""
    name = "bench_lane_smoke.json" if smoke else "bench_lane.json"
    out_path = os.path.join(ROOT, "benchmarks", "artifacts", name)
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "bench_lane_scale.py"),
           "--out", out_path] + (["--smoke"] if smoke else [])
    subprocess.run(cmd, check=True, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    with open(out_path) as f:
        return json.load(f)


def lane_smoke() -> dict:
    """CI gate for the sharded path: the curve must come off a real 8-way
    lane mesh with the macro-tick dispatch accounting intact."""
    res = lane_bench(smoke=True)
    assert res["lane_mesh_shape"] == [8], res
    for n_side, row in res["per_n_side"].items():
        assert row["tick_s"] > 0
        assert row["per_lane_cost_s"] > 0
        assert row["dispatches_per_tick"] == 1.0 / res["sync_every"], (n_side, row)
    print("smoke,ok,lane-sharded dispatch accounting verified")
    return res


def smoke() -> dict:
    """One reduced throughput iteration + the macro-tick dispatch-accounting
    assertions. Single source of truth: tests/test_bench_smoke.py calls this
    same function, so the CI script step and the pytest check cannot drift."""
    from benchmarks import bench_throughput

    out = bench_throughput.run(side_counts=(2,), ticks=4, warmup=4, sync_every=2,
                               ab_reps=3, adaptive_ticks=48)
    res = out["per_side"][2]
    assert res["tick_s"] > 0
    assert res["active"] == 2
    # macro engine: whole sync_every windows ride one scanned dispatch, so
    # the amortized dispatch rate is exactly 1/sync_every...
    assert res["dispatches_per_tick"] == 1.0 / out["sync_every"], res
    # ...equivalently, each dispatch advances sync_every virtual ticks
    assert res["ticks_per_dispatch"] == out["sync_every"], res
    assert res["macro_dispatches"] >= 1
    # drains every sync_every ticks -> at most 1/sync_every syncs per tick
    assert res["host_syncs_per_tick"] <= 1.0 / out["sync_every"] + 1e-9
    # pipelined drains: the A/B arm must actually overlap host work with
    # device windows (multi-window chunks), bitwise-parity asserted inside
    assert out["ab"]["overlap_fraction"] > 0, out["ab"]
    # adaptive windows: a trigger-free run lengthens past the base window
    # and drops the amortized dispatch rate below 1/sync_every
    ada = out["adaptive"]
    assert ada["longest_window"] > out["sync_every"], ada
    assert ada["dispatches_per_tick"] < 1.0 / out["sync_every"], ada
    assert ada["overlap_fraction"] > 0, ada
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/bench_smoke.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    print("smoke,ok,macro-tick dispatch accounting verified")
    return out


def hibernate_smoke() -> dict:
    """CI gate for the tiered synapse memory (ISSUE 7): a dormant agent
    must cost exactly ZERO device bytes (`assert_dormant_zero` inside the
    bench), the registry split must add up, and the async wake must land a
    token. Sized small; the recorded baseline uses registered=256."""
    from benchmarks import bench_hibernate

    out = bench_hibernate.run(registered=16, active=4, sync_every=4,
                              wake_reps=2, ticks_every=8)
    assert out["agents"]["dormant"] == out["registered"] - out["active"]
    assert out["wake_to_first_token_s"] > 0
    assert out["wakes"] >= 2 and out["hibernates"] >= out["registered"] - out["active"]
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/bench_hibernate_smoke.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    print("smoke,ok,dormant agents hold zero device bytes; async wake verified")
    return out


def chaos_smoke() -> dict:
    """CI gate for the resilience layer (ISSUE 8): a scripted fault storm —
    bit-flipped cold blob, transient read failures, a murdered prefetch
    worker — against hibernate/wake churn. The engine must degrade
    per-agent (permanent loss -> LOST, transient -> retried/rewoken), keep
    ticking, and leave untouched lanes bitwise identical to a fault-free
    engine. Writes the fault-injection report artifact."""
    import tempfile

    import jax

    from repro.configs import get_config
    from repro.core.engine import CortexEngine
    from repro.core.prism import Prism
    from repro.data.tokenizer import ByteTokenizer
    from repro.memory import ACTIVE, HIBERNATED, LOST, FaultInjector, SynapseStore
    from repro.models import model as model_lib
    from repro.serving.sampler import SamplingParams

    cfg = get_config("qwen2.5-0.5b", reduced=True)
    params = model_lib.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    prompts = {"A": "agent A considers the first question at length.",
               "B": "agent B writes a careful second answer here.",
               "C": "agent C is the untouched control stream."}

    def build(store=None):
        eng = CortexEngine(Prism(params, cfg), tok, n_main=3, max_side=2,
                           main_capacity=128, theta=1e9, sync_every=4,
                           sampling=SamplingParams(greedy=True), store=store)
        for lane, (aid, p) in enumerate(prompts.items()):
            eng.submit(p, lane=lane, agent_id=aid)
        return eng

    ref = build()
    ref.run(32)
    ref_c = next(m for m in ref.mains if m.agent_id == "C").text

    faults = (
        FaultInjector()
        .flip_write("A")                          # permanent: A's blob corrupt on disk
        .fail_read("B", nth=1, times=2)           # transient: first wake retries through
        .kill_worker_on_read("B", nth=4)          # second wake murders the worker
    )
    cold = tempfile.mkdtemp(prefix="chaos_cold_")
    store = SynapseStore(warm_capacity_bytes=1, cold_dir=cold, faults=faults,
                         wake_backoff_s=0.001)
    eng = build(store)
    eng.run(16)
    eng.hibernate("A")
    eng.hibernate("B")
    eng.wake("A")   # corrupt blob -> quarantine -> LOST; engine keeps ticking
    eng.wake("B")   # two injected read failures -> retry -> lands
    eng.run(8)
    eng.flush_wakes()
    assert eng.registry.get("A").status == LOST, eng.registry.get("A").status
    assert eng.registry.get("B").status == ACTIVE, eng.registry.get("B").status
    assert store.stats["quarantined"] == 1 and store.stats["wake_retries"] == 2, store.stats
    # round 2: the prefetch worker dies mid-promotion; supervision must fail
    # the ticket (B stays HIBERNATED, re-wakeable), respawn, then succeed
    eng.hibernate("B")
    eng.wake("B")
    eng.run(4)
    eng.flush_wakes()
    assert eng.registry.get("B").status == HIBERNATED, eng.registry.get("B").status
    assert eng.stats["wake_failures"] >= 1 and store.stats["worker_respawns"] == 1
    eng.wake("B", wait=True)
    eng.run(4)
    eng.flush_wakes()
    assert eng.registry.get("B").status == ACTIVE
    # the control lane never noticed any of it: bitwise parity at tick 32
    assert eng.stats["ticks"] == 32, eng.stats["ticks"]
    chaos_c = next(m for m in eng.mains if m.agent_id == "C").text
    assert chaos_c == ref_c, (chaos_c[:60], ref_c[:60])
    assert eng.stats["lost_agents"] == 1 and eng.stats["wakes"] == 2

    out = {
        "faults": faults.report(),
        "store_stats": dict(store.stats),
        "engine_stats": {k: eng.stats[k] for k in
                         ("ticks", "hibernates", "wakes", "wake_failures",
                          "lost_agents", "host_syncs", "macro_dispatches")},
        "agents": eng.registry.counts(),
        "control_parity": True,
    }
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/chaos_report.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    print("smoke,ok,chaos: transient faults retried, permanent loss degraded, "
          "control lane bitwise")
    return out


def serving_smoke() -> dict:
    """CI gate for the serving front-end (ISSUE 9): the `serving` bench
    section's key set must stay intact (TTFT + tick-latency percentiles,
    per-tenant token shares, fairness counters), the 4:1 weighted tenants
    must measure token shares within 10% of the weight ratio under
    saturation, and no tenant may starve."""
    from benchmarks import bench_serving

    out = bench_serving.run(per_tenant=40, budget=8, ticks=60)
    # key-set assertions: the section cannot silently rot
    assert {"p50", "p99"} <= set(out["ttft_s"]), out["ttft_s"]
    assert {"p50", "p99", "n"} <= set(out["tick_latency_s"])
    assert out["tick_latency_s"]["n"] > 0
    assert {"admission_rounds", "starvation_promotions",
            "starvation_rounds"} <= set(out["fairness"])
    for name, row in out["tenants"].items():
        assert {"weight", "token_share", "expected_share", "admitted",
                "rejected", "ttft_p50_s", "ttft_p99_s"} <= set(row), (name, row)
    # fairness acceptance: 4:1 weights -> shares within 10%, nobody starves
    for name, row in out["tenants"].items():
        assert row["share_error"] <= 0.10, (name, row)
        assert row["tokens_out"] > 0 and row["admitted"] > 0, (name, row)
    assert out["completed"] > 0 and out["ttft_s"]["p50"] > 0
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/bench_serving_smoke.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    shares = {n: round(r["token_share"], 3) for n, r in out["tenants"].items()}
    print(f"smoke,ok,serving: weighted-fair shares {shares} within 10%; "
          "TTFT/tick-latency/fairness keys intact")
    return out


def transport_smoke() -> dict:
    """CI gate for the HTTP/SSE transport (ISSUE 10): the in-process vs
    loopback A/B must produce both legs with sane SLOs, every loopback
    stream must complete over a REAL socket (no disconnects, no stalled
    writes on a healthy client), and the `serving.transport` section's key
    set must stay intact."""
    from benchmarks import bench_serving

    out = bench_serving.transport_ab(n_lanes=2, n_requests=2, budget=8)
    assert {"in_process", "loopback", "overhead"} <= set(out), set(out)
    for leg in ("in_process", "loopback"):
        row = out[leg]
        assert {"ttft_s", "tpot_s", "wall_s", "tokens_per_s"} <= set(row)
        assert row["ttft_s"]["n"] == out["n_requests"], (leg, row["ttft_s"])
        assert row["ttft_s"]["p50"] > 0 and row["tokens_out"] > 0, (leg, row)
    ts = out["loopback"]["transport_stats"]
    assert ts["streams_ok"] == ts["streams_opened"] == out["n_requests"] + 1
    assert ts["disconnects"] == 0 and ts["stalled_writes"] == 0, ts
    assert {"ttft_p50_ms", "tpot_p50_us"} <= set(out["overhead"])
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/bench_transport_smoke.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"smoke,ok,transport: loopback SSE A/B complete, "
          f"ttft overhead {out['overhead']['ttft_p50_ms']:.2f}ms")
    return out


def main() -> None:
    import jax

    from benchmarks import (
        bench_hibernate, bench_kernels, bench_serving, bench_synapse_quality,
        bench_table1, bench_table2, bench_throughput,
    )

    print("name,us_per_call,derived")
    results = {}
    for name, mod in [
        ("table1", bench_table1),
        ("table2", bench_table2),
        ("synapse_quality", bench_synapse_quality),
        ("throughput", bench_throughput),
        ("kernels", bench_kernels),
    ]:
        results[name] = mod.run()
    os.makedirs("benchmarks/artifacts", exist_ok=True)
    with open("benchmarks/artifacts/bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)

    # top-level perf-trajectory artifact: tick latency per side-count plus
    # the engine's dispatch/sync counters, tracked across PRs. Written only
    # once every phase has passed.
    throughput = results["throughput"]
    if jax.default_backend() == "cpu":
        lane = lane_bench(smoke=False)
        throughput["lane_mesh_shape"] = lane["lane_mesh_shape"]
        throughput["lane_scale"] = lane["per_n_side"]
    throughput["hibernate"] = bench_hibernate.run()
    throughput["serving"] = bench_serving.run()
    # in-process vs loopback wire overhead
    throughput["serving"]["transport"] = bench_serving.transport_ab()
    with open(os.path.join(ROOT, "BENCH_throughput.json"), "w") as f:
        json.dump(throughput, f, indent=1, default=str)


if __name__ == "__main__":
    # support `python benchmarks/run.py` (CI) as well as `-m benchmarks.run`
    sys.path.insert(0, ROOT)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="reduced CI pass; no baseline rewrite")
    ap.add_argument("--lane", action="store_true",
                    help="with --smoke: add the forced-8-device lane-mesh curve")
    ap.add_argument("--chaos", action="store_true",
                    help="with --smoke: run ONLY the fault-injection chaos "
                         "smoke (writes benchmarks/artifacts/chaos_report.json)")
    ap.add_argument("--serving", action="store_true",
                    help="with --smoke: run ONLY the serving front-end smoke "
                         "(weighted-fair shares + SLO key set)")
    ap.add_argument("--transport", action="store_true",
                    help="with --smoke: run ONLY the HTTP/SSE transport smoke "
                         "(loopback A/B, writes bench_transport_smoke.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        if args.chaos:
            chaos_smoke()
        elif args.serving:
            serving_smoke()
        elif args.transport:
            transport_smoke()
        else:
            smoke()
            hibernate_smoke()
            serving_smoke()
            transport_smoke()
            if args.lane:
                lane_smoke()
    else:
        main()
