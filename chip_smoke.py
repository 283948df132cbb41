"""Chip smoke test: the cortex engine and its serving path on one TPU chip,
at a model's published widths (qwen2.5-0.5b by default: 24 layers, d_model
896, 14/2 heads, d_head 64, d_ff 4864, vocab 151936) with random bf16
weights from a seed, held once.

    python chip_smoke.py            # one chip: kernels, council, hibernate/wake, serving
    python chip_smoke.py --arch qwen3-4b  # the same at Qwen3-4B's widths
    python chip_smoke.py --lanes 4  # four chips: lane-mesh council vs the one-chip council

Everything runs in this one process (a chip belongs to the process that
touched JAX first). Phases, in order, each failing the run by raising:

1. the two Pallas kernels against ``kernels/ref.py`` at real widths;
2. a council (2 rivers, 8 side lanes, greedy, 8-tick windows): ``[TASK: ...]``
   tags spawn sides (landmark compression), sides decode through the synapse
   kernel and merge back; the compiled window must hold ``tpu_custom_call``;
3. hibernate one agent, wake it into another lane, and replay its greedy
   stream bitwise against the council's never-hibernated copy;
4. ``ServingFrontend`` requests from two tenants, one of them over the
   loopback HTTP/SSE transport.

The last line of standard output is the JSON contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
anything but a TPU exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# kernel-vs-reference tolerances (max absolute error). Attention outputs are
# rounded to bf16 by both sides (one bf16 ulp near 1.0 is 2**-7); masses,
# densities and distances stay f32, where the only difference is summation
# order over at most 512 keys.
TOL_BF16_OUT = 2e-2
TOL_F32 = 1e-3

SYNC_EVERY = 8
COUNCIL_TICKS = 64
PROMPT_LEN = 96  # every prompt is padded to this many bytes: one prefill program
COUNCIL_PROMPT = ("Plan the launch. [TASK: check the memory budget] "
                  "[TASK: list the risks] Then decide.")
PLAIN_PROMPT = "A plain river with no tasks: it only decodes."
OTHER_PROMPT = "Another resident takes the freed lane meanwhile."


def _prompt(text: str) -> str:
    assert len(text.encode()) <= PROMPT_LEN, text
    return text.ljust(PROMPT_LEN)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
                 f"({dev.device_kind})")
    from repro.kernels import ops

    assert ops.INTERPRET is False, "Pallas kernels would run in interpret mode"
    return dev


def build_engine(params, cfg, *, mesh=None):
    """The council engine every phase shares: the settings of
    ``launch/serve.py --mode cortex`` with greedy lanes (parity is checked
    bitwise), 8 side lanes and 8-tick windows."""
    from repro.core.engine import CortexEngine
    from repro.core.prism import Prism
    from repro.data.tokenizer import ByteTokenizer
    from repro.serving.sampler import SamplingParams

    return CortexEngine(
        Prism(params, cfg), ByteTokenizer(cfg.vocab_size), n_main=2, max_side=8,
        main_capacity=512, side_max_steps=24, inject_tokens=16, theta=-1.0,
        sampling=SamplingParams(greedy=True), sync_every=SYNC_EVERY, mesh=mesh,
    )


def _max_err(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))


def check_kernels(cfg, seed: int) -> None:
    """Both kernels against the f32 oracle in ``kernels/ref.py``, at the
    model's head widths: the side set (landmarks + window + inject slots)
    and the river's cache, then the side pass's in-place attend over the
    three pieces at the council's 256 lanes and at qwen3-4b's 64."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.key(seed), 64))
    normal = lambda shape: jax.random.normal(next(ks), shape).astype(bf)
    log(f"kernels: tolerance max|err| <= {TOL_BF16_OUT} (bf16 outputs), "
        f"<= {TOL_F32} (f32 mass/density/distance)")
    for B, T, what in [(8, 64 + 64 + 16, "side set"), (2, 512, "main cache")]:
        q, k, v = normal((B, H, D)), normal((B, T, Hkv, D)), normal((B, T, Hkv, D))
        valid = jax.random.bernoulli(next(ks), 0.8, (B, T)).at[:, 0].set(True)
        out, mass = ops.synapse_attention(q, k, v, valid)
        with jax.default_matmul_precision("highest"):
            out_r, mass_r = ref.synapse_attention_ref(q, k, v, valid)
        e_out, e_mass = _max_err(out, out_r), _max_err(mass, mass_r)
        log(f"kernels: synapse_attention {what} B={B} T={T}: "
            f"max|out err| {e_out:.3g}, max|mass err| {e_mass:.3g}")
        assert e_out <= TOL_BF16_OUT and e_mass <= TOL_F32, (what, e_out, e_mass)

    T = 512
    for B, n_lm in [(cfg.n_layers, 0), (4, 8)]:
        q, k = normal((B, H, D)), normal((B, T, Hkv, D))
        valid = jnp.arange(T)[None, :] < jnp.array([T - 13 * i for i in range(B)])[:, None]
        lm = normal((B, n_lm, D)) if n_lm else None
        dens, dist = ops.landmark_score(q, k, lm, valid)
        with jax.default_matmul_precision("highest"):
            logits_r, dist_r = ref.landmark_score_ref(q, k, lm if n_lm else normal((B, 1, D)))
            dens_r = jax.nn.softmax(
                jnp.where(valid[:, None, :], logits_r, ops.NEG_INF), axis=-1
            ).sum(axis=1)
        e_dens = _max_err(dens, dens_r)
        what = f"with {n_lm} landmarks" if n_lm else "density-only (spawn sweep)"
        msg = f"kernels: landmark_score {what} B={B} T={T}: max|density err| {e_dens:.3g}"
        assert e_dens <= TOL_F32, (what, e_dens)
        if n_lm:
            e_dist = _max_err(dist, dist_r)
            msg += f", max|dist err| {e_dist:.3g}"
            assert e_dist <= TOL_F32, (what, e_dist)
        else:
            assert dist is None
        log(msg)

    # the side pass's attend, its three pieces read in place from stacks of
    # every layer's lane-dense rows, partly filled: the council's 256 lanes
    # at this model's widths, and 64 lanes at qwen3-4b's (8 kv heads of 128,
    # the widest row, 8 lanes a grid step), its council's lanes also where
    # qwen3-4b is this model
    from repro.configs import get_config
    from repro.kernels import synapse_attention as sa

    sizes = (64, 64, 16)
    widths = {cfg.name: (cfg, 256), "qwen3-4b": (get_config("qwen3-4b"), 64)}
    for c, B in widths.values():
        H, Hkv, D, NL = c.n_heads, c.n_kv_heads, c.d_head, c.n_layers
        layer = NL - 1
        assert sa.fits_in_place(B, H, sizes, Hkv * D, 2), (c.name, "must take the in-place path")
        q = normal((B, H, D))
        stacks = [(normal((NL, B, T, Hkv * D)), normal((NL, B, T, Hkv * D))) for T in sizes]
        fills = [jax.random.randint(next(ks), (B,), 0, T + 1) for T in sizes]
        fills[1] = jnp.maximum(fills[1], 1)  # the window always holds the new token
        valids = [jnp.arange(T)[None, :] < n[:, None] for T, n in zip(sizes, fills)]
        out, masses = jax.jit(
            lambda q, p, m, i: ops.attend_pieces(q, p, m, 1.0 / D ** 0.5, layer=i))(
            q, stacks, valids, jnp.int32(layer))
        pieces = [(k[layer], v[layer]) for k, v in stacks]
        heads = lambda a: a.reshape(B, -1, Hkv, D)
        with jax.default_matmul_precision("highest"):
            out_r, mass_r = ref.synapse_attention_ref(
                q, jnp.concatenate([heads(k) for k, _ in pieces], 1),
                jnp.concatenate([heads(v) for _, v in pieces], 1), jnp.concatenate(valids, 1))
        e_out, e_mass = _max_err(out, out_r), _max_err(jnp.concatenate(masses, 1), mass_r)
        log(f"kernels: synapse_attention in place, {c.name} widths, pieces {sizes} B={B}, "
            f"layer {layer} of {NL}: max|out err| {e_out:.3g}, max|mass err| {e_mass:.3g}")
        assert e_out <= TOL_BF16_OUT and e_mass <= TOL_F32, ("pieces", c.name, e_out, e_mass)
        del stacks, pieces


def _count(events, kind: str) -> int:
    return sum(e[0] == kind for e in events)


def run_council(eng) -> dict:
    """Submit the council prompts and run COUNCIL_TICKS ticks. Returns the
    first window's seconds, the ticks/s after it, and the token streams."""
    eng.submit(_prompt(COUNCIL_PROMPT), lane=0)
    eng.submit(_prompt(PLAIN_PROMPT), lane=1)
    t0 = time.perf_counter()
    eng.run(SYNC_EVERY)
    t1 = time.perf_counter()
    eng.run(COUNCIL_TICKS - SYNC_EVERY)
    t2 = time.perf_counter()
    return {
        "first_window_s": t1 - t0,
        "ticks_per_s": (COUNCIL_TICKS - SYNC_EVERY) / (t2 - t1),
        "mains": [list(m.tokens) for m in eng.mains],
        "sides": [list(s.tokens) for s in eng.sides],
        "events": [(e["event"], e["agent"], e.get("accepted")) for e in eng.history
                   if e["event"] in ("spawn", "merge")],
    }


def _retire_all(eng) -> None:
    assert not any(s.active for s in eng.sides), "sides still live"
    for m in eng.mains:
        if m.active:
            eng.retire_main(m.lane)


def council_phase(eng) -> None:
    """Two council rounds on one engine: the first pays the compiles, the
    second is timed warm and must repeat the first token for token."""
    cold = run_council(eng)
    spawns, merges = _count(cold["events"], "spawn"), _count(cold["events"], "merge")
    assert spawns >= 1 and merges >= 1, (spawns, merges)
    assert not any(s.active for s in eng.sides), "a side outlived its step budget"
    _retire_all(eng)
    warm = run_council(eng)
    assert warm["mains"] == cold["mains"] and warm["sides"] == cold["sides"], \
        "a repeated greedy council diverged"
    n_tok = sum(len(t) for t in warm["mains"]) + sum(len(t) for t in warm["sides"])
    log(f"council: {spawns} spawns, {merges} merges, "
        f"{eng.stats['ticks']} ticks, {n_tok} tokens in the lanes")
    log(f"council: first window (compile + run) {cold['first_window_s']:.2f}s cold, "
        f"{warm['first_window_s']:.3f}s warm; {cold['ticks_per_s']:.1f} ticks/s cold, "
        f"{warm['ticks_per_s']:.1f} ticks/s warm (2 rivers, 8 side lanes, "
        f"{SYNC_EVERY}-tick windows)")

    fn = eng._macro_fn(SYNC_EVERY, True, False, True)
    hlo = fn.lower(eng._params, eng.state).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    log(f"council: compiled {SYNC_EVERY}-tick window holds {n_calls} tpu_custom_call")
    assert n_calls >= 1


def hibernate_phase(eng) -> None:
    """A river decoded for 64 ticks is the reference. The same prompt as
    agent "alice" is hibernated after 16 ticks, another agent takes its
    lane, and alice wakes into the other lane: its greedy stream must
    replay the reference bitwise."""
    _retire_all(eng)
    ref = eng.submit(_prompt(PLAIN_PROMPT), lane=0, agent_id="reference")
    eng.run(64)
    ref_tokens = list(ref.tokens)
    _retire_all(eng)
    eng.submit(_prompt(PLAIN_PROMPT), lane=0, agent_id="alice")
    eng.run(16)
    eng.hibernate("alice")
    eng.submit(_prompt(OTHER_PROMPT), lane=0, agent_id="bob")
    eng.run(8)
    alice = eng.wake("alice", wait=True)
    assert alice.lane == 1, alice.lane
    eng.run(40)
    n = len(alice.tokens)
    assert n == len(ref_tokens) - 64 + 56, (n, len(ref_tokens))
    assert alice.tokens == ref_tokens[:n], "hibernate/wake replay diverged"
    log(f"hibernate: alice parked after 16 ticks, woke into lane {alice.lane}, "
        f"{n} tokens replay bitwise")


def serving_phase(eng) -> None:
    """Requests from two tenants through ServingFrontend: three in process,
    then one over the loopback HTTP/SSE transport."""
    from repro.serving.frontend import ServingFrontend
    from repro.serving.transport import TransportServer, generate_sync

    _retire_all(eng)
    fe = ServingFrontend(eng, tenants={"gold": 4.0, "free": 1.0},
                         default_max_new_tokens=24)
    for tenant, text in [("gold", COUNCIL_PROMPT), ("gold", PLAIN_PROMPT),
                         ("free", OTHER_PROMPT)]:
        fe.submit(_prompt(text), tenant=tenant)
    fe.serve(max_ticks=2048)
    srv = TransportServer(fe, port=0).start()
    try:
        res = generate_sync(srv.host, srv.port, _prompt(PLAIN_PROMPT),
                            tenant="free", max_new_tokens=24)
    finally:
        srv.stop()
    assert res["http_status"] == 200 and res["status"] == "ok", res
    assert srv.stats["streams_ok"] == 1 and srv.stats["pump_errors"] == 0, srv.stats
    m = fe.metrics()
    for row in m["requests"]:
        assert row["status"] == "ok" and row["tokens_out"] >= 24, row
        log(f"serving: request {row['rid']} ({row['tenant']}) "
            f"{row['tokens_out']} tokens, TTFT {row['ttft_s'] * 1e3:.1f}ms, "
            f"TPOT {row['tpot_s'] * 1e3:.2f}ms")
    log(f"serving: {m['completed']} requests completed, the last over SSE "
        f"(rid {res['rid']}); TTFT p50 {m['ttft_s']['p50'] * 1e3:.1f}ms")


def lanes_phase(params, cfg, n_lanes: int) -> None:
    """The same council on a lane mesh of ``n_lanes`` chips and on one chip:
    greedy streams and spawn/merge events must be equal, and the side
    leaves must really be spread over the mesh."""
    import jax

    from repro.launch.mesh import make_lane_mesh

    assert jax.device_count() >= n_lanes, jax.device_count()
    one = run_council(build_engine(params, cfg))
    eng = build_engine(params, cfg, mesh=make_lane_mesh(n_lanes))
    placed = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(eng.state.side_caches)}
    local = {s.data.shape[1] for leaf in jax.tree.leaves(eng.state.side_caches)
             for s in leaf.addressable_shards}
    assert placed == {n_lanes} and local == {eng.max_side // n_lanes}, (placed, local)
    log(f"lanes: side caches spread over {n_lanes} devices, "
        f"{eng.max_side // n_lanes} side lanes each")
    mesh = run_council(eng)
    spawns, merges = _count(one["events"], "spawn"), _count(one["events"], "merge")
    assert spawns >= 1 and merges >= 1, one["events"]
    assert mesh["events"] == one["events"], (mesh["events"], one["events"])
    assert mesh["mains"] == one["mains"] and mesh["sides"] == one["sides"], \
        "lane-mesh greedy streams differ from the one-chip engine"
    log(f"lanes: {n_lanes}-chip lane mesh matches one chip: {spawns} spawns, "
        f"{merges} merges, every river and side stream equal; "
        f"{mesh['ticks_per_s']:.1f} ticks/s on the mesh vs "
        f"{one['ticks_per_s']:.1f} on one chip (cold, compiles included)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, default=None, metavar="N",
                    help="run only the lane-mesh council on N chips against "
                         "the one-chip council")
    ap.add_argument("--seed", type=int, default=0, help="weights and kernel inputs")
    ap.add_argument("--arch", default="qwen2.5-0.5b", help="the model (repro.configs)")
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as model_lib

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    cfg = dataclasses.replace(get_config(args.arch), param_dtype="bfloat16")
    log(f"jax {jax.__version__}; device {dev.device_kind} ({dev.platform}), "
        f"{jax.device_count()} visible")
    log(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_head {cfg.d_head}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.compute_dtype}; random {cfg.param_dtype} weights, "
        f"seed {args.seed}")
    t0 = time.perf_counter()
    params = model_lib.init_params(jax.random.key(args.seed), cfg)
    jax.block_until_ready(params)
    log(f"weights: initialised in {time.perf_counter() - t0:.1f}s")

    if args.lanes:
        lanes_phase(params, cfg, args.lanes)
    else:
        check_kernels(cfg, args.seed)
        eng = build_engine(params, cfg)
        council_phase(eng)
        hibernate_phase(eng)
        serving_phase(eng)

    stats = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} on {dev.device_kind}")
    log(f"compile cache: {cache_dir}: {cache['hits']} hits, {cache['writes']} writes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}), flush=True)


if __name__ == "__main__":
    main()
