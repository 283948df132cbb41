"""The readers of the engine's spawn program and spawn counters, on a small
synthetic run, and the accepted readers' indifference to program spans in
the trace."""
import json
import os
import types

import pytest

from bench import harness, traffic, xplane

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MIX = json.load(open(os.path.join(ROOT, "bench", "traffic", "council-256.json")))
MODEL = json.load(open(os.path.join(ROOT, "bench", "configs", "qwen2.5-0.5b.json")))["model_config"]
ACCEPTED = ("aux_dispatches_per_tick.council", "overlapped_drain_share.council",
            "window_device_ms.council", "step_mfu.council", "synapse_attention_roofline",
            "landmark_score_roofline", "device_idle.council")
SYNAPSE = "%synapse_attention.8 = (bf16[256,2,7,128]) custom-call(bf16[256,2,7,128] %x)"
LANDMARK = "%landmark_score.3 = f32[1,2,7,2304] custom-call(bf16[1,2,7,128] %q)"

# harness spans: three engine steps in a window of [0, 1000) us
HARNESS_SPANS = [("fe.step", 0.0, 300e3), ("fe.submit", 300e3, 310e3),
                 ("fe.step", 320e3, 620e3), ("fe.step", 640e3, 940e3),
                 ("gen.sleep", 940e3, 1000e3)]
# program spans inside the steps, nested by call
PROGRAM_SPANS = [("engine.boundary", 5e3, 60e3), ("fe.admit", 6e3, 59e3),
                 ("engine.submit", 7e3, 58e3), ("engine.spawn", 10e3, 20e3),
                 ("engine.dispatch", 61e3, 62e3), ("engine.fetch", 62e3, 200e3),
                 ("engine.postprocess", 200e3, 290e3), ("engine.merge", 210e3, 230e3),
                 ("engine.merge", 240e3, 260e3)]


def _trace(spans, spawn_key="jit_engine_spawn(8633606048328782328)"):
    """Module events are keyed as the trace reduction keys them: the
    profiler's ``name(fingerprint)``, with ``#<program id>`` where given."""
    window = [(62e3 + k, 190e3 + k, "jit__unknown(1696)") for k in (0.0, 320e3, 640e3)]
    spawns = [(12e3, 18e3, spawn_key), (330e3, 334e3, spawn_key),
              (1200e3, 1210e3, spawn_key)]  # the last one after the window
    merges = [(212e3, 214e3, "jit_engine_merge(8894)")]
    mods = sorted(window + spawns + merges)
    ops = [(s, e, SYNAPSE if n == window[0][2] else LANDMARK, "")
           for s, e, n in mods if n in (window[0][2], spawn_key)]
    return xplane.Trace(
        (0.0, 1000e3),
        [xplane.Events.of(ops)],
        [xplane.Events.of([(s, e, n, n) for s, e, n in mods])],
        list(spans),
    )


def _run(trace, stats_open=None, stats_close=None):
    """A finished run, as far as the readers look into one."""
    run = types.SimpleNamespace(
        trace=trace, model=dict(MODEL), mix=dict(MIX), window_s=1e-3,
        t_open=0.0, t_close=1.0,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        stats_open=stats_open or {"ticks": 0, "aux_dispatches": 0, "drains": 0,
                                  "overlapped_drains": 0},
        stats_close=stats_close or {"ticks": 24, "aux_dispatches": 70, "drains": 3,
                                    "overlapped_drains": 1},
    )
    river = harness.Agent("main", times=[0.1, 0.5], counts=[8, 8])
    side = harness.Agent("side", times=[0.5], counts=[8], task="count the boats")
    sent = harness.Sent(traffic.Request(0.0, "p" * 64, 16, True), 0.0)
    run.records = [{"agent": river, "sent": sent}]
    run.rec = types.SimpleNamespace(sides=[side])
    run.kernel_match = lambda kernel: (
        lambda name, module: xplane.base_name(name) == kernel)
    run.window_program = harness.Run._window_program(run) if trace is not None else None
    return run


def _read(name, run):
    return harness.reader(name, ROOT)(run)


def test_new_metrics_are_declared_for_the_council():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("spawn_device_ms.council", "spawn_drop_share.council"):
        m = per_layer[name]
        assert m["layer"] == "engine scheduler (core/engine.py)"
        assert m["moves"] == "tokens_per_s" and m["workloads"] == ["qwen2.5-0.5b.council-256"]


@pytest.mark.parametrize("key,ms", [
    ("jit_engine_spawn(8633606048328782328)", 5e-3),  # two executions, 6 and 4 us
    ("jit_engine_spawn(86)#3", 5e-3),
    ("jit_engine_spawn", 5e-3),
    ("jit__unknown(4242)", None),  # a spawn compiled under no name of its own
])
def test_spawn_device_ms_reads_the_spawn_program(key, ms):
    value = _read("spawn_device_ms.council", _run(_trace(HARNESS_SPANS, key)))
    assert value == (None if ms is None else pytest.approx(ms))


def test_spawn_device_ms_needs_a_trace():
    assert _read("spawn_device_ms.council", _run(None)) is None


def test_spawn_drop_share_reads_the_counters():
    run = _run(_trace(HARNESS_SPANS),
               {"spawns": 10, "spawns_dropped": 1}, {"spawns": 46, "spawns_dropped": 5})
    assert _read("spawn_drop_share.council", run) == pytest.approx(100.0 * 4 / 40)
    quiet = _run(_trace(HARNESS_SPANS), {"spawns": 3, "spawns_dropped": 0},
                 {"spawns": 3, "spawns_dropped": 0})
    assert _read("spawn_drop_share.council", quiet) is None
    # a program without the counters reads nothing
    assert _read("spawn_drop_share.council", _run(_trace(HARNESS_SPANS))) is None


def test_accepted_readers_ignore_program_spans():
    plain = _run(_trace(HARNESS_SPANS))
    spanned = _run(_trace(sorted(HARNESS_SPANS + PROGRAM_SPANS, key=lambda s: s[1])))
    assert plain.window_program == spanned.window_program == "jit__unknown(1696)"
    for name in ACCEPTED:
        a, b = _read(name, plain), _read(name, spanned)
        assert a is not None and a == b, name


def test_idle_gaps_take_the_innermost_program_span():
    # the busy union leaves holes at [0,12) [18,62) [190,330) [334,382)
    # [510,702) [830,1000) us; each is named by the span open at its middle
    spanned = _trace(sorted(HARNESS_SPANS + PROGRAM_SPANS, key=lambda s: s[1]))
    gaps = {n: round(s * 1e6) for n, s in spanned.top_gaps()}
    assert gaps == {"fe.admit": 12, "engine.submit": 44, "engine.postprocess": 140,
                    "fe.step": 48 + 192 + 170}
    plain = {n: round(s * 1e6) for n, s in _trace(HARNESS_SPANS).top_gaps()}
    assert plain == {"fe.step": sum(gaps.values())}
