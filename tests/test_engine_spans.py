"""The control plane's host spans and spawn and merge counters, read back
from a profiler trace of a short council served through ``ServingFrontend``.

The spans are ``jax.profiler.TraceAnnotation``s, so they land in the
profiler's host plane on the clock the device events are placed on. One
river's prompt carries more ``[TASK]`` tags than there are side lanes, so
one spawn is refused and counted; every merge is counted, and with the
gate open every one is counted as accepted. The sides that finish in one
drain merge in one batch: one ``engine.merge`` span carries how many
(``n``) and their river (``parent``), and the history names the agents.
"""
import dataclasses
import glob
import os

import jax
import pytest

from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.data.tokenizer import ByteTokenizer
from repro.models import model as model_lib
from repro.serving.frontend import ServingFrontend
from repro.serving.sampler import SamplingParams

SPANS = ("engine.boundary", "fe.admit", "engine.submit", "engine.dispatch",
         "engine.fetch", "engine.postprocess", "engine.spawn", "engine.merge")
MAX_SIDE = 2
# the two sides' tasks are of one length, so both finish in the same drain
PROMPT = "plan: [TASK: read the map] [TASK: sail the bay] [TASK: name the tides] go"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(engine, spans, host event names) of one traced council."""
    cfg = dataclasses.replace(get_config("qwen2.5-0.5b", reduced=True), compute_dtype="float32")
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = CortexEngine(
        Prism(params, cfg), ByteTokenizer(cfg.vocab_size), n_main=2, max_side=MAX_SIDE,
        main_capacity=256, inject_tokens=4, theta=-1.0, side_max_steps=4,
        sampling=SamplingParams(greedy=True), sync_every=4,
    )
    fe = ServingFrontend(eng)
    stream = fe.submit(PROMPT, max_new_tokens=8)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        fe.serve()
    assert stream.done and stream.status == "ok"
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans, names = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                names.add(e.name)
                if e.name in SPANS:
                    spans.append({"name": e.name, "line": (plane.name, li),
                                  "start": e.start_ns, "end": e.start_ns + e.duration_ns,
                                  "ids": dict(e.stats)})
    return eng, fe, spans, names


def _parent(span, spans, names):
    """The innermost span among ``names`` that holds ``span``."""
    holders = [p for p in spans if p is not span and p["name"] in names
               and p["line"] == span["line"]
               and p["start"] <= span["start"] and span["end"] <= p["end"]]
    return max(holders, key=lambda p: p["start"], default=None)


def test_every_span_appears(served):
    _, _, spans, _ = served
    assert {s["name"] for s in spans} == set(SPANS)
    by = lambda n: [s for s in spans if s["name"] == n]
    assert all(s["ids"]["n"] > 0 for s in by("engine.dispatch"))
    assert all("overlapped" in s["ids"] for s in by("engine.postprocess"))


def test_spans_nest_by_call(served):
    _, _, spans, _ = served
    for s in spans:
        if s["name"] == "fe.admit":
            assert _parent(s, spans, {"engine.boundary"}) is not None
        elif s["name"] == "engine.submit":
            assert _parent(s, spans, {"fe.admit"}) is not None
        elif s["name"] in ("engine.spawn", "engine.merge"):
            p = _parent(s, spans, {"engine.submit", "engine.postprocess"})
            assert p is not None, s
    # the prompt's spawns run inside its submit, the merges inside a drain
    spawn_holders = {_parent(s, spans, {"engine.submit", "engine.postprocess"})["name"]
                     for s in spans if s["name"] == "engine.spawn"}
    assert spawn_holders == {"engine.submit"}
    merge_holders = {_parent(s, spans, {"engine.submit", "engine.postprocess"})["name"]
                     for s in spans if s["name"] == "engine.merge"}
    assert merge_holders == {"engine.postprocess"}


def test_side_spans_carry_the_river_id(served):
    eng, fe, spans, _ = served
    river = fe.requests[1].backend_id
    (submit,) = [s for s in spans if s["name"] == "engine.submit"]
    assert submit["ids"]["agent"] == river
    sides = [s for s in spans if s["name"] in ("engine.spawn", "engine.merge")]
    assert sides and all(s["ids"]["parent"] == river for s in sides)
    spawned = {ev["agent"] for ev in eng.history if ev["event"] == "spawn"}
    merged = {ev["agent"] for ev in eng.history if ev["event"] == "merge"}
    # a batch's span names no side: the history does, every spawned side merged
    assert merged == spawned
    assert all("agent" not in s["ids"] for s in sides if s["name"] == "engine.merge")
    # a refused spawn has no side to name
    assert {s["ids"].get("agent") for s in sides if s["name"] == "engine.spawn"} == \
        spawned | {None}


def test_spawn_counters_match_the_history(served):
    eng, _, spans, _ = served
    spawns = sum(1 for ev in eng.history if ev["event"] == "spawn")
    assert eng.stats["spawns"] == spawns == MAX_SIDE
    # three tags, two side lanes: the third trigger is refused and counted
    assert eng.stats["spawns_dropped"] == 1
    assert sum(1 for s in spans if s["name"] == "engine.spawn") == spawns + 1


def test_merge_counters_match_the_history(served):
    eng, _, spans, _ = served
    merges = [ev for ev in eng.history if ev["event"] == "merge"]
    assert merges and eng.stats["merges"] == len(merges)
    # the gate's threshold is -1: every thought is let in
    assert all(ev["accepted"] for ev in merges)
    assert eng.stats["merges_accepted"] == eng.stats["merges"]
    # both sides finish in one drain: one batch, one merge program, one span
    batches = [s for s in spans if s["name"] == "engine.merge"]
    assert len(batches) == eng.stats["merge_dispatches"] == 1
    assert batches[0]["ids"]["n"] == len(merges) == MAX_SIDE


def test_engine_programs_carry_their_names(served):
    _, _, _, names = served
    for program in ("engine_prefill", "engine_spawn", "engine_merge", "engine_admit_main",
                    "engine_admit_side", "engine_retire_side", "engine_retire_main"):
        assert f"PjitFunction({program})" in names, program
