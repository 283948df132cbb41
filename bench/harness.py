"""One run of one cell: set-up, warm-up, the measured window, the optional
trace, the comparison with the reference, and the result line.

``bench/cell.py`` is the command; this module holds the run so that the
CPU tests can drive it on a tiny configuration. Everything a cell needs is
found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the model configuration as it is run
  (``model_config``), its published source and its ``family``, which names
  ``bench/models/<family>.py`` (seeded weights in the program's layout) and
  ``bench/reference/<family>.py`` (the plain reference);
* ``bench/traffic/<traffic>.json`` — the traffic mix, read by
  ``bench/traffic.py``, with the engine settings it is served under;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(run)``;
* ``bench/limits/<workload>.json`` — the limit of each number compared.

The window drives ``ServingFrontend`` over ``CortexEngine`` from this
module's own loop, on one thread: each request is submitted when it falls
due and the engine is stepped one ``sync_every`` window at a time. Every
latency is read from the request's due time.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from bench import traffic as traffic_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLOCK = time.monotonic  # the clock ServingFrontend stamps its requests with
TRACE_SPANS = ("fe.step", "fe.submit", "gen.sleep")
WINDOW_SPAN = "bench.window"
TAIL_S = 60.0  # how long after the window a due request may take to start


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, workload: str, reported_e2e: set | None = None) -> bool:
    """Whether ``metric`` is reported in ``workload``: listed cells when the
    metric names them, otherwise every cell (per-layer metrics: every cell
    that reports the end-to-end metric they move)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported_e2e is None or metric["moves"] in reported_e2e


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if applies(m, name, names)]
    return Cell(
        workload=w,
        config=load_json(os.path.join(root, cfg_entry["file"])),
        mix=load_json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json")),
        limits=load_json(os.path.join(root, "bench", "limits", f"{name}.json")),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reader(metric: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    """The devices of the run; refuses anything but ``n`` or more TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(
            f"needs {n} TPU chip(s); JAX found {len(devs)} {devs[0].platform} "
            f"device(s) ({devs[0].device_kind})"
        )
    return devs[:n]


# ---------------------------------------------------------------------------
# what the run saw: deliveries, spawns, merges
# ---------------------------------------------------------------------------
@dataclass
class Agent:
    kind: str                              # "main" | "side"
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)   # one per delivery
    counts: list = field(default_factory=list)  # tokens per delivery
    parent: "Agent | None" = None          # side: its river
    task: str = ""                         # side: its task payload
    sides: list = field(default_factory=list)   # river: spawned sides
    merges: list = field(default_factory=list)  # river: (tokens so far, side)
    merged: bool = False                   # side: merged back
    history_thought: str = ""              # side: the engine's record (80 chars)
    picks: "np.ndarray | None" = None      # side: landmark positions at spawn [L, K]


class Recorder:
    """Chains after the front-end's stream tap and records every delivery;
    reads spawn and merge events off the engine's history after each step."""

    def __init__(self, eng, clock=CLOCK):
        import jax
        import jax.numpy as jnp

        self.eng, self.clock = eng, clock
        self.current: dict[str, Agent] = {}   # live agent id -> record
        self.sides: list[Agent] = []
        self.deliveries: list[tuple[float, int]] = []  # (time, tokens)
        self._fe_tap = eng.stream_tap
        self._seen = len(eng.history)
        self._copy = jax.jit(jnp.copy)
        self._snap = None  # (device copy of the landmark positions, [(lane, side)])
        eng.stream_tap = self.tap

    def tap(self, view, chunk, toks):
        self._fe_tap(view, chunk, toks)
        now = self.clock()
        a = self.current.get(view.agent_id)
        if a is None:
            a = self.current[view.agent_id] = Agent(view.kind)
        a.tokens.extend(int(t) for t in toks)
        a.times.append(now)
        a.counts.append(len(toks))
        self.deliveries.append((now, len(toks)))

    def river(self, agent_id: str) -> Agent:
        a = self.current.get(agent_id)
        if a is None:
            a = self.current[agent_id] = Agent("main")
        return a

    def after_step(self):
        """Read the step's spawns and merges. The landmark positions of the
        step's new sides are copied off the device without waiting (the
        comparison judges them once the window has closed)."""
        eng = self.eng
        self.flush()
        spawned = []
        for ev in eng.history[self._seen:]:
            if ev["event"] == "spawn":
                view = next(s for s in eng.sides if s.agent_id == ev["agent"] and s.active)
                parent = self.river(eng.mains[view.parent_lane].agent_id)
                side = Agent("side", parent=parent, task=ev["task"])
                self.current[ev["agent"]] = side
                parent.sides.append(side)
                self.sides.append(side)
                spawned.append((view.lane, side))
            elif ev["event"] == "merge":
                side = self.current.pop(ev["agent"])
                side.merged = True
                side.history_thought = ev["thought"]
                river = side.parent
                river.merges.append((len(river.tokens), side))
        self._seen = len(eng.history)
        if spawned:
            snap = self._copy(eng.state.side_caches.groups[0].lm_pos)
            snap.copy_to_host_async()
            self._snap = (snap, spawned)

    def flush(self):
        """Land the previous step's landmark copy on its side records."""
        if self._snap is not None:
            arr, spawned = np.asarray(self._snap[0]), self._snap[1]
            for lane, side in spawned:
                side.picks = arr[:, lane, :]
            self._snap = None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclass
class Sent:
    """One request the generator sent."""

    req: traffic_lib.Request
    due: float            # absolute clock time
    rid: int = -1         # front-end id (-1: refused)
    submitted: float = 0.0
    warm: bool = False

    @property
    def agent_id(self) -> str:
        return f"fe{self.rid}"


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, *,
                 t_start: float | None = None, require_tpu: bool = True):
        self.cell, self.seed, self.seconds, self.trace_on = cell, seed, seconds, trace
        self.mix, self.model = cell.mix, dict(cell.config["model_config"])
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.chips = cell.workload["chips"]
        self.require_tpu = require_tpu
        self.sent: list[Sent] = []
        self.window_compiles = 0
        self.trace = None
        self.numerics = ("f32",)  # the control adds "fp8"

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        self.devices = require_chips(self.chips) if self.require_tpu else jax.devices()[: self.chips]
        self._listen_compiles()
        from repro.core.engine import CortexEngine
        from repro.core.prism import Prism
        from repro.core.synapse import SynapsePolicy
        from repro.data.tokenizer import ByteTokenizer
        from repro.models.config import ModelConfig
        from repro.models.model import CacheSpec
        from repro.serving.frontend import ServingFrontend
        from repro.serving.sampler import SamplingParams

        family = self.cell.config["family"]
        self.cfg = ModelConfig(**self.model)
        self.params = importlib.import_module(f"bench.models.{family}").make_params(
            self.seed, self.model)
        jax.block_until_ready(self.params)
        e, syn = self.mix["engine"], self.mix["engine"]["synapse"]
        side_spec = CacheSpec(
            kind="synapse", n_landmarks=syn["n_landmarks"], window=syn["window"],
            n_inject=syn["n_inject"],
            policy=SynapsePolicy(alpha=syn["alpha"], score_ema=syn["score_ema"],
                                 coverage_cap=syn["coverage_cap"]),
        )
        self.greedy = SamplingParams(greedy=True)
        self.eng = CortexEngine(
            Prism(self.params, self.cfg), ByteTokenizer(self.cfg.vocab_size),
            n_main=e["n_main"], max_side=e["max_side"], main_capacity=e["main_capacity"],
            side_spec=side_spec, theta=e["theta"], inject_tokens=syn["n_inject"],
            side_max_steps=e["side_max_steps"],
            sampling=SamplingParams(temperature=e["temperature"]),
            side_sampling=self.greedy if e["side_greedy"] else None,
            seed=self.seed & 0x7FFFFFFF, sync_every=e["sync_every"],
            side_prompt_cap=e["side_prompt_cap"],
        )
        self.fe = ServingFrontend(self.eng, max_queue=e["max_queue"], clock=CLOCK)
        self.rec = Recorder(self.eng)
        self.sync = e["sync_every"]

    def _listen_compiles(self):
        import jax

        self._in_window = False

        def on_duration(event, duration, **_):
            if self._in_window and event in (
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration",
            ):
                self.window_compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    # -- driving -----------------------------------------------------------
    def submit(self, req: traffic_lib.Request, due: float, warm: bool = False) -> Sent:
        from repro.serving.frontend import AdmissionError

        s = Sent(req, due, warm=warm)
        with span("fe.submit"):
            try:
                stream = self.fe.submit(
                    req.prompt, tenant=req.tenant, max_new_tokens=req.max_new_tokens,
                    sampling=self.greedy if req.greedy else None)
                s.rid = stream.rid
            except AdmissionError:
                s.rid = -1
        s.submitted = CLOCK()
        self.sent.append(s)
        if not warm:
            self.schedule.track(s)
        return s

    def done(self, s: Sent) -> bool:
        return s.rid < 0 or self.fe.requests[s.rid].status in ("ok", "cancelled", "error")

    def step(self):
        with span("fe.step"):
            self.fe.step(self.sync)
        self.rec.after_step()

    def run_until_done(self, sents: list[Sent], cap_s: float = 600.0):
        t_end = CLOCK() + cap_s
        while not all(self.done(s) for s in sents):
            if CLOCK() > t_end:
                raise RuntimeError("warm-up requests did not finish")
            self.step()

    def warm_up(self):
        """Compile exactly the programs the cell's traffic uses, by serving
        a fixed set of requests: the prefill length, the window variants
        (sides on and off; with and without a greedy lane), spawn, merge,
        and the admit and retire programs."""
        m = self.mix
        rng = traffic_lib.rng_for(self.seed, 99)
        now = CLOCK()
        c = traffic_lib.Council(dict(m, tags_per_prompt=1), self.seed)
        greedy_turn = traffic_lib.Request(0.0, c.prompt(c.sessions, 0), 16, True)
        plain = traffic_lib.Request(0.0, traffic_lib._filler(rng, m["prompt_bytes"]), 16, False)
        for req in (greedy_turn, plain):
            self.run_until_done([self.submit(req, now, warm=True)])

    def pump(self, until: float, schedule, *, arrivals: bool = True, stop=None):
        """Serve until ``until`` (or ``stop()``): submit what falls due, step
        while anything is pending, otherwise sleep to the next arrival."""
        while True:
            now = CLOCK()
            if now >= until or (stop is not None and stop()):
                return
            if arrivals:
                for req, due in schedule.due(now):
                    self.submit(req, due)
            if self.fe.pending():
                self.step()
                schedule.after_step(self, CLOCK())
            else:
                wake = min(until, schedule.next_due() if arrivals else math.inf)
                if math.isinf(wake):
                    raise RuntimeError("nothing is pending and nothing falls due")
                with span("gen.sleep"):
                    time.sleep(max(0.0, wake - CLOCK()))

    # -- the whole run -----------------------------------------------------
    def execute(self) -> dict:
        import jax

        self.setup()
        self.warm_up()
        self.schedule = schedule = SCHEDULES[self.mix["kind"]](self, CLOCK())
        self.pump(math.inf, schedule, stop=schedule.preroll_done)
        self.setup_s = time.perf_counter() - self.t_start
        self.stats_open = _stats(self.eng)
        self.t_open = CLOCK()
        gc.collect()
        gc.disable()
        self._in_window = True
        t_end = self.t_open + self.seconds
        trace_dir = None
        if self.trace_on:
            # the last trace_s seconds of the window are traced; the profiler
            # stops (and writes the trace) after the window has closed
            self.pump(t_end - min(self.seconds, self.mix["trace_s"]), schedule)
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with span(WINDOW_SPAN):
                self.pump(t_end, schedule)
        else:
            self.pump(t_end, schedule)
        self.t_close = CLOCK()
        self._in_window = False
        gc.enable()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        self.stats_close = _stats(self.eng)
        self.window_s = self.t_close - self.t_open
        self.pump(self.t_close + TAIL_S, schedule, arrivals=False, stop=self._all_started)
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices)
        if trace_dir is not None:
            from bench import xplane

            self.trace = xplane.read(trace_dir, WINDOW_SPAN, TRACE_SPANS)
            shutil.rmtree(trace_dir, ignore_errors=True)
            self.window_program = self._window_program()
        self.records = self._request_records()
        metrics = self._metrics()
        # the program's state goes before the reference runs: the peak above
        # is the program's, and the reference needs the room
        self._free_program()
        from bench import check

        self.check = check.run(self, self.numerics)
        return self._result(metrics)

    def _window_program(self) -> str | None:
        """The macro-window program: of the programs that ran in about
        every engine step of the traced window, the one that took the most
        device time."""
        tr = self.trace
        steps = sum(1 for n, s, e in tr.spans if n == "fe.step"
                    and s >= tr.window[0] and e <= tr.window[1])
        ev = tr.modules[0]
        count, total = {}, {}
        for s, e, n in zip(ev.start, ev.end, ev.name):
            if s >= tr.window[0] and e <= tr.window[1]:
                count[n] = count.get(n, 0) + 1
                total[n] = total.get(n, 0.0) + (e - s)
        cands = [n for n in count if count[n] >= 0.8 * steps]
        return max(cands, key=lambda n: total[n], default=None)

    def kernel_match(self, kernel: str):
        """Matches the op events of ``kernel`` (its custom call is named
        after the kernel's function: ``%synapse_attention.8 = ...``)."""
        from bench import xplane

        return lambda name, module: xplane.base_name(name) == kernel

    def _all_started(self) -> bool:
        return all(self._first_time(s) is not None or self.done(s) for s in self.in_window())

    def in_window(self) -> list[Sent]:
        return [s for s in self.sent
                if not s.warm and self.t_open <= s.due < self.t_close]

    def _first_time(self, s: Sent):
        if s.rid < 0:
            return None
        a = self.rec.current.get(s.agent_id)
        return a.times[0] if a is not None and a.times else None

    def _request_records(self) -> list[dict]:
        out = []
        for s in self.sent:
            fr = self.fe.requests.get(s.rid) if s.rid >= 0 else None
            a = self.rec.current.get(s.agent_id) if s.rid >= 0 else None
            out.append({
                "sent": s, "status": fr.status if fr else "refused",
                "t_admit": fr.t_admit if fr else None,
                "t_first": a.times[0] if a is not None and a.times else None,
                "agent": a, "in_window": (not s.warm) and self.t_open <= s.due < self.t_close,
            })
        return out

    def failed(self) -> int:
        bad = 0
        for r in self.records:
            if r["in_window"] and (r["status"] in ("refused", "error", "cancelled")
                                   or r["t_first"] is None):
                bad += 1
        return bad

    def _metrics(self) -> dict:
        out = {}
        names = self.cell.per_layer if self.trace_on else self.cell.end_to_end
        for m in names:
            value = reader(m["name"])(self)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def _free_program(self):
        self.rec.flush()
        self.eng.stream_tap = None
        self.eng.admission_hook = None
        self.rec.eng = None
        del self.fe, self.eng
        gc.collect()

    def _result(self, metrics: dict) -> dict:
        d = self.devices[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(self.devices),
                  "memory_peak_bytes": int(self.memory_peak)}
        result = {
            "correct": bool(self.check["correct"]),
            "attempted": len(self.in_window()),
            "failed": self.failed(),
            "metrics": metrics,
            "device": device,
        }
        if self.trace is not None:
            device["busy_s"] = self.trace.busy_s()
            device["window_s"] = self.trace.window_s
            result["breakdown"] = {"device_ops": self.trace.top_ops(),
                                   "idle_gaps": self.trace.top_gaps()}
        result["check"] = self.check["numbers"]
        return result


def _stats(eng) -> dict:
    return {k: v for k, v in eng.stats.items() if isinstance(v, (int, float))}


# ---------------------------------------------------------------------------
# schedules: what falls due when
# ---------------------------------------------------------------------------
class CouncilSchedule:
    """Closed loop: each session's next turn falls due when its previous
    turn completes. The sessions start ``stagger_steps`` engine steps apart
    and the window opens after ``preroll_steps`` steps, so every run serves
    the same turns at the same steps and only the time a step takes varies."""

    def __init__(self, run: Run, now: float):
        self.gen = traffic_lib.Council(run.mix, run.seed)
        self.stagger, self.preroll = run.mix["stagger_steps"], run.mix["preroll_steps"]
        self.steps = 0
        self.pending = [(now, 0)]
        self.turn = [0] * self.gen.sessions
        self.current: dict[int, Sent] = {}

    def preroll_done(self) -> bool:
        return self.steps >= self.preroll

    def due(self, now: float):
        ready = [(t, i) for t, i in self.pending if t <= now]
        self.pending = [(t, i) for t, i in self.pending if t > now]
        for t, i in sorted(ready):
            req = self.gen.turn(i, self.turn[i], t)
            self.turn[i] += 1
            yield req, t

    def next_due(self) -> float:
        return min((t for t, _ in self.pending), default=math.inf)

    def after_step(self, run: Run, now: float):
        self.steps += 1
        i, r = divmod(self.steps, self.stagger)
        if r == 0 and 0 < i < self.gen.sessions:
            self.pending.append((now, i))
        for i, s in list(self.current.items()):
            if run.done(s):
                del self.current[i]
                self.pending.append((now, i))

    def track(self, sent: Sent):
        self.current[sent.req.session] = sent


SCHEDULES = {"council": CouncilSchedule}
