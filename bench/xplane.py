"""Reduction of a profiler trace to the numbers the benchmark reports.

The trace is the ``.xplane.pb`` the JAX profiler writes, read with
``jax.profiler.ProfileData``. Device planes are named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed operation and their
``XLA Modules`` line one event per executed program. Host planes hold the
harness's own spans (``jax.profiler.TraceAnnotation``), on the clock the
device events are placed on.

Everything here is plain arithmetic over intervals, checked on a synthetic
trace by ``bench/tests/test_xplane.py``:

* busy time: the union of a device's op intervals inside the window;
* idle gaps: the holes in that union, each named by the innermost harness
  span open at the gap's midpoint (``"none"`` if no span was open);
* kernel time: the summed durations of the op events that match a name;
* an op's self time: its duration less that of the ops nested in it (the
  ops line holds a loop and, inside it, the ops of its body).

Op events are named by their HLO instruction (``%name.N = shape op(...)``);
``short_name`` keeps ``name.N`` and ``base_name`` the ``name``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Events:
    """Parallel arrays of events: start and end in ns, name, and the
    program (``hlo_module``) each op belongs to ("" when unknown)."""

    start: np.ndarray
    end: np.ndarray
    name: list
    module: list = field(default_factory=list)

    @staticmethod
    def of(rows) -> "Events":
        rows = list(rows)
        if not rows:
            return Events(np.zeros(0), np.zeros(0), [], [])
        s, e, n, m = zip(*rows)
        return Events(np.asarray(s, np.float64), np.asarray(e, np.float64), list(n), list(m))


@dataclass
class Trace:
    """What a run's readers need from its trace."""

    window: tuple[float, float]            # ns, the traced window
    ops: list[Events]                      # per device
    modules: list[Events]                  # per device
    spans: list[tuple[str, float, float]]  # harness spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, dev: int) -> np.ndarray:
        ev = self.ops[dev]
        return union(clip(np.stack([ev.start, ev.end], axis=1), *self.window))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        per = [length(self.busy_intervals(d)) for d in range(len(self.ops))]
        return float(np.mean(per)) * 1e-9 if per else 0.0

    def idle_gaps(self, dev: int = 0) -> list[tuple[str, float]]:
        """(span name, seconds) of every hole in the busy union."""
        busy = self.busy_intervals(dev)
        gaps = holes(busy, *self.window)
        return [(span_at(self.spans, (a + b) / 2), (b - a) * 1e-9) for a, b in gaps]

    def op_seconds(self, match, dev: int = 0) -> tuple[float, int]:
        """(summed seconds, count) of the ops whose name or program ``match``
        accepts, clipped to the window."""
        ev = self.ops[dev]
        w0, w1 = self.window
        total, n = 0.0, 0
        for s, e, name, mod in zip(ev.start, ev.end, ev.name, ev.module):
            if e <= w0 or s >= w1 or not match(name, mod):
                continue
            total += min(e, w1) - max(s, w0)
            n += 1
        return total * 1e-9, n

    def top_ops(self, k: int = 10, dev: int = 0) -> list[list]:
        """The ``k`` ops that took most device self time in the window,
        named ``program/op`` and summed over their executions."""
        ev = self.ops[dev]
        w0, w1 = self.window
        own = self_times(ev.start, ev.end)
        acc = defaultdict(float)
        for s, e, t, name, mod in zip(ev.start, ev.end, own, ev.name, ev.module):
            if s >= w0 and e <= w1:
                n = short_name(name)
                acc[f"{mod}/{n}" if mod else n] += t * 1e-9
        return [[n, t] for n, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10, dev: int = 0) -> list[list]:
        """Idle seconds summed by the harness span open during them."""
        acc = defaultdict(float)
        for name, sec in self.idle_gaps(dev):
            acc[name] += sec
        return [[n, t] for n, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def short_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(event_name: str) -> str:
    """``%synapse_attention.8 = ...`` -> ``synapse_attention``."""
    head, _, tail = short_name(event_name).rpartition(".")
    return head if head and tail.isdigit() else short_name(event_name)


def self_times(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each event's duration less the durations of its direct children,
    for properly nested intervals."""
    order = np.lexsort((-end, start))  # parents before the children they hold
    own = end - start
    stack: list[int] = []
    for i in order:
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack and end[i] <= end[stack[-1]]:
            own[stack[-1]] -= end[i] - start[i]
        stack.append(i)
    return own


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge overlapping [start, end) intervals; returns them sorted."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def holes(busy: np.ndarray, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps of a merged, sorted interval list inside [lo, hi)."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def span_at(spans, t: float) -> str:
    """The innermost (latest-starting) span open at time ``t``."""
    best, best_start = "none", -np.inf
    for name, s, e in spans:
        if s <= t < e and s > best_start:
            best, best_start = name, s
    return best


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def read(log_dir: str, window_span: str, span_names) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``. The traced
    window is the harness span ``window_span``; ``span_names`` are the
    harness spans kept for naming idle gaps."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    keep = set(span_names) | {window_span}
    spans, window = [], None
    ops, modules = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            op_rows, mod_rows = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        mod = _stat(e, "hlo_module") or ""
                        op_rows.append((e.start_ns, e.start_ns + e.duration_ns, e.name, str(mod)))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        pid = _stat(e, "program_id")
                        key = e.name if pid is None else f"{e.name}#{pid}"
                        mod_rows.append((e.start_ns, e.start_ns + e.duration_ns, key, key))
            ops.append(Events.of(op_rows))
            modules.append(Events.of(mod_rows))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        iv = (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        if e.name == window_span:
                            window = iv[1:]
                        else:
                            spans.append(iv)
    if window is None:
        raise ValueError(f"span {window_span!r} not found in the trace")
    if not ops:
        raise ValueError("the trace has no TPU device plane")
    return Trace(window, ops, modules, spans)
