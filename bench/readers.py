"""Arithmetic the metric readers share. A reader (``bench/metrics/<name>.py``)
takes the finished ``harness.Run`` and returns a number, or None when the
run has nothing for it to read (the metric is then left out of the line).
"""
from __future__ import annotations

import math

from bench import flops as flops_lib
from bench.peaks import peaks_for


def percentile(values, q: float):
    """Nearest-rank percentile (rank ceil(q/100·n)); None on no samples."""
    s = sorted(values)
    if not s:
        return None
    return float(s[min(len(s), max(1, math.ceil(q / 100.0 * len(s)))) - 1])


def in_window(run, t: float) -> bool:
    return run.t_open <= t <= run.t_close


def window_tokens(run) -> int:
    return sum(n for t, n in run.rec.deliveries if in_window(run, t))


def tokens_per_s(run):
    n = window_tokens(run)
    return n / run.window_s if n else None


def chunk_gaps(run) -> list[float]:
    """Gaps between successive token deliveries to one request's stream,
    both ends inside the window, over all requests."""
    out = []
    for rec in run.records:
        a = rec["agent"]
        if a is None or rec["sent"].warm:
            continue
        ts = [t for t in a.times if in_window(run, t)]
        out += [b - a_ for a_, b in zip(ts, ts[1:])]
    return out


def window_requests(run):
    return [r for r in run.records if r["in_window"]]


def stat_delta(run, key: str) -> int:
    return run.stats_close[key] - run.stats_open[key]


def peaks(run) -> dict:
    return peaks_for(run.devices[0].device_kind)


def device_idle_pct(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def window_program_ms(run):
    """Mean device time of one execution of the engine's macro-window
    program in the traced window."""
    tr = run.trace
    if tr is None:
        return None
    name = run.window_program
    ev = tr.modules[0]
    w0, w1 = tr.window
    durs = [e - s for s, e, n in zip(ev.start, ev.end, ev.name)
            if n == name and s >= w0 and e <= w1]
    return 1e-6 * sum(durs) / len(durs) if durs else None


def kernel_roofline(run, kernel: str, shape_fn):
    """Roofline share of ``kernel``'s events in the traced window: the
    least time its calls need at their true shapes over the time they took.
    ``shape_fn(run)`` gives (flops, bytes) of one call."""
    tr = run.trace
    if tr is None:
        return None
    match = run.kernel_match(kernel)
    seconds, n = tr.op_seconds(match)
    if n == 0 or seconds <= 0:
        return None
    f, b = shape_fn(run)
    return flops_lib.roofline_share(n * f, n * b, seconds, peaks(run))


def synapse_call(run):
    m, syn = run.model, run.mix["engine"]["synapse"]
    T = syn["n_landmarks"] + syn["window"] + syn["n_inject"]
    return flops_lib.synapse_attention(run.mix["engine"]["max_side"], m["n_heads"],
                                       m["n_kv_heads"], T, m["d_head"])


def landmark_call(run):
    m = run.model
    return flops_lib.landmark_score(m["n_layers"], m["n_heads"], m["n_kv_heads"],
                                    run.mix["engine"]["main_capacity"], m["d_head"])


def step_mfu(run):
    """Model operations of the tokens delivered in the window, per second,
    over the chips' bf16 peak."""
    syn = run.mix["engine"]["synapse"]
    total = 0.0
    for rec in run.records:
        a = rec["agent"]
        if a is None or rec["sent"].warm:
            continue
        total += _agent_flops(run, a, len(rec["sent"].req.prompt) + 1, syn)
    for side in run.rec.sides:
        total += _agent_flops(run, side, None, syn)
    if total <= 0:
        return None
    return 100.0 * total / run.window_s / (peaks(run)["flops_bf16"] * len(run.devices))


def _agent_flops(run, a, prompt_len, syn) -> float:
    """Operations of ``a``'s tokens delivered in the window; each attends
    over the slots its agent held: a river its prompt, its tokens and the
    thoughts injected so far; a side its landmarks, its window and its
    filled inject slots."""
    total, seen = 0.0, 0
    merges = list(a.merges)
    for t, n in zip(a.times, a.counts):
        for j in range(n):
            seen += 1
            if not in_window(run, t):
                continue
            if prompt_len is not None:
                injected = syn["n_inject"] * sum(1 for k, _ in merges if k < seen)
                slots = prompt_len + seen + injected
            else:
                slots = syn["n_landmarks"] + min(seen + len(a.task) + 8, syn["window"])
            total += flops_lib.decode_token_flops(run.model, slots)
    return total
