"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished, drawn from the seed, is replayed through the
plain float32 reference: each sampled river over its prompt, its decode
inputs and the thoughts its sides injected; each sampled side agent over
its parent's prompt (for the spawn), its task and its own tokens. Every
served token compared is greedy.

The numbers compared, each against its limit in ``bench/limits/<cell>.json``:

* ``token_gap``: the widest gap, over every served token of the sample, by
  which its reference logit lies below the reference's best logit at that
  position. With random weights the best token changes on rounding, so
  logits are compared, not tokens.
* ``spawn_gap``: how far the spawn's landmark choice falls behind the
  reference's (see below).
* ``thought_mismatch``: merges whose thought, rebuilt from the side's
  tokens, differs from the engine's record (exact).

A spawn's landmark choice is a discrete pick among near-equal scores, and
rounding alone moves some picks, which then moves every later logit of
the side. So the side is decoded in the context its spawn chose: the
reference replays the greedy selection over the landmarks the program kept
and decodes the side from there. The choice itself is judged by
``spawn_gap``: the widest gap, over the greedy steps of every layer, by
which the best key the program kept scores below the best key of all.
Rounding moves it by a few hundredths; a choice that leaves out a term of
the score moves it by tenths.

The control is judged by the same numbers and limits: ``run`` with
``numerics`` ``("f32", "fp8")`` gives a verdict for the program (``f32``)
and one for the reference one precision step below put in its place
(``fp8``), which reads at every position the token that the lower precision
puts first.

The river's decode inputs are what the engine feeds: its first decode step
reads the prompt's last token again, at position P (the prefill's own
logits are not used), then each served token in turn.
"""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_lib
from bench.reference import synapse as syn_ref

PAD, BOS = 256, 257  # the byte tokenizer's pad and bos ids


def decode_bytes(tokens) -> str:
    return bytes(t for t in tokens if 0 <= t < 256).decode("utf-8", errors="replace")


def thought_ids(side_tokens, n_inject: int) -> list[int]:
    """The tokens a merge injects: the last ``n_inject`` bytes of the side's
    decoded text, padded."""
    ids = list(decode_bytes(side_tokens).encode("utf-8"))[-n_inject:]
    return ids + [PAD] * (n_inject - len(ids))


def task_ids(task: str, cap: int) -> list[int]:
    ids = list(f"[TASK: {task}]".encode("utf-8"))
    if len(ids) > cap:
        ids = ids[: cap - 1] + list(b"]")
    return ids


def prompt_ids(prompt: str) -> list[int]:
    return [BOS] + list(prompt.encode("utf-8"))


def gaps(logits, served) -> np.ndarray:
    """best logit − logit of the served token, per position."""
    served = jnp.asarray(served, jnp.int32)
    got = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return np.asarray(jnp.max(logits, axis=1) - got)


def control_gaps(ref_logits, low_logits) -> np.ndarray:
    """The gap, under the reference, of the token the lower precision puts
    first."""
    return gaps(ref_logits, jnp.argmax(low_logits, axis=1))


def sample(r, rng) -> tuple[list, list]:
    """(rivers, sides) to compare: finished greedy rivers, the longest
    first; finished sides of any river."""
    chk = r.mix["check"]
    by_agent = {}
    rivers = []
    for rec in r.records:
        s, a = rec["sent"], rec["agent"]
        if s.warm or a is None:
            continue
        by_agent[id(a)] = s
        if s.req.greedy and rec["status"] == "ok" and a.tokens:
            rivers.append((s, a))
    rivers.sort(key=lambda sa: -len(sa[1].tokens))
    pick = rivers[:1]
    rest = rivers[1:]
    if rest and chk["rivers"] > 1:
        idx = rng.choice(len(rest), size=min(len(rest), chk["rivers"] - 1), replace=False)
        pick += [rest[i] for i in sorted(idx)]
    sides = [sd for sd in r.rec.sides
             if sd.merged and sd.tokens and sd.picks is not None and id(sd.parent) in by_agent]
    chosen = []
    if sides and chk.get("sides", 0):
        idx = rng.choice(len(sides), size=min(len(sides), chk["sides"]), replace=False)
        chosen = [(by_agent[id(sides[i].parent)], sides[i]) for i in sorted(idx)]
    return pick, chosen


def river_case(sent, agent, n_inject: int):
    p = prompt_ids(sent.req.prompt)
    served = list(agent.tokens)
    inputs = [p[-1]] + served[:-1]
    merges = [(k, thought_ids(side.tokens, n_inject)) for k, side in agent.merges]
    mismatch = sum(decode_bytes(side.tokens)[:80] != side.history_thought
                   for _, side in agent.merges)
    return p, inputs, merges, served, mismatch


def side_case(parent_sent, side, cap: int):
    t = task_ids(side.task, cap)
    served = list(side.tokens)
    return t + served[:-1], served, len(t)


def _finite(x):
    return x if x is not None and np.isfinite(x) else None


def verdict(readings: dict, numerics: str, limits: dict, mismatch: int | None = None) -> dict:
    """``correct`` and the numbers compared, each beside its limit, for one
    set of readings (``numerics``: ``f32``, the program; ``fp8``, the
    control). Every number in ``limits`` is compared; one that has nothing
    to read is not correct."""
    served = readings[f"river_gap.{numerics}"] + readings[f"side_gap.{numerics}"]
    spawns = readings[f"spawn_gap.{numerics}"]
    values = {"token_gap": max(served) if served else None,
              "spawn_gap": max(spawns) if spawns else None,
              "thought_mismatch": mismatch}
    numbers = {k: {"value": _finite(values.get(k)), "limit": lim} for k, lim in limits.items()}
    correct = bool(numbers) and all(
        v["value"] is not None and v["value"] <= v["limit"] for v in numbers.values())
    return {"correct": correct, "numbers": numbers}


def run(r, numerics=("f32",)) -> dict:
    """Compare the run's sample with the reference. ``numerics`` may add
    ``"fp8"``: the control, the reference one precision below."""
    family = r.cell.config["family"]
    qd = importlib.import_module(f"bench.reference.{family}")
    dims = qd.Dims.of(r.model)
    syn = r.mix["engine"]["synapse"]
    J, cap = syn["n_inject"], r.mix["engine"]["side_prompt_cap"]
    rng = traffic_lib.rng_for(r.seed, 7)
    t0 = time.perf_counter()
    rivers, sides = sample(r, rng)
    readings = {f"{k}.{n}": [] for k in ("river_gap", "side_gap", "spawn_gap") for n in numerics}
    mismatch, n_tokens = 0, 0
    prompt_kv_cache: dict = {}
    with jax.default_matmul_precision("highest"):
        for sent, agent in rivers:
            p, inputs, merges, served, bad = river_case(sent, agent, J)
            mismatch += bad
            n_tokens += len(served)
            ref, pkv = qd.river_logits(r.params, dims, qd.F32, p, inputs, merges)
            prompt_kv_cache[id(sent)] = pkv
            readings["river_gap.f32"].append(float(gaps(ref, served).max()))
            if "fp8" in numerics:
                low, _ = qd.river_logits(r.params, dims, qd.FP8, p, inputs, merges)
                readings["river_gap.fp8"].append(float(control_gaps(ref, low).max()))
        for parent, side in sides:
            p = prompt_ids(parent.req.prompt)
            if id(parent) not in prompt_kv_cache:
                _, prompt_kv_cache[id(parent)] = qd.river_logits(
                    r.params, dims, qd.F32, p, [p[-1]], [])
            inputs, served, plen = side_case(parent, side, cap)
            n_tokens += len(served)
            pkv = prompt_kv_cache[id(parent)]
            # the side is decoded in the context its spawn chose; the choice
            # itself is judged by its selection gap
            ref, sel, _ = syn_ref.side_logits(r.params, dims, qd.F32, pkv, syn, inputs,
                                              len(p), side.picks)
            readings["side_gap.f32"].append(float(gaps(ref[plen - 1:], served).max()))
            readings["spawn_gap.f32"].append(sel)
            if "fp8" in numerics:
                _, pkv8 = qd.river_logits(r.params, dims, qd.FP8, p, [p[-1]], [])
                low, _, picks8 = syn_ref.side_logits(r.params, dims, qd.FP8, pkv8, syn,
                                                     inputs, len(p))
                ref8, sel8, _ = syn_ref.side_logits(r.params, dims, qd.F32, pkv, syn, inputs,
                                                    len(p), picks8)
                readings["side_gap.fp8"].append(
                    float(control_gaps(ref8[plen - 1:], low[plen - 1:]).max()))
                readings["spawn_gap.fp8"].append(sel8)
    limits = r.cell.limits
    verdicts = {"f32": verdict(readings, "f32", limits, mismatch)}
    # the control makes no thoughts of its own: it is judged by the rest
    for n in numerics[1:]:
        verdicts[n] = verdict(readings, n, {k: v for k, v in limits.items()
                                            if k != "thought_mismatch"})
    return {
        "correct": verdicts["f32"]["correct"], "numbers": verdicts["f32"]["numbers"],
        "verdicts": verdicts, "readings": readings,
        "rivers": len(rivers), "sides": len(sides), "tokens": n_tokens,
        "seconds": time.perf_counter() - t0,
    }
