"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into the requests a run sends. The file's ``kind`` names the
kind of mix; there is one so far:

* ``council`` — a closed loop. One session per river lane; a session sends
  its next council turn when the previous one completes (an orchestrator
  that waits on each turn). Every turn's prompt is exactly
  ``prompt_bytes`` long: seeded filler around ``tags_per_prompt``
  ``[TASK: ...]`` tags whose payload lengths spread evenly over
  ``payload_bytes``. The harness starts the sessions ``stagger_steps``
  engine steps apart so that their spawn bursts do not line up.

Every seed gets the same multiset of sizes and greedy sessions, in its own
order, so seeds change the order of the work and never its amount. A share
``greedy_share`` of sessions decode greedily (the comparison with the
reference needs greedy tokens); the rest sample at ``temperature``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# filler alphabet: printable ASCII without the router's brackets
_ALPHABET = np.frombuffer(
    bytes(c for c in range(32, 127) if chr(c) not in "[]"), dtype=np.uint8
)


@dataclass
class Request:
    due: float          # seconds after the schedule's origin
    prompt: str
    max_new_tokens: int
    greedy: bool
    tenant: str = "default"
    session: int = -1   # council: the session (river) that sends it


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *salt])


def _filler(rng, n: int) -> str:
    return rng.choice(_ALPHABET, size=n).tobytes().decode("ascii")


def _spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n integers spread evenly over [lo, hi]."""
    return np.rint(np.linspace(lo, hi, n)).astype(int)


def _fixed_count_mask(rng, n: int, share: float) -> np.ndarray:
    k = int(round(share * n))
    mask = np.zeros(n, bool)
    mask[:k] = True
    rng.shuffle(mask)
    return mask


class Council:
    """Closed-loop council turns: ``turn(session, k)`` is session's k-th."""

    def __init__(self, mix: dict, seed: int):
        self.mix, self.seed = mix, seed
        self.sessions = mix["sessions"]
        self.greedy = _fixed_count_mask(rng_for(seed, 1), self.sessions, mix["greedy_share"])

    def prompt(self, session: int, turn: int) -> str:
        m = self.mix
        rng = rng_for(self.seed, 2, session, turn)
        lo, hi = m["payload_bytes"]
        sizes = _spread(lo, hi, m["tags_per_prompt"])
        rng.shuffle(sizes)
        tags = [f"[TASK: {_filler(rng, int(s))}]" for s in sizes]
        fill = m["prompt_bytes"] - sum(len(t) for t in tags)
        if fill < 0:
            raise ValueError(f"{m['tags_per_prompt']} tags do not fit {m['prompt_bytes']} bytes")
        # filler between the tags, cut at seeded points
        cuts = np.sort(rng.integers(0, fill + 1, size=len(tags)))
        text = _filler(rng, fill)
        parts, prev = [], 0
        for c, tag in zip(cuts, tags):
            parts += [text[prev:c], tag]
            prev = c
        parts.append(text[prev:])
        return "".join(parts)

    def turn(self, session: int, k: int, due: float) -> Request:
        return Request(due, self.prompt(session, k), self.mix["max_new_tokens"],
                       bool(self.greedy[session]), session=session)
