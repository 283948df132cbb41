"""The prompt generator: deterministic by seed, exact in length, and the
same multiset of sizes for every seed."""
import json
import os
import re

from bench import harness, traffic


def _council():
    return json.load(open(os.path.join(harness.ROOT, "bench", "traffic", "council-256.json")))


def test_council_prompts_exact_and_deterministic():
    mix = _council()
    a, b = traffic.Council(mix, 2**31 + 7), traffic.Council(mix, 2**31 + 7)
    p = a.prompt(3, 5)
    assert p == b.prompt(3, 5)
    assert p != a.prompt(3, 6)
    assert len(p.encode()) == mix["prompt_bytes"]
    tags = re.findall(r"\[TASK: ([^\]]+)\]", p)
    assert len(tags) == mix["tags_per_prompt"]
    assert p.count("[") == p.count("]") == mix["tags_per_prompt"]
    lo, hi = mix["payload_bytes"]
    assert all(lo <= len(t) <= hi for t in tags)


def test_council_work_is_the_same_for_every_seed():
    mix = _council()
    sizes = lambda seed: sorted(
        len(t) for t in re.findall(r"\[TASK: ([^\]]+)\]", traffic.Council(mix, seed).prompt(0, 0)))
    assert sizes(1) == sizes(99) == sizes(2**33 + 5)
    greedy = [traffic.Council(mix, s).greedy.sum() for s in (1, 2, 3)]
    assert len(set(greedy)) == 1 and greedy[0] == round(mix["greedy_share"] * mix["sessions"])
