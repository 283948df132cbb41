"""CortexRouter contract (ISSUE 5): incremental feeds, boundary splits,
duplicate suppression, the tail-size contract, and the trigger-plausibility
hint the pipelined engine's drain gate builds on."""
import dataclasses

import jax

from repro.configs import get_config
from repro.core.engine import CortexEngine
from repro.core.prism import Prism
from repro.core.router import CortexRouter
from repro.data.tokenizer import ByteTokenizer
from repro.models import model as model_lib


TEXT = "pre amble [TASK: alpha beta] mid [DONE] post [ANSWER: gamma] end"


def _kinds(triggers):
    return [(t.kind, t.payload) for t in triggers]


def test_tags_split_across_drains_at_every_offset():
    """Whatever drain boundary cuts the stream — including inside a tag —
    each trigger fires exactly once, with absolute spans."""
    whole = CortexRouter().feed("ref", TEXT)
    expected = _kinds(whole)
    assert expected == [
        ("task", "alpha beta"), ("done", ""), ("answer", "gamma")
    ]
    spans = [t.span for t in whole]
    assert spans[0] == (TEXT.index("["), TEXT.index("]") + 1)
    for cut in range(len(TEXT) + 1):
        r = CortexRouter(tail=64)
        got = r.feed("a", TEXT[:cut]) + r.feed("a", TEXT[cut:])
        assert _kinds(got) == expected, cut
        assert [t.span for t in got] == spans, cut


def test_three_way_split_and_empty_chunks():
    for c1 in (5, 12, 20):
        for c2 in (c1, c1 + 7, 40):
            r = CortexRouter(tail=64)
            got = (r.feed("a", TEXT[:c1]) + r.feed("a", "")
                   + r.feed("a", TEXT[c1:c2]) + r.feed("a", TEXT[c2:]))
            assert _kinds(got) == _kinds(CortexRouter().feed("ref2", TEXT))


def test_feed_scan_mixing_suppresses_duplicates():
    """scan() (full text) and feed() (chunks) may interleave — a trigger
    already reported by either API must never fire again."""
    r = CortexRouter(tail=64)
    first = r.feed("a", TEXT[:30])          # contains the whole [TASK:] tag
    assert _kinds(first) == [("task", "alpha beta")]
    assert _kinds(r.scan("a", TEXT)) == [("done", ""), ("answer", "gamma")]
    assert r.scan("a", TEXT) == []          # fully scanned: idempotent
    assert r.feed("a", " [DONE]")[0].kind == "done"  # new text still fires


def test_tag_longer_than_tail_is_missed_documented():
    """The documented tail contract: once a tag outgrows the retained
    overlap, its opening '[' is evicted and a boundary-straddling match is
    (silently) dropped. This is WHY the engine must size its router tail
    >= the longest tag it round-trips."""
    tag = f"[TASK: {'x' * 40}]"
    r = CortexRouter(tail=8)                # tail << len(tag)
    cut = len(tag) // 2
    got = r.feed("a", tag[:cut]) + r.feed("a", tag[cut:])
    assert got == []                        # the miss, pinned on purpose
    # the same split with an adequate tail matches
    r2 = CortexRouter(tail=len(tag))
    got2 = r2.feed("a", tag[:cut]) + r2.feed("a", tag[cut:])
    assert _kinds(got2) == [("task", "x" * 40)]


def test_engine_sizes_tail_for_its_longest_tag_and_window():
    """Engine-side of the contract: the router tail covers the longest tag
    the engine round-trips ('[TASK: ' + side_prompt_cap bytes + ']') and a
    full sync_every drain of text."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-0.5b", reduced=True), compute_dtype="float32"
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer(cfg.vocab_size)
    for sync_every, cap in ((1, 64), (64, 64), (4, 200)):
        eng = CortexEngine(
            Prism(params, cfg), tok, n_main=1, max_side=1,
            sync_every=sync_every, side_prompt_cap=cap,
        )
        longest_tag = len("[TASK: ]") + cap
        assert eng.router._tail >= longest_tag
        assert eng.router._tail >= 8 * sync_every
        assert eng.router._tail >= 256


def test_plausible_hint():
    """plausible() == unclosed '[' in the retained tail: the pipelined gate
    refuses to overlap a ']'-bearing window while it holds."""
    r = CortexRouter(tail=64)
    assert not r.plausible("a")             # unknown agent: nothing pending
    r.feed("a", "calm text, no brackets")
    assert not r.plausible("a")
    r.feed("a", " now an open [TA")
    assert r.plausible("a")
    got = r.feed("a", "SK: finish] done")   # the split tag completes
    assert _kinds(got) == [("task", "finish")]
    assert not r.plausible("a")             # ']' closed it
    r.feed("a", " stray ] then [ again")
    assert r.plausible("a")
    r.reset("a")
    assert not r.plausible("a")


def test_spans_stay_absolute_across_many_feeds():
    r = CortexRouter(tail=16)
    r.feed("a", "x" * 100)
    got = r.feed("a", "[DONE]")
    assert got[0].span == (100, 106)
    got2 = r.feed("a", "y" * 3 + "[DONE]")
    assert got2[0].span == (109, 115)
