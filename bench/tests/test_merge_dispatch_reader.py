"""The reader of the engine's batched-merge counters, on the small synthetic
run of ``test_program_readers.py``, and its declaration."""
import pytest

from bench.tests.test_program_readers import BENCH, HARNESS_SPANS, _read, _run, _trace


def test_merges_per_dispatch_is_declared_for_both_councils():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "merges_per_dispatch.council"]
    assert m["layer"] == "engine scheduler (core/engine.py)" and m["source"] == "program_counter"
    assert m["moves"] == "tokens_per_s" and m["better"] == "higher"
    assert m["workloads"] == ["qwen2.5-0.5b.council-256", "qwen3-4b.council-64"]


def test_merges_per_dispatch_reads_the_counters():
    run = _run(_trace(HARNESS_SPANS), {"merges": 10, "merge_dispatches": 1},
               {"merges": 74, "merge_dispatches": 3})
    assert _read("merges_per_dispatch.council", run) == pytest.approx(64 / 2)
    # a window with no merge reads nothing
    quiet = _run(_trace(HARNESS_SPANS), {"merges": 3, "merge_dispatches": 1},
                 {"merges": 3, "merge_dispatches": 1})
    assert _read("merges_per_dispatch.council", quiet) is None
    # nor does a program without the counter (one merge program per side)
    old = _run(_trace(HARNESS_SPANS), {"merges": 10}, {"merges": 42})
    assert _read("merges_per_dispatch.council", old) is None
