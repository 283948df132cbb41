"""The reader of the engine's merge program, on a small synthetic trace
(the run and trace of ``test_program_readers.py``), and its declaration."""
import pytest

from bench import xplane
from bench.tests.test_program_readers import BENCH, HARNESS_SPANS, _read, _run


def _trace(merge_key):
    """Two merges inside the window of [0, 1000) us (2 and 6 us), one
    after it, beside a spawn and a window program of other names."""
    mods = sorted([(62e3, 190e3, "jit__unknown(1696)"), (12e3, 18e3, "jit_engine_spawn(86)"),
                   (212e3, 214e3, merge_key), (700e3, 706e3, merge_key),
                   (1200e3, 1230e3, merge_key)])
    return xplane.Trace((0.0, 1000e3), [xplane.Events.of([])],
                        [xplane.Events.of([(s, e, n, n) for s, e, n in mods])],
                        list(HARNESS_SPANS))


def test_merge_metric_is_declared_for_both_councils():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "merge_device_ms.council"]
    assert m["layer"] == "engine scheduler (core/engine.py)" and m["source"] == "device_trace"
    assert m["moves"] == "tokens_per_s" and m["unit"] == "ms"
    assert m["workloads"] == ["qwen2.5-0.5b.council-256", "qwen3-4b.council-64"]


@pytest.mark.parametrize("key,ms", [
    ("jit_engine_merge(8894)", 4e-3),
    ("jit_engine_merge(88)#3", 4e-3),
    ("jit_engine_merge", 4e-3),
    ("jit__unknown(4242)", None),  # a merge compiled under no name of its own
])
def test_merge_device_ms_reads_the_merge_program_in_the_window(key, ms):
    value = _read("merge_device_ms.council", _run(_trace(key)))
    assert value == (None if ms is None else pytest.approx(ms))


def test_merge_device_ms_needs_a_trace():
    assert _read("merge_device_ms.council", _run(None)) is None
