"""A run with its timed path broken underneath (``bench/faults.py``) must
come out not correct: a served token altered where it is produced, and a
spawn whose landmark choice ignores the density term. The look for a chip
is skipped."""
from bench import faults
from bench.tests import tiny


def test_altered_token_is_not_correct(monkeypatch):
    faults.altered_token(monkeypatch.setattr)
    r = tiny.run(2**31 + 202)
    assert r.result["correct"] is False
    assert r.result["check"]["token_gap"]["value"] > r.result["check"]["token_gap"]["limit"]
    assert min(r.check["readings"]["river_gap.f32"] + r.check["readings"]["side_gap.f32"]) > 0.05


def test_flat_density_spawn_is_not_correct(monkeypatch):
    faults.flat_density(monkeypatch.setattr)
    r = tiny.run(2**31 + 404)
    assert r.result["correct"] is False
    gap = r.result["check"]["spawn_gap"]
    assert gap["value"] > 3 * gap["limit"]


def test_sound_run_is_correct():
    r = tiny.run(2**31 + 303)
    assert r.result["correct"] is True
    assert r.result["attempted"] > 0 and r.result["failed"] == 0
    assert r.window_compiles == 0
    assert set(r.result["metrics"]) == {"tokens_per_s", "chunk_gap_p95_s", "setup_s"}
    assert r.check["rivers"] >= 1 and r.check["sides"] >= 1
