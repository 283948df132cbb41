"""The reference's comparisons with the program (``test_reference.py``) on a
Qwen3-shaped tiny model: no qkv bias, q and k normalised per head, 4 query
heads of 32 over d_model 64 (so H·D = 128 ≠ d) and 2 kv heads (Hkv·D = 64),
a tied head. The three tests are imported from ``test_reference.py`` and
collected here, where they take this module's ``setup``. A council on the
same model, served through the harness, comes out correct."""
import pytest

from bench import harness
from bench.models import qwen_dense
from bench.reference import qwen_dense as qd
from bench.tests import tiny
from bench.tests.test_reference import (  # noqa: F401  (collected on this model)
    test_fp8_control_reads_far_from_the_reference,
    test_river_with_injected_thought,
    test_side_agent_spawn_and_decode_past_its_window,
)

QWEN3 = dict(tiny.MODEL, name="tiny-qwen3", qkv_bias=False, qk_norm=True, d_head=32)


@pytest.fixture(scope="module")
def setup():
    from repro.models.config import ModelConfig

    model = dict(QWEN3, compute_dtype="float32")
    cfg = ModelConfig(**model)
    params = qwen_dense.make_params(2**32 + 5, model)
    assert "bq" not in params["groups"][0]["attn"] and "head" not in params
    assert params["groups"][0]["attn"]["wq"].shape == (2, 64, 128)
    return cfg, model, params, qd.Dims.of(model)


def test_qwen3_shaped_council_is_correct():
    cell = tiny.cell()
    cell.config["model_config"] = dict(QWEN3)
    r = harness.Run(cell, 2**31 + 505, 2.0, False, require_tpu=False)
    result = r.execute()
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["thought_mismatch"]["value"] == 0
    assert r.check["rivers"] >= 1 and r.check["sides"] >= 1
    merged = [sd for sd in r.rec.sides if sd.merged]
    assert merged and r.stats_close["merges"] >= len(merged)
