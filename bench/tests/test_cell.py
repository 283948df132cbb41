"""Every cell of BENCHMARK.json resolves to its files by name, the file
keeps to its contract, and the command refuses to run off the TPU."""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.models import qwen_dense

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CONFIG_FILES = sorted(glob.glob(os.path.join(ROOT, "bench", "configs", "*.json")))


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(name):
    cell = harness.resolve(name, ROOT)
    assert cell.mix["kind"] in harness.SCHEDULES
    assert cell.config["model_config"]["param_dtype"] == "bfloat16"
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(m["name"], ROOT))
    assert cell.limits and all(isinstance(v, (int, float)) for v in cell.limits.values())


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_config_file_states_the_published_widths(path):
    doc = json.load(open(path))
    m = doc["model_config"]
    for entry in BENCH["configs"]:
        if os.path.join(ROOT, entry["file"]) == path:
            assert doc["source"] == entry["source"]
    assert m["d_model"] == doc["hidden_size"] and m["d_ff"] == doc["intermediate_size"]
    assert m["n_layers"] == doc["num_hidden_layers"]
    assert m["n_heads"] == doc["num_attention_heads"]
    assert m["n_kv_heads"] == doc["num_key_value_heads"]
    assert m["d_head"] == doc.get("head_dim", doc["hidden_size"] // doc["num_attention_heads"])
    assert m["vocab_size"] == doc["vocab_size"] and m["rope_theta"] == doc["rope_theta"]
    assert m["norm_eps"] == doc["rms_norm_eps"]
    assert m["tie_embeddings"] == doc["tie_word_embeddings"]


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_weights_have_the_programs_layout(path):
    from repro.models import model as model_lib
    from repro.models.config import ModelConfig

    m = json.load(open(path))["model_config"]
    ours = jax.eval_shape(lambda: qwen_dense.make_params(2**31 + 11, m))
    theirs = model_lib.abstract_params(ModelConfig(**m))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_refuses_a_platform_that_is_not_a_tpu():
    name = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "cell.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
