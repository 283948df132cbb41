"""Operation and byte counts against hand counts at the true shapes."""
import json
import os

import pytest

from bench import flops, harness
from bench.peaks import PEAKS, peaks_for


def test_synapse_attention_true_shape():
    # qwen2.5-0.5b side pass: 256 lanes, 14/2 heads, 64+64+16 slots, d_head 64
    f, b = flops.synapse_attention(256, 14, 2, 144, 64)
    assert f == 4 * 256 * 14 * 144 * 64
    kv = 2 * 256 * 144 * 2 * 64 * 2
    assert b == kv + 2 * (256 * 14 * 64 * 2) + 256 * 144 + 256 * 144 * 4


def test_landmark_score_true_shape():
    # spawn sweep: 24 layers folded into the batch, 2304 cache slots
    f, b = flops.landmark_score(24, 14, 2, 2304, 64)
    assert f == 2 * 24 * 14 * 2304 * 64
    assert b == 24 * 14 * 64 * 2 + 24 * 2304 * 2 * 64 * 2 + 24 * 14 * 2304 * 4


def test_decode_token_flops_qwen25():
    cfg = json.load(open(os.path.join(harness.ROOT, "bench", "configs", "qwen2.5-0.5b.json")))
    m = cfg["model_config"]
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    dense = 2 * (24 * per_layer + 896 * 151936)
    assert flops.decode_token_flops(m, 0) == dense
    assert flops.decode_token_flops(m, 100) - dense == 4 * 24 * 14 * 64 * 100


def test_roofline_share_takes_the_binding_bound():
    pk = peaks_for("TPU v5 lite")
    # memory bound: 819 MB in 2 ms at 819 GB/s is 1 ms of least time
    assert flops.roofline_share(1.0, 819e6, 2e-3, pk) == pytest.approx(50.0)
    # compute bound
    assert flops.roofline_share(197e9, 1.0, 1e-3, pk) == pytest.approx(100.0)


def test_unknown_chip_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
