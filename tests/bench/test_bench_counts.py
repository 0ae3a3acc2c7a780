"""FLOPs and bytes computed from shapes, and the peaks table."""
import json
import os

import _bench_path  # noqa: F401
import pytest

from benchlib import counts, peaks

CONFIGS = os.path.join(_bench_path.BENCH, "configs")


def dims(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return counts.dense_dims(json.load(f))


def test_layer_graph_flops_at_danube_widths():
    assert counts.layer_graph_flops(2048, 2560, 6912) == 295_279_001_600


def test_weight_bytes_of_both_configurations():
    # danube: untied head, 24 layers; granite: tied, 9 of 36 layers
    assert counts.weight_bytes(dims("danube-1.8b")) == 3_662_402_560
    assert counts.weight_bytes(dims("granite-8b-9l")) == 4_328_677_376


def test_decode_needs_counts_weights_per_step_and_kv_per_position():
    d = dims("danube-1.8b")
    f1, b1 = counts.decode_needs(d, 1, [100])
    f2, b2 = counts.decode_needs(d, 1, [100, 300])
    per_pos = 24 * 2 * 640 * 2
    assert b2 - b1 == 300 * per_pos + per_pos + 2560 * 2
    assert f2 - f1 == counts.decode_token_flops(d, 300)
    _, b_two_steps = counts.decode_needs(d, 2, [100])
    # the embedding table is only gathered, never read whole
    assert b_two_steps - b1 == counts.weight_bytes(d) - 32000 * 2560 * 2


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
