"""FLOPs and bytes computed from shapes, and the peaks table."""
import json
import os

import _bench_path  # noqa: F401
import pytest

from benchlib import counts, peaks, spec

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


#: the program's configuration of each served cell, as the harness built
#: it before configurations named their architecture
PROGRAM_CONFIGS = {
    "danube.chat": dict(
        name="danube-1.8b", family="dense", n_layers=24, d_model=2560,
        n_heads=32, n_kv_heads=8, d_ff=6912, vocab=32000, head_dim=80,
        swa_window=4096, rope_theta=10000.0, norm_eps=1e-05,
        tie_embeddings=False, dtype="bfloat16"),
    "granite.batch": dict(
        name="granite-8b-9l", family="dense", n_layers=9, d_model=4096,
        n_heads=32, n_kv_heads=8, d_ff=14336, vocab=49152, head_dim=128,
        swa_window=None, rope_theta=10000000.0, norm_eps=1e-05,
        tie_embeddings=True, dtype="bfloat16")}


@pytest.mark.parametrize("cell", sorted(PROGRAM_CONFIGS))
def test_architecture_gives_the_same_program_config(cell):
    from repro.configs.base import ModelConfig

    c = spec.load_cell(_bench_path.REPO, cell)
    got = c.architecture().program_config(c.config)
    assert got == ModelConfig(**PROGRAM_CONFIGS[cell])


#: (FLOPs, bytes) of 3 steps over contexts 100, 1500 and 2047, and of one
#: step with no live slot, as ``counts.decode_needs`` gave them
DECODE_NEEDS = {
    "danube.chat": ((11_391_221_760, 10_719_959_040), (0, 3_498_562_560)),
    "granite.batch": ((13_523_337_216, 13_120_585_728),
                      (0, 4_328_677_376))}


@pytest.mark.parametrize("cell", sorted(DECODE_NEEDS))
def test_architecture_gives_the_same_decode_needs(cell):
    c = spec.load_cell(_bench_path.REPO, cell)
    arch = c.architecture()
    d = arch.dims(c.config)
    got = (arch.decode_needs(d, 3, [100, 1500, 2047]),
           arch.decode_needs(d, 1, []))
    assert got == DECODE_NEEDS[cell]
    assert got[0] == counts.decode_needs(counts.dense_dims(c.config), 3,
                                         [100, 1500, 2047])
