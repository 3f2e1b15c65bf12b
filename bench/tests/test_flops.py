"""``bench/flops.py`` against the program's own Table I statistics."""
import json

import pytest

from bench import flops, spec

CONFIGS = spec.BENCH / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_alexnet_macs_match_table_one():
    from repro.models.cnn import network_stats

    got = flops.layers(_cfg("alexnet"), 1)
    want = network_stats("alexnet")
    assert [g["name"] for g in got] == [w.name for w in want]
    assert [g["macs"] for g in got] == [w.macs for w in want]
    assert sum(g["macs"] for g in got if g["kind"] == "conv") == 1_076_634_144
    assert sum(g["macs"] for g in got if g["kind"] == "fc") == 58_621_952
    assert flops.flops_per_image(_cfg("alexnet")) == 2 * (
        1_076_634_144 + 58_621_952)


@pytest.mark.parametrize("batch", [1, 8])
def test_fc_bytes_stream_weights_once_at_served_width(batch):
    fp32 = {g["name"]: g for g in flops.layers(_cfg("alexnet"), batch)}
    int8 = {g["name"]: g for g in flops.layers(_cfg("alexnet-int8"), batch)}
    k, n = 9216, 4096
    acts = batch * (k + n) * 4
    assert fp32["fc1"]["bytes"] == acts + k * n * 4 + n * 4
    assert int8["fc1"]["bytes"] == acts + k * n * 1 + n * 8
    assert fp32["fc1"]["flops"] == 2 * batch * k * n


def test_conv_bytes_write_the_pooled_map():
    conv1 = flops.layers(_cfg("alexnet"), 2)[0]
    # 227x227x3 in, 11x11x3x96 weights, pooled 27x27x96 out
    assert conv1["bytes"] == (2 * (227 * 227 * 3 + 27 * 27 * 96) * 4
                              + 11 * 11 * 3 * 96 * 4 + 96 * 4)


def test_least_time_takes_the_larger_bound():
    peak = json.loads((spec.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    cfg = _cfg("alexnet")
    fc = flops.least_seconds(cfg, 8, "fc", peak)
    stream = sum(g["bytes"] for g in flops.layers(cfg, 8)
                 if g["kind"] == "fc") / peak["hbm_bytes_per_s"]
    assert fc == pytest.approx(stream)          # batch 8: the FC stream binds
    conv = flops.least_seconds(cfg, 8, "conv", peak)
    ops = sum(g["flops"] for g in flops.layers(cfg, 8)
              if g["kind"] == "conv") / peak["mxu_flops_per_s"]
    assert conv >= ops
