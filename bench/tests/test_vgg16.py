"""The ``vgg16`` configuration and the ``sa_conv_halo_share.bulk`` reader."""
import dataclasses
import json
import time

import pytest

from bench import flops, spec, weights
from conftest import cpu_peaks, tiny_cell

CFG = json.loads((spec.BENCH / "configs" / "vgg16.json").read_text())


def test_layer_table_matches_the_program_shape_for_shape():
    from repro.models.cnn import VGG16, network_stats

    assert [s["kind"] for s in CFG["layers"]] == [s.kind for s in VGG16]
    for row, s in zip(CFG["layers"], VGG16):
        if s.kind == "conv":
            assert (row["out"], row["k"], row["stride"], row["pad"]) == (
                s.out_ch, s.kernel, s.stride, s.pad)
        elif s.kind == "pool":
            assert (row["k"], row["stride"]) == (s.kernel, s.stride)
        else:
            assert (row["out"], row["act"]) == (s.out_ch, s.act)
    got = [s for s in weights.shapes(CFG) if s["kind"] != "pool"]
    want = network_stats("vgg16")
    assert [(g["ifm"], g["ofm"]) for g in got] == [(w.ifm, w.ofm)
                                                   for w in want]


def test_macs_match_table_one():
    got = flops.layers(CFG, 1)
    conv = sum(g["macs"] for g in got if g["kind"] == "conv")
    fc = sum(g["macs"] for g in got if g["kind"] == "fc")
    assert conv == 15_346_630_656            # 15.35 G (MPNA Table I)
    assert fc == 123_633_664                 # 123.6 M
    assert flops.flops_per_image(CFG) == 2 * (conv + fc)


def _halo_share():
    return spec.reader("sa_conv_halo_share.bulk")


def test_halo_share_reads_the_ratio_of_the_counters():
    counts = {("sa_conv.slab_bytes", "cnn.conv_dispatch"): 3000,
              ("sa_conv.slab_bytes", None): 1000,
              ("sa_conv.halo_bytes", "cnn.conv_dispatch"): 40,
              ("sa_conv.bands", "cnn.conv_dispatch"): 7,
              ("cnn.rows", "cnn.wave"): 8}
    assert _halo_share()(None, counts) == pytest.approx(1.0)


@pytest.mark.parametrize("counts", [
    {}, {("cnn.rows", "cnn.wave"): 8},
    {("sa_conv.halo_bytes", "cnn.conv_dispatch"): 0}])
def test_halo_share_is_none_without_the_counters(counts):
    assert _halo_share()(None, counts) is None


def test_tiny_run_is_correct_and_reads_the_halo_share(tmp_path):
    """The cell end to end at a size the interpreter runs: every answer
    within the limit, and the program's counters give a share."""
    from bench import cell as cellmod

    cell = tiny_cell("vgg16.bulk", tmp_path, chunk=8)
    cell = dataclasses.replace(cell, config=dict(cell.config, in_res=32))
    r = cellmod.run(cell, seed=2 ** 31 + 23, seconds=1.0, traced=False,
                    t0=time.perf_counter(), require_tpu=False,
                    peaks=cpu_peaks())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _halo_share()(None) == 0.0        # one band per conv here
