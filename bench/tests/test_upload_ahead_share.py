"""The ``upload_ahead_share.*`` readers."""
import pytest

from bench import spec

NAMES = ["upload_ahead_share.bulk", "upload_ahead_share.open"]


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_ratio_of_the_counters_over_every_span(name):
    counts = {("cnn.uploads", "cnn.upload"): 14,
              ("cnn.uploads", None): 2,
              ("cnn.upload_ahead", "cnn.upload"): 14,
              ("cnn.rows", "cnn.wave"): 64}
    assert spec.reader(name)(None, counts) == pytest.approx(87.5)


@pytest.mark.parametrize("name", NAMES)
def test_no_upload_ahead_reads_zero(name):
    counts = {("cnn.uploads", "cnn.upload"): 5}
    assert spec.reader(name)(None, counts) == 0.0


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("counts", [
    {}, {("cnn.rows", "cnn.wave"): 8},
    {("cnn.uploads", "cnn.upload"): 0}])
def test_none_without_the_counters(name, counts):
    assert spec.reader(name)(None, counts) is None


def test_tiny_bulk_run_reads_its_share(tmp_path):
    """A bulk cell at a size the interpreter runs: served correctly, and
    the program's counters give a share, most waves uploaded ahead."""
    import time

    from bench import cell as cellmod
    from conftest import cpu_peaks, tiny_cell

    cell = tiny_cell("alexnet.bulk", tmp_path, chunk=16)
    r = cellmod.run(cell, seed=2 ** 31 + 29, seconds=1.0, traced=False,
                    t0=time.perf_counter(), require_tpu=False,
                    peaks=cpu_peaks())
    assert r["correct"], r["checks"]
    assert 0.0 < spec.reader(NAMES[0])(None) < 100.0
