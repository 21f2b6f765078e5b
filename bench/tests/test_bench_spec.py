"""BENCHMARK.json resolves to files, and the roofline arithmetic."""

import json

import pytest

from bench import roofline, spec

BENCH = json.loads((spec.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_and_readers(name):
    cell = spec.load_cell(name)
    assert cell.config["chips"] == cell.chips
    assert cell.traffic["loop"] in ("open", "closed")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    # a per-layer metric's cells report the end-to-end metric it moves
    for m in cell.per_layer:
        assert m["moves"] in names


def test_a_split_metric_falls_back_to_its_stem_reader():
    stem = spec.metric_reader("program_ms")
    for name in ("program_ms.lat", "program_ms.tput"):
        assert spec.metric_reader(name).__code__.co_code == \
            stem.__code__.co_code
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric.lat")


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        conf = json.loads((spec.REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for key in conf["reduced"]:
            assert conf["published"][key] != conf.get(key), key
            assert key in conf["reduced_why"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_v5e_peaks_from_the_table():
    p = roofline.peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.mark.parametrize("rows,points,dim", [(32, 1 << 20, 100),
                                             (1, 1 << 22, 128),
                                             (4096, 1 << 12, 128)])
def test_least_time_is_a_lower_bound(rows, points, dim):
    peak = roofline.peaks("TPU v5 lite")
    flops, nbytes = roofline.query_work(rows, points, dim)
    assert flops == 2 * rows * points * dim
    assert nbytes == points * (dim * 4 + 4)
    least = roofline.least_time(flops, nbytes, peak)
    # a launch that reads the points and ids once at the peak bandwidth
    # and does every flop at the peak rate, one after the other, cannot
    # beat the least time: its share is at most 1
    serial = nbytes / peak["bytes_per_s"] + flops / peak["flops_per_s"]
    assert 0.5 < least / serial <= 1.0


def test_hand_worked_shares():
    peak = roofline.peaks("TPU v5 lite")
    # 32 rows over 2^20 x 100: memory bound, 2^20 * 404 B / 819 GB/s
    least = roofline.least_time(*roofline.query_work(32, 1 << 20, 100),
                                peak)
    assert least == pytest.approx((1 << 20) * 404 / 819e9)
    assert least / 0.6e-3 == pytest.approx(0.862, abs=1e-3)
    # 4096 rows over 2^12 x 128: compute bound
    least = roofline.least_time(*roofline.query_work(4096, 1 << 12, 128),
                                peak)
    assert least == pytest.approx(2 * 4096 * 4096 * 128 / 197e12)
