"""The torus design's byte count against a hand count at 8x8, each
configuration against its source, and the table of peaks."""
import copy
import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg(name: str, rows: int, cols: int) -> dict:
    cfg = copy.deepcopy(harness.read_json(os.path.join(
        harness.BENCH, "configs", name + ".json")))
    cfg.update(grid_rows=rows, grid_cols=cols)
    return cfg


@pytest.fixture(scope="module")
def torus():
    # the design module is pure arithmetic here: no device is touched
    return harness.load_module("designs", "torus_allreduce")


def test_boundary_channels_hand_count(torus):
    # wafer_64k's layout at 8x8: the pod tier cuts rows 3|4 and 7|0 (16
    # south links); inside each 4x8 pod the 2x2 tier cuts rows 1|2 and 5|6
    # (16 south links) and columns 3|4 and 7|0 in all 8 rows (16 east)
    assert torus.boundary_channels(_cfg("wafer_64k", 8, 8)) == [16, 32]


def test_bytes_per_cycle_hand_count(torus):
    # per core and 16-cycle epoch: state 37 B read + written, parameter
    # 4 B, 2 out channels x (2 words x 4 B + valid 1 B) read + written
    # = 114 B
    cores = 64 * 114 / 16
    # a slab holds min(period, capacity 62 - 1) packets x 8 B + count 4 B
    # + credit 4 B, read and written: the pod tier every 64 cycles carries
    # 61 packets (496 B), the inner tier every 16 carries 16 (136 B)
    pod = 16 * 2 * 496 / 64
    inner = 32 * 2 * 136 / 16
    assert torus.bytes_per_cycle(_cfg("wafer_64k", 8, 8)) == pytest.approx(
        cores + pod + inner)  # 1,248 B


def test_bytes_per_cycle_at_cell_size(torus):
    cfg = harness.read_json(os.path.join(harness.BENCH, "configs",
                                         "wafer_64k.json"))
    assert torus.boundary_channels(cfg) == [512, 1024]
    assert torus.bytes_per_cycle(cfg) == pytest.approx(
        65536 * 114 / 16 + 512 * 2 * 496 / 64 + 1024 * 2 * 136 / 16)


def test_peaks_table():
    v5e = harness.peak_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_for("TPU v9 imaginary")


def test_configs_differ_from_source_only_where_reduced():
    """Every configuration holds its source's values but for the keys it
    lists under ``reduced``, and ``BENCHMARK.json`` lists the same keys."""
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for entry in bench["configs"]:
        cfg = harness.read_json(os.path.join(harness.ROOT, entry["file"]))
        changed = {k for k, v in cfg["source_values"].items() if cfg[k] != v}
        assert changed == set(cfg["reduced"]) == set(entry["reduced"]), \
            entry["name"]
        assert {"source", "deployment", "assumed", "guarantees"} <= set(cfg)
