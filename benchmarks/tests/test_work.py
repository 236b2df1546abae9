"""The work counts against shapes computed by hand."""

import json

import pytest

from benchmarks import run
from benchmarks.lib import peaks, work


def test_dlrm_flops_per_example():
    kernels = [(13, 64), (64, 16), (367, 128), (128, 64), (64, 1)]
    macs = 13 * 64 + 64 * 16 + 367 * 128 + 128 * 64 + 64
    assert work.dense_flops_per_example(kernels) == 6 * macs == 342_528
    assert work.step_flops(kernels, 6 * 27 * 27 * 16, 8192) == (342_528 + 69_984) * 8192


def test_twotower_flops_per_example(bench):
    # the committed configuration: 16-wide towers, a 16-wide dot product
    config = json.loads((run.ROOT / bench["configs"][0]["file"]).read_text())
    kernels = [tuple(v) for k, v in config["reference"]["dense_shapes"].items()
               if k.endswith("/kernel")]
    assert sorted(kernels) == [(16, 16), (16, 16), (16, 16), (98, 16)]
    stated = config["work"]["interaction_flops_per_example"]
    assert stated == 3 * 2 * 16
    assert work.step_flops(kernels, stated, 1) == 6 * (3 * 256 + 98 * 16) + 96


def test_update_and_step_bytes():
    # row-wise Adagrad, d = 16: 16 floats + 1 cell, read and written
    assert work.update_bytes(1000, 16, "rowwise_adagrad") == 2 * 1000 * 17 * 4
    # Adam, d = 64: row + m + v
    assert work.update_bytes(10, 64, "adam") == 2 * 10 * 192 * 4
    assert work.update_bytes(10, 64, "sgd") == 2 * 10 * 64 * 4
    got = work.step_bytes(lookups=200, unique_rows=100, dim=16,
                          kind="rowwise_adagrad", dense_param_count=50,
                          batch_bytes=7)
    assert got == 200 * 64 + 2 * 100 * 17 * 4 + 2 * 3 * 50 * 4 + 7


def test_peaks_have_no_default():
    assert peaks.chip_peaks("TPU v5 lite").flops_per_s == 197e12
    assert peaks.chip_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(SystemExit):
        peaks.chip_peaks("cpu")
