"""The work counts and the peaks table, tied to the configurations'
arithmetic."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import peaks  # noqa: E402
from bench.models import dense_transformer as dense  # noqa: E402
from bench.models import mamba2  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


SC = config("starcoder2_3b")
MB = config("mamba2_370m")


def test_starcoder2_weights_and_kv():
    # 30 x (q, k, v, o at 3072 x 128 x (24 + 24 + 2 + 2) + SwiGLU 3 x
    # 3072 x 12288 + 2 norms) + 49,152 x 3072 tied embedding + final norm
    assert dense.weight_bytes(SC) == 2 * (30 * (3072 * 128 * 52
                                                + 3 * 3072 * 12288
                                                + 2 * 3072)
                                          + 49152 * 3072 + 3072)
    assert round(dense.weight_bytes(SC) / 1e9, 1) == 8.3
    assert dense.kv_bytes_per_token(SC) == 30 * 2 * 2 * 128 * 2 == 30720


def test_mamba2_state_per_slot():
    ssm = 48 * 32 * 64 * 128 * 4
    conv = 48 * 3 * 2048 * 2
    assert mamba2.state_bytes_per_row(MB) == ssm + conv
    assert round(ssm / 1e6, 1) == 50.3
    assert 0.7e9 < mamba2.weight_bytes(MB) < 0.8e9


def test_decode_and_prefill_counts():
    f1, b1 = dense.decode_step_work(SC, [0])
    f2, b2 = dense.decode_step_work(SC, [0, 1000])
    # a second row reads its 1,001 tokens of KV and adds its matmuls
    assert b2 - b1 == 1001 * 30720
    assert f2 > 2 * f1 * 0.99
    f, b = dense.prefill_work(SC, 1000)
    assert b == dense.weight_bytes(SC) + 1000 * 30720
    assert f > 2 * dense.matmul_params(SC) * 1000
    fs, bs = mamba2.decode_step_work(MB, [5, 9])
    assert bs == mamba2.weight_bytes(MB) + 4 * mamba2.state_bytes_per_row(MB)
    fa, ba = dense.decode_attention_work(SC, [100])
    assert ba == 2 * 2 * 2 * 128 * 100 + 2 * 2 * 24 * 128
    fl, bl = dense.flash_prefill_work(SC, 4)
    assert fl == 4 * 24 * 128 * 10
    fk, bk = mamba2.ssd_scan_work(MB, 0)
    assert fk == 0 and bk == 4 * 32 * 64 * 128


def test_peaks_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    # compute-bound and memory-bound work
    assert peaks.least_seconds(197e12, 0, v5e) == 1.0
    assert peaks.least_seconds(0, 819e9, v5e) == 1.0
