"""Work functions against hand counts at the cells' shapes, and the peak
table."""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from perfbench import work  # noqa: E402

QWEN = json.load(open(ROOT / "perfbench/configs/qwen3-1.7b.json"))["model"]


def test_peaks_v5e():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_select_work_one_step():
    flops, nbytes = work.select_work(1024, 10_000, 1)
    assert flops == 1024 * 10_000 * 32
    assert nbytes == 10_000 * 7 * 4 + 1024 * (8 + 8)
    # compute bound at the v5e peaks: 1.66 us
    t = work.least_time_s(flops, nbytes, work.peaks("TPU v5 lite"))
    assert t == pytest.approx(1024 * 10_000 * 32 / 197e12)


def test_flash_attention_work_qwen3():
    flops, nbytes = work.flash_attention_work(QWEN, [1024])
    # 28 layers x 16 heads x 128 dims, QK^T and PV over 1024*1025/2 pairs
    assert flops == 28 * 2 * 2 * 16 * 128 * (1024 * 1025 // 2)
    # Q and O: 16 heads, K and V: 8 heads, 128 dims, bf16
    assert nbytes == 28 * 1024 * (2 * 16 + 2 * 8) * 128 * 2


def test_decode_attention_work_reads_only_to_position():
    flops, nbytes = work.decode_attention_work(QWEN, [2049])
    assert flops == 28 * 4 * 16 * 128 * 2049
    assert nbytes == 28 * (2 * 8 * 2049 + 2 * 16) * 128 * 2
    assert work.decode_attention_work(QWEN, [10, 20]) == tuple(
        a + b for a, b in zip(work.decode_attention_work(QWEN, [10]),
                              work.decode_attention_work(QWEN, [20])))


def test_model_flops_qwen3():
    P = 28 * (2048 * 16 * 128 * 2 + 2048 * 8 * 128 * 2 + 3 * 2048 * 6144)
    assert work.layer_matmul_params(QWEN) == P == 1_409_286_144
    unembed = 2 * 2048 * 151936
    attn_p, _ = work.flash_attention_work(QWEN, [512])
    attn_d, _ = work.decode_attention_work(QWEN, [513])
    want = 2 * P * 512 + attn_p + unembed + 2 * P + unembed + attn_d
    assert work.model_flops(QWEN, [512], [513]) == want
