"""Work counts from shapes, checked against counts made by hand."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import work  # noqa: E402

QWEN = json.loads((ROOT / "chipbench/configs/qwen2-0.5b.json").read_text())


def test_maecho_qwen2_0_5b_by_hand():
    """N = 2, tau = 20.  Per layer and outer iteration, a full-projector
    leaf (in 896) costs two P-products per client (Eq. 6/7 and Eq. 11:
    4·N·in²·out), the Gram (2·N²·in·out), the Eq. 7 sum (2·N·in·out) and
    (5N + 2)·in·out elementwise: in·out·(8·896 + 8 + 4 + 12).  The full
    leaves' outputs sum to 896 + 128 + 128 + 4864 + 4864 = 10880."""
    full = 896 * 10880 * (8 * 896 + 24)          # 70.11 GFLOP
    assert full == pytest.approx(4 * 2 * 10880 * 896 ** 2, rel=4e-3)
    # diagonal / scalar leaves: the products are elementwise,
    # in·out·(2N + 2N² + 2N + 5N + 2) = 28·in·out at N = 2
    scalar_layer = 28 * (896 * 896 + 4864 * 896          # wo, w_down
                         + 896 + 128 + 128 + 896 + 896)   # bq bk bv ln1 ln2
    embed_lnf = 28 * (151936 * 896 + 896)                 # once, unstacked
    per_iter = 24 * (full + scalar_layer) + embed_lnf
    got = work.maecho_aggregate(QWEN, n=2, tau=20)
    assert got["flops"] == 20 * per_iter
    assert got["flops"] == pytest.approx(34.0e12, rel=0.02)


def test_maecho_bytes_read_twice_written_once():
    w = work.maecho_leaf(896, 4864, "full", n=2)
    io = 896 * 4864
    assert w["bytes"] == 4 * (2 * 3 * io + 2 * 2 * 896 * 896 + 3 * io)
    s = work.maecho_leaf(896, 1, "scalar", n=2, layers=24, tau=20)
    assert s["bytes"] == 24 * 20 * 4 * (9 * 896 + 4)


def test_kernel_subset_is_smaller():
    allw = work.maecho_aggregate(QWEN, 2, 20)
    sub = work.maecho_aggregate(QWEN, 2, 20, ["layers.wq", "embed"])
    assert 0 < sub["flops"] < allw["flops"]


def test_decode_attention_bytes_by_hand():
    """Prompt 1024, 256 generated: 255 decode steps at positions
    1024..1278, reading p + 1 cached positions of K and V, each
    2 heads × 64 × 2 bytes, in 24 layers."""
    w = work.decode_attention_request(QWEN, 1024, 256)
    live = sum(range(1025, 1280))
    assert live == 293_760
    assert w["bytes"] == 24 * 2 * 2 * 64 * 2 * live
    assert w["flops"] == 24 * 4 * 14 * 64 * live


def test_forward_flops_per_token_by_hand():
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert layer == 14_909_440
    f = work.forward_flops(QWEN, context=1, head=False)
    assert f == 24 * (2 * layer + 4 * 14 * 64)
    head = work.forward_flops(QWEN, 1, True) - f
    assert head == 2 * 896 * 151936


def test_request_flops_counts_each_token_once():
    small = dict(QWEN, num_hidden_layers=1)
    got = work.request_flops(small, prompt=3, gen=2)
    want = (work.forward_flops(small, 1, False)
            + work.forward_flops(small, 2, False)
            + work.forward_flops(small, 3, True)
            + work.forward_flops(small, 4, True))
    assert got == want


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_least_seconds_names_the_bound():
    p = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds({"flops": 197e12, "bytes": 1.0}, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_seconds({"flops": 1.0, "bytes": 819e9 * 2}, p, 2)
    assert (t, bound) == (1.0, "memory")
