"""The yardstick's counters on hand-computed shapes, and the peaks table."""
import math

import pytest

import run
import work
from conftest import ROOT

MH = run.load_arch(ROOT, "multihyena")

CFG = {"n_layers": 2, "d_model": 8, "d_ff": 16, "vocab": 10,
       "n_filter_heads": 2, "distill_order": 4, "short_conv": 3}


def test_decode_flops_per_token_by_hand():
    # per layer: 2*(4*64 + 2*8*16) + 2*3*24 + 8*(11*2 + 2) + 8
    per_layer = 2 * (256 + 256) + 144 + 8 * 24 + 8
    assert MH.decode_flops_per_token(CFG) == 2 * per_layer + 2 * 10 * 8


def test_prefill_flops_by_hand():
    T, n = 4, 8
    fft = 2.5 * n * math.log2(n)
    conv = 8 * (2 * fft + 6 * 5) + 2 * fft
    per_layer = T * (2 * 512 + 144 + 16) + conv + 2 * 2 * T * 8 * 2
    assert MH.prefill_flops(CFG, T) == pytest.approx(2 * per_layer + 160)


def test_ssm_decode_bytes_and_bound():
    w = MH.ssm_decode(3, CFG)
    # state 2 arrays x (read + write) x 3*8*2 x 4 B; u, y 2 x 3*8 x 4 B;
    # 4 modal params (2, 2) and h0 (8,) in float32
    assert w["bytes"] == 4 * 48 * 4 + 2 * 24 * 4 + 4 * 4 * 4 + 8 * 4
    assert w["flops"] == 3 * 8 * (11 * 2 + 2)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_s(w, peak)
    assert bound == "bytes" and t == pytest.approx(w["bytes"] / 819e9)
    assert MH.ssm_decode(3, CFG, state_itemsize=2)["bytes"] < w["bytes"]
    assert MH.KERNELS["ssm_decode"][1] is MH.ssm_decode


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
