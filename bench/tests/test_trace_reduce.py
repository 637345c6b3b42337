"""The trace reduction on hand-made events and on a trace recorded on a
TPU v5e (bench/tests/data/v5e_trace.json.gz: the events `extract` read from
a profile of the 153M chat cell, cut to a short window)."""
from pathlib import Path

import pytest

import run
import trace_reduce as tr
from conftest import ROOT

DATA = Path(__file__).parent / "data" / "v5e_trace.json.gz"
MH = run.load_arch(ROOT, "multihyena")


def ev():
    # window 0..100 ns; a `while` at 10-40 holds fusion.1 (10-25) and the
    # kernel (25-40); module runs at 10-40 and 60-80; host
    # spans cover the gaps 40-60 (step) and 80-100 (submit)
    return tr.Events(
        ops={0: [("while", 10, 40), ("fusion.1", 10, 25), ("k", 25, 40),
                 ("fusion.2", 60, 80), ("fusion.1", -5, 5),
                 ("late", 95, 120)]},
        modules={0: [("jit__unknown", 10, 40), ("jit__unknown", 60, 80)]},
        host=[(tr.WINDOW, 0, 100), ("bench.step", 38, 62),
              ("bench.submit", 80, 96)])


def test_busy_union_gaps_and_names():
    red = tr.reduce(ev(), [0])
    assert red.window_s == pytest.approx(100e-9)
    # busy: 0-5, 10-40, 60-80, 95-100 = 5 + 30 + 20 + 5
    assert red.busy_s == pytest.approx(60e-9)
    # self times: the while's body covers all of it
    assert red.op_s["while"] == pytest.approx(0.0)
    assert red.op_s["fusion.1"] == pytest.approx(20e-9)
    assert red.op_n["fusion.1"] == 2
    assert red.ops_of("^k$") == (pytest.approx(15e-9), 1)
    assert red.runs_of(holding="^k$") == (pytest.approx(30e-9), 1)
    assert red.runs_of(name="unknown", lacking="^k$") == (
        pytest.approx(20e-9), 1)
    labels = dict((round(s * 1e9), lab) for lab, s in red.gaps)
    assert labels == {5: tr.NO_SPAN, 20: "bench.step", 15: "bench.submit"}
    assert red.gaps[0][1] == pytest.approx(20e-9)


def test_breakdown_stable_names():
    bd = tr.breakdown(tr.reduce(ev(), [0]))
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == "fusion" and "fusion.1" not in names
    assert tr.stable_name("%ssm_decode_pallas.4 = (f32[64,864,1]{2,1,0}) "
                          "custom-call(f32[64,864,8] %bitcast.184)") == \
        "ssm_decode_pallas"
    assert tr.stable_name("jit_prefill(7)") == "jit_prefill"
    assert len(bd["idle_gaps"]) <= 10


def test_window_missing_raises():
    e = ev()
    e.host = [x for x in e.host if x[0] != tr.WINDOW]
    with pytest.raises(ValueError):
        tr.reduce(e, [0])


def test_recorded_chip_trace():
    """Half a second of the 153M chat cell on a TPU v5e: 16 pooled decode
    runs (8.3 and 15.9 ms, the guarded one every other tick) and 8 prefill
    runs; the names the harness matches are there."""
    e = tr.load(str(DATA))
    red = tr.reduce(e, sorted(e.ops))
    assert red.window_s == pytest.approx(0.5, rel=1e-6)
    assert 0 < red.busy_s <= red.window_s
    dec_s, dec_n = red.runs_of(**MH.DECODE_RUNS)
    assert dec_n == 16 and 0.15 < dec_s < 0.2
    pre_s, pre_n = red.runs_of(**MH.PREFILL_RUNS)
    assert pre_n == 8 and pre_s > 0
    k_s, k_n = red.ops_of(MH.KERNELS["ssm_decode"][0])
    assert k_n == 16 * 18 and 0 < k_s < dec_s
    # self times add up to the busy union, to rounding
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s, rel=1e-3)
    names = [n for n, _ in tr.breakdown(red)["device_ops"]]
    assert all(" " not in n and "%" not in n for n in names)
