"""The benchmark's run (`bench/run.py`) of tiny Granite-4.0-H cells on the
CPU: correct as served, and not correct under every fault a cell of the
architecture can have (`bench/faults.py` and the architecture's `FAULTS`),
each planted in the serving program. The faults of the sampler's nucleus
and temperature, which leave greedy rows alone, run on the chat cell, whose
sampled requests are checked; the others on the greedy closed-loop cell.
"""
import json
import shutil

import pytest

from test_granite import (BENCH, ROOT, TINY, TINY_CHAT, TINY_DECODE,
                          TINY_LIMITS)

import faults  # noqa: E402  (bench/, on the path test_granite sets)
import run  # noqa: E402

CHAT_FAULTS = {"top_p_skipped", "temperature_ignored"}


@pytest.fixture
def granite_root(tmp_path):
    """A checkout-like root with two tiny Granite cells (closed-loop greedy
    and open-loop chat) added as the real ones were: new files, entries."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    peaks = json.loads((tmp_path / "bench" / "peaks.json").read_text())
    # made-up peaks so that the CPU run's arithmetic runs; never a result
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps(peaks))
    (tmp_path / "bench" / "configs" / "tiny-granite.json").write_text(
        json.dumps(TINY))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-granite", "source": "test",
                         "file": "bench/configs/tiny-granite.json",
                         "reduced": [], "why": "test"})
    for cell, mix, e2e, lim in (
            ("tg.decode", TINY_DECODE, ("setup_s", "output_tok_s"),
             {"max_gap": 2.5e-4, "min_checked_tokens": 1}),
            ("tg.chat", TINY_CHAT, ("setup_s", "ttft_p50_ms", "itl_p50_ms"),
             TINY_LIMITS)):
        (tmp_path / "bench" / "traffic" / f"{cell}.json").write_text(
            json.dumps(mix))
        (tmp_path / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(lim))
        b["workloads"].append({"name": cell, "config": "tiny-granite",
                               "traffic": cell, "chips": 1, "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if m["name"] in e2e + ("kernel.mamba2_decode_roofline.tput",) \
                    or (m.get("moves") in e2e
                        and "ssm_decode" not in m["name"]):
                if "workloads" in m:
                    m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [("tg.decode", 0), ("tg.decode", 1),
                                        ("tg.chat", 0)])
def test_run_is_correct(granite_root, capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 11),
                   "--seconds", "1.5", "--trace", str(trace)],
                  root=granite_root, require_tpu=False)
    assert rc == 0
    res = _result(capsys)
    assert res["correct"] is True, res["compared"]
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert res["device"]["window_s"] > 0


@pytest.fixture
def fresh_program():
    faults.clear_programs()
    yield
    faults.clear_programs()


@pytest.mark.parametrize("fault", sorted(faults.table(
    run.load_arch(ROOT, "granite"))))
def test_fault_is_not_correct(granite_root, capsys, monkeypatch,
                              fresh_program, fault):
    cell = run.Cell(granite_root,
                    "tg.chat" if fault in CHAT_FAULTS else "tg.decode")
    faults.plant(fault, cell.arch, monkeypatch.setattr)
    rc = run.main(["--workload", cell.name, "--seed", "4", "--seconds", "1.5",
                   "--trace", "0"], root=granite_root, require_tpu=False)
    assert rc == 0
    res = _result(capsys)
    assert res["correct"] is False, res["compared"]
