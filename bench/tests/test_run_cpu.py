"""bench/run.py end to end on the CPU at a tiny size, on a tiny
MultiHyena and on a tiny GQA Transformer, whose architecture joined the
benchmark by new files alone."""
import json

import pytest

import run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell,trace", [("tiny.chat", 0), ("tiny.chat", 1),
                                        ("tiny.decode", 0),
                                        ("tiny.decode", 1),
                                        ("gqa.chat", 0), ("gqa.chat", 1),
                                        ("gqa.decode", 0), ("gqa.decode", 1)])
def test_run_prints_result(tiny_root, capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 7),
                   "--seconds", "1.5", "--trace", str(trace)],
                  root=tiny_root, require_tpu=False)
    assert rc == 0
    res = last_json(capsys)
    assert list(res)[:5] == KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    assert res["device"]["platform"] == "cpu"
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in b[kind] if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) <= want
    assert "setup_s" in res["metrics"] or trace
    if trace:
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_real_cell_refuses_cpu(capsys):
    rc = run.main(["--workload", "mh153m.chat", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc == run.NO_CHIP_EXIT
    assert capsys.readouterr().out == ""
