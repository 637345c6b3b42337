"""A cell is added by new files and entries alone: the harness finds its
configuration, traffic, limits and metrics by name, and no file that was
there changes."""
import hashlib
import json
import shutil

import run
from conftest import (BENCH, ROOT, TINY_CLOSED, TINY_GQA_CFG, TINY_MIX,
                      add_cell)


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_add_cell_touches_no_existing_file(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = digest(tmp_path)
    add_cell(tmp_path, "tiny.extra", "tiny_extra", TINY_MIX)
    after = digest(tmp_path)
    assert all(after[p] == h for p, h in before.items())
    assert set(after) - set(before) == {
        p.relative_to(tmp_path) for p in (
            tmp_path / "bench/configs/tiny.json",
            tmp_path / "bench/traffic/tiny_extra.json",
            tmp_path / "bench/limits/tiny.extra.json")}
    cell = run.Cell(tmp_path, "tiny.extra")
    assert cell.mix == TINY_MIX and cell.cfg["name"] == "tiny"
    names = {m["name"] for m in cell.metrics("end_to_end")}
    assert {"setup_s", "ttft_p50_ms", "itl_p50_ms"} <= names
    for m in cell.metrics("per_layer"):
        assert hasattr(cell.reader(m["name"]), "read")


def test_add_architecture_touches_no_existing_file(tmp_path):
    """A second architecture joins by its module, a configuration that
    names it, and the cells' files: every file that was there keeps its
    digest, and the harness reaches the new model through its module."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = digest(tmp_path)
    bench_before = (tmp_path / "BENCHMARK.json").read_text()
    add_cell(tmp_path, "gqa.chat", "tiny_chat", TINY_MIX, arch="gqa")
    add_cell(tmp_path, "gqa.decode", "tiny_decode", TINY_CLOSED, arch="gqa",
             end_to_end=("setup_s", "output_tok_s"))
    after = digest(tmp_path)
    assert all(after[p] == h for p, h in before.items())
    assert set(after) - set(before) == {
        p.relative_to(tmp_path) for p in (
            tmp_path / "bench/archs/gqa.py",
            tmp_path / "bench/configs/tiny-gqa.json",
            tmp_path / "bench/traffic/tiny_chat.json",
            tmp_path / "bench/traffic/tiny_decode.json",
            tmp_path / "bench/limits/gqa.chat.json",
            tmp_path / "bench/limits/gqa.decode.json")}
    # BENCHMARK.json only gains entries: every entry that was there stays
    b0 = json.loads(bench_before)
    b1 = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert b1[key][:len(b0[key])] == b0[key]
    cell = run.Cell(tmp_path, "gqa.chat")
    assert cell.arch.__file__ == str(tmp_path / "bench/archs/gqa.py")
    assert cell.vocab == TINY_GQA_CFG["vocab"]
    assert cell.arch.model_config(cell.cfg).n_kv_heads == 2


def test_config_without_arch_is_refused(tmp_path, capsys):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    add_cell(tmp_path, "tiny.extra", "tiny_extra", TINY_MIX)
    cfg = tmp_path / "bench/configs/tiny.json"
    cfg.write_text(json.dumps({k: v for k, v in json.loads(
        cfg.read_text()).items() if k != "arch"}))
    rc = run.main(["--workload", "tiny.extra", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], root=tmp_path, require_tpu=False)
    assert rc == 2 and capsys.readouterr().out == ""


def test_every_metric_has_a_reader_and_unit():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell(ROOT, b["workloads"][0]["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert cell.reader(m["name"]).UNIT == m["unit"]
