"""A cell is added by new files and entries alone: the harness finds its
configuration, traffic, limits and metrics by name, and no file that was
there changes."""
import hashlib
import json
import shutil

import run
from conftest import BENCH, ROOT, TINY_MIX, add_cell


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


def test_every_metric_has_a_reader_and_unit():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell(ROOT, b["workloads"][0]["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert cell.reader(m["name"]).UNIT == m["unit"]
