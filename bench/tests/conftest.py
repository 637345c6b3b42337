"""CPU tests of the benchmark: `python -m pytest bench/tests`.

They run on the CPU at a tiny size; the harness's look for a TPU is
skipped where a test says so. `tiny_root` builds a checkout-like directory
holding BENCHMARK.json and the bench/ tree plus tiny cells added from new
files only: a chat and a closed-loop cell of a tiny MultiHyena, and the
same two of a tiny GQA Transformer, an architecture that the benchmark
does not have (its module is `data/gqa.py`).
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

TINY_CFG = {
    "name": "tiny", "source": "test", "arch": "multihyena",
    "n_layers": 2, "d_model": 64, "n_filter_heads": 4, "d_ff": 128,
    "vocab": 257, "act": "gelu", "norm": "layernorm", "tie_embeddings": True,
    "filter_order": 16, "filter_emb": 9, "short_conv": 3, "sine_freq": 4.0,
    "modulate": True, "distill_order": 8, "dtype": "float32",
    "max_seq": 512,
}

TINY_MIX = {
    "why": "test", "loop": "open", "rate_rps": 40.0, "ramp_s": 0.3,
    "slots": 4, "max_len": 128, "prefills_per_step": 2,
    "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 4,
               "max": 64},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "sampling": {"temperature": 0.15, "top_p": 0.9}, "greedy_every": 2,
    "check": {"requests": 3, "sampled": 16}, "trace_s": 0.3,
}
# the tiny model's logits are nearly flat (spread ~0.16), so its sampled
# requests run cold enough for a wrong temperature to show
TINY_LIMITS = {"max_gap": 1e-3, "min_checked_tokens": 1,
               "nucleus_excess": 1e-3, "surprise_z": 5.0,
               "min_sampled_tokens": 1}

TINY_CLOSED = dict(TINY_MIX, loop="closed", clients=4, requests_per_client=6,
                   sampling={"temperature": 0.0},
                   check={"requests": 3})

TINY_GQA_CFG = {
    "name": "tiny-gqa", "source": "test", "arch": "gqa", "n_layers": 2,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
    "d_ff": 128, "vocab": 257, "rope_theta": 10000.0, "dtype": "float32",
    "max_seq": 512,
}
# the program keeps the KV cache in bfloat16: greedy gaps up to ~5e-4 at a
# tiny size against the float32 reference, the fp8 control's 0.06-0.10;
# nucleus excess up to 0.0026 in sound runs (a loaded test run; -0.0014 or
# less on 12 seeds alone), 0.084-0.100 with the nucleus cut skipped or the
# temperature ignored
TINY_GQA_LIMITS = dict(TINY_LIMITS, max_gap=1e-2, nucleus_excess=0.02)

# architecture -> (configuration name, its file's dict, the limits of a
# tiny chat cell)
ARCHS = {"multihyena": ("tiny", TINY_CFG, TINY_LIMITS),
         "gqa": ("tiny-gqa", TINY_GQA_CFG, TINY_GQA_LIMITS)}


def arch(name: str):
    """An architecture module: the benchmark's own, or one of `data/`."""
    import run
    path = BENCH / "archs" / f"{name}.py"
    return run.load_module(path if path.exists() else DATA / f"{name}.py",
                           "bench_arch_" + name)


def add_cell(root: Path, name: str, mix_name: str, mix: dict,
             arch: str = "multihyena",
             end_to_end=("setup_s", "ttft_p50_ms", "itl_p50_ms"),
             limits=None) -> None:
    """Add a cell of architecture `arch` to the benchmark at `root` by new
    files and entries; an architecture the benchmark lacks brings its
    module from `data/`."""
    config, cfg, chat_limits = ARCHS[arch]
    module = root / "bench" / "archs" / f"{arch}.py"
    if not module.exists():
        shutil.copy(DATA / f"{arch}.py", module)
    (root / "bench" / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / f"{mix_name}.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / f"{name}.json").write_text(json.dumps(
        limits or chat_limits))
    b = json.loads((root / "BENCHMARK.json").read_text())
    if not any(c["name"] == config for c in b["configs"]):
        b["configs"].append({"name": config, "source": "test",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    b["workloads"].append({"name": name, "config": config,
                           "traffic": mix_name, "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] in end_to_end and "workloads" in m:
            m["workloads"].append(name)
    for m in b["per_layer"]:
        if "workloads" in m and m["moves"] in end_to_end:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))


@pytest.fixture
def tiny_root(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    peaks = json.loads((tmp_path / "bench" / "peaks.json").read_text())
    # made-up peaks so that the CPU run's arithmetic runs; never a result
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps(peaks))
    add_cell(tmp_path, "tiny.chat", "tiny_chat", TINY_MIX)
    add_cell(tmp_path, "tiny.decode", "tiny_decode", TINY_CLOSED,
             end_to_end=("setup_s", "output_tok_s"),
             limits={"max_gap": 1e-3, "min_checked_tokens": 1})
    add_cell(tmp_path, "gqa.chat", "tiny_chat", TINY_MIX, arch="gqa")
    add_cell(tmp_path, "gqa.decode", "tiny_decode", TINY_CLOSED, arch="gqa",
             end_to_end=("setup_s", "output_tok_s"),
             limits={"max_gap": 1e-2, "min_checked_tokens": 1})
    return tmp_path
