"""The chip's published peaks and a roofline.

Operations and bytes are the yardstick's counts, not the compiler's: each
architecture counts them from its shapes (`bench/archs/<arch>.py`:
`decode_flops_per_token`, `prefill_flops` and a work function per kernel in
`KERNELS`), so a change to the program cannot change what it is measured
against.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip of this kind; an unknown kind raises."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in {path}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(work: dict, peak: dict) -> tuple:
    """(least seconds, which bound) of `work` on a chip with `peak`."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
