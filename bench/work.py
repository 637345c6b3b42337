"""Operations and bytes the served model needs, counted from its shapes.

These are the yardstick's counts, not the compiler's: they depend on the
configuration and the traffic, never on how the program implements a step,
so a change to the program cannot change what it is measured against.

Model FLOPs count what serving a token requires: the projections (q, k, v
and output), the MLP, the short convolution, the long convolution (FFTs at
prefill, the modal recurrence at decode, and the modal state prefill hands
to decode) and one row of the tied LM head per token produced. The implicit
filter's MLP is recomputed on every prefill and is not counted; nor is
bucket padding.

The `ssm_decode` count is of the algorithm at the pool's shapes: the state
(B, D, d/2), real and imaginary, read and written once in its served dtype;
u and y (B, D) in float32; the modal parameters at per-head size (M, d/2)
and h0 (D). Its time bound is bytes over HBM bandwidth: the kernel does
about 11 d/2 FLOPs per 16 d/2 bytes of state.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip of this kind; an unknown kind raises."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in {path}; known: {sorted(table)}")
    return table[device_kind]


def _fft(n: int) -> float:
    """FLOPs of one real FFT of length n (the usual 2.5 n log2 n)."""
    return 2.5 * n * math.log2(n)


def layer_matmul_params(cfg: dict) -> int:
    D, F = cfg["d_model"], cfg["d_ff"]
    return 4 * D * D + 2 * D * F


def decode_flops_per_token(cfg: dict) -> float:
    """One decoded token: projections, MLP, short conv, the modal step
    (output 4 d/2 + 2 and update 7 d/2 per channel) and the LM head."""
    L, D, V = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    d2, W = cfg["distill_order"] // 2, cfg["short_conv"]
    per_layer = (2 * layer_matmul_params(cfg) + 2 * W * 3 * D
                 + D * (11 * d2 + 2) + D)
    return L * per_layer + 2 * V * D


def long_conv_flops(T: int, D: int, M: int) -> float:
    """Causal FFT convolution of T positions: per channel a forward and an
    inverse transform of length 2T and the complex product; per filter
    head one forward transform."""
    n = 2 * T
    return D * (2 * _fft(n) + 6 * (n // 2 + 1)) + M * _fft(n)


def prefill_flops(cfg: dict, T: int) -> float:
    """A prompt of T tokens, up to its first output token."""
    L, D, V, M = cfg["n_layers"], cfg["d_model"], cfg["vocab"], cfg["n_filter_heads"]
    d2, W = cfg["distill_order"] // 2, cfg["short_conv"]
    per_layer = (T * (2 * layer_matmul_params(cfg) + 2 * W * 3 * D + 2 * D)
                 + long_conv_flops(T, D, M)
                 + 2 * 2 * T * D * d2)                 # modal state, re + im
    return L * per_layer + 2 * V * D


def ssm_decode(B: int, cfg: dict, state_itemsize: int = 4) -> dict:
    """FLOPs and bytes of one ssm_decode call over a (B, D, d/2) pool."""
    D, M, d2 = cfg["d_model"], cfg["n_filter_heads"], cfg["distill_order"] // 2
    flops = B * D * (11 * d2 + 2)
    nbytes = (2 * 2 * B * D * d2 * state_itemsize      # re, im; read + write
              + 2 * B * D * 4                         # u in, y out
              + 4 * M * d2 * 4 + D * 4)               # modal params, h0
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_s(work: dict, peak: dict) -> tuple:
    """(least seconds, which bound) of `work` on a chip with `peak`."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
