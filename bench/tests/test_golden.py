"""MultiHyena's part of the harness reproduces, bit for bit, what it gave
before it moved into `bench/archs/multihyena.py`
(`data/golden_multihyena.json`, recorded from the harness before the
move): the weights from seed 3 of the tiny config, the reference's logits
for one fixed sequence under them in float32 and in the fp8 control, and
the FLOP and byte counts of both real configuration files. The arrays are
hashed in a process of their own with one Eigen thread, since XLA's CPU
matrix products sum in an order that depends on the thread count."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import DATA, ROOT, TINY_CFG, arch

GOLDEN = json.loads((DATA / "golden_multihyena.json").read_text())
MAX_LEN, T, N = 64, 11, 48


def readings() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    mod = arch("multihyena")
    w = mod.make_weights(TINY_CFG, 3)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        for part in (k, str(a.shape), str(a.dtype)):
            h.update(part.encode())
        h.update(a.tobytes())
    out = {"weights_sha256": h.hexdigest()}
    seq = np.zeros(MAX_LEN, np.int32)
    seq[:T + N] = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (T + N,), 0, TINY_CFG["vocab"]), np.int32)
    for p in ("f32", "fp8"):
        lg = mod.logits_at(w, jnp.asarray(seq), T, cfg=TINY_CFG,
                           max_len=MAX_LEN, n_out=N, precision=p)
        out[f"logits_{p}_sha256"] = hashlib.sha256(
            np.asarray(lg).tobytes()).hexdigest()
    return out


@pytest.fixture(scope="module")
def hashed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_cpu_multi_thread_eigen=false").strip())
    res = subprocess.run([sys.executable, __file__], env=env, timeout=300,
                         capture_output=True, text=True,
                         cwd=Path(__file__).parent)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("key", ["weights_sha256", "logits_f32_sha256",
                                 "logits_fp8_sha256"])
def test_same_weights_and_reference(hashed, key):
    assert hashed[key] == GOLDEN[key]


@pytest.mark.parametrize("config", sorted(GOLDEN["counts"]))
def test_same_counts(config):
    mod = arch("multihyena")
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    want = GOLDEN["counts"][config]
    assert mod.decode_flops_per_token(cfg) == want["decode_flops_per_token"]
    assert mod.prefill_flops(cfg, 512) == want["prefill_flops_512"]
    assert mod.ssm_decode(64, cfg, 2) == want["ssm_decode_64_bf16"]
    assert mod.ssm_decode(64, cfg, 4) == want["ssm_decode_64_f32"]
    assert mod.KERNELS["ssm_decode"][1](64, cfg, 2) == \
        want["ssm_decode_64_bf16"]


if __name__ == "__main__":
    print(json.dumps(readings()))
