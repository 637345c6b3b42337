"""A run whose timed path is broken underneath comes out not correct.

Each fault a cell can have (`bench/faults.py`, and its architecture's
`FAULTS`) is planted in the serving program at a tiny size on the CPU (the
harness's look for a TPU skipped), and the whole run, load loop, reference
check and all, has to print `correct: false`. The faults of the decode path
and of a token run on the greedy closed-loop cell; those of the sampler's
nucleus and temperature, which leave greedy rows alone, on the chat cell,
whose sampled requests are checked. The GQA Transformer has the same
faults of the engine and sampler, and its own: a KV cache left unchanged by
the decode step, on its closed-loop cell. A mean over half a batch has no
place in serving, and the exchange between chips exists only in a
four-chip cell, which the benchmark does not have.
"""
import json

import pytest

import faults
import run
from conftest import arch

CHAT = {"top_p_skipped", "temperature_ignored"}
CASES = [(prefix, f) for prefix, name in (("tiny", "multihyena"),
                                          ("gqa", "gqa"))
         for f in sorted(faults.table(arch(name)))]


@pytest.fixture
def fresh_program():
    faults.clear_programs()
    yield
    faults.clear_programs()


@pytest.mark.parametrize("prefix,fault", CASES)
def test_fault_is_not_correct(tiny_root, capsys, monkeypatch, fresh_program,
                              prefix, fault):
    cell = run.Cell(tiny_root,
                    f"{prefix}.{'chat' if fault in CHAT else 'decode'}")
    faults.plant(fault, cell.arch, monkeypatch.setattr)
    rc = run.main(["--workload", cell.name, "--seed", "4", "--seconds", "1.5",
                   "--trace", "0"], root=tiny_root, require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, res["compared"]
