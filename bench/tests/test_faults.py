"""A run whose timed path is broken underneath comes out not correct.

Each fault of `bench/faults.py` is planted in the serving program at a
tiny size on the CPU (the harness's look for a TPU skipped), and the whole
run, load loop, reference check and all, has to print `correct: false`.
The faults of the decode path and of a token run on the greedy closed-loop
cell; those of the sampler's nucleus and temperature, which leave greedy
rows alone, on the chat cell, whose sampled requests are checked. A mean
over half a batch has no place in serving, and the exchange between chips
exists only in a four-chip cell, which the benchmark does not have.
"""
import json

import pytest

import faults
import run

CELL = {"top_p_skipped": "tiny.chat", "temperature_ignored": "tiny.chat"}


@pytest.fixture
def fresh_program():
    faults.clear_programs()
    yield
    faults.clear_programs()


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(tiny_root, capsys, monkeypatch, fresh_program,
                              fault):
    faults.plant(fault, monkeypatch.setattr)
    rc = run.main(["--workload", CELL.get(fault, "tiny.decode"), "--seed",
                   "4", "--seconds", "1.5", "--trace", "0"], root=tiny_root,
                  require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, res["compared"]
