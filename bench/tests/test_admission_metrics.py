"""The admission readers' arithmetic on hand-made requests, and all four
present in a traced CPU run of the tiny chat cell."""
import json
import math

import pytest

import drive
import program
import run

NAMES = ("sched.queue_ms.ttft", "sched.admit_host_ms.ttft",
         "sched.admit_wait_ms.ttft", "step.prefill_useful_share.ttft")


class Ctx:
    """The part of `run.Context` the readers use."""

    def __init__(self, seen, span, prefills_per_step=2):
        self.record = drive.Record(seen=seen)
        self.mix = {"prefills_per_step": prefills_per_step}
        self._span = span

    def host_span(self):
        return self._span


def request(idx, plen, stamps, bucket, rows):
    program._import_path()
    from repro.serve.scheduler import Request
    r = Request(rid=idx, prompt=list(range(plen)), max_new_tokens=4)
    (r.t_submit, r.t_dequeued, r.t_prefill_enqueued,
     r.t_admitted) = stamps
    r.prefill_bucket, r.prefill_rows = bucket, rows
    return drive.Seen(plan=None, req=r)


@pytest.fixture(scope="module")
def readers():
    cell = run.Cell(run.ROOT, "mh153m.chat")
    return {n: cell.reader(n) for n in NAMES}


def test_readers_arithmetic(readers):
    seen = [
        # a two-row call at bucket 8: 5 and 7 prompt tokens
        request(0, 5, (10.0, 10.010, 10.012, 10.040), 8, 2),
        request(1, 7, (10.5, 10.530, 10.531, 10.561), 8, 2),
        # a one-row call at bucket 16: 12 prompt tokens of 2 x 16 computed
        request(2, 12, (11.0, 11.020, 11.024, 11.054), 16, 1),
        # submitted before the span, and one never admitted: left out
        request(3, 9, (9.0, 9.1, 9.2, 9.3), 16, 1),
        request(4, 9, (11.5, 11.6, math.nan, math.nan), 0, 0),
    ]
    ctx = Ctx(seen, (10.0, 12.0))
    got = {n: readers[n].read(ctx) for n in NAMES}
    assert got["sched.queue_ms.ttft"] == pytest.approx((10 + 30 + 20) / 3)
    assert got["sched.admit_host_ms.ttft"] == pytest.approx((2 + 1 + 4) / 3)
    assert got["sched.admit_wait_ms.ttft"] == pytest.approx((28 + 30 + 30) / 3)
    # the three means add up to the mean of t_admitted - t_submit
    total = (40 + 61 + 54) / 3
    assert (got["sched.queue_ms.ttft"] + got["sched.admit_host_ms.ttft"]
            + got["sched.admit_wait_ms.ttft"]) == pytest.approx(total)
    # 24 prompt tokens of 2 x 8 + 2 x 16 = 48 computed
    assert got["step.prefill_useful_share.ttft"] == pytest.approx(50.0)


def test_readers_find_nothing_without_stamps(readers):
    """A program whose requests lack the admission stamps (or a span with
    no admitted request in it) gives nothing to read, and does not raise."""

    class Bare:
        t_submit, t_admitted = 10.5, 10.6
        prompt = [1, 2, 3]

    ctx = Ctx([drive.Seen(plan=None, req=Bare())], (10.0, 12.0))
    assert all(readers[n].read(ctx) is None for n in NAMES)
    assert all(readers[n].read(Ctx([], (10.0, 12.0))) is None for n in NAMES)


def test_traced_tiny_chat_reports_all_four(tiny_root, capsys):
    rc = run.main(["--workload", "tiny.chat", "--seed", str(2 ** 31 + 7),
                   "--seconds", "1.5", "--trace", "1"],
                  root=tiny_root, require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    assert set(NAMES) <= set(m)
    assert 0.0 < m["step.prefill_useful_share.ttft"]["value"] <= 100.0
    assert all(m[n]["value"] >= 0.0 for n in NAMES)
