"""Every seed gets the same work in another order."""
import numpy as np

import traffic
from conftest import TINY_CLOSED, TINY_MIX


def test_same_work_other_order():
    a = traffic.generate(TINY_MIX, 1, 257, 2.0)
    b = traffic.generate(TINY_MIX, 2 ** 33 + 5, 257, 2.0)
    assert len(a) == len(b)
    for phase in (lambda p: p.due_s < 0, lambda p: p.due_s >= 0):
        pa, pb = [p for p in a if phase(p)], [p for p in b if phase(p)]
        assert len(pa) == len(pb)
        for f in (lambda p: len(p.prompt), lambda p: p.max_new_tokens,
                  lambda p: p.temperature):
            assert sorted(map(f, pa)) == sorted(map(f, pb))
        # the gaps between arrivals are n - 1 of the n mid-point quantiles
        n, rate = len(pa), TINY_MIX["rate_rps"]
        q = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
        for ps in (pa, pb):
            g = np.diff([p.due_s for p in ps])
            assert np.abs(g[:, None] - q[None, :]).min(axis=1).max() < 1e-9
    for f in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert list(map(f, a)) != list(map(f, b))


def test_same_seed_same_requests():
    a = traffic.generate(TINY_MIX, 9, 257, 2.0)
    b = traffic.generate(TINY_MIX, 9, 257, 2.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, b))


def test_lengths_within_bounds_and_rate():
    a = traffic.generate(TINY_MIX, 3, 257, 10.0)
    lo, hi = traffic.length_bounds(TINY_MIX["prompt"])
    assert all(lo <= len(p.prompt) <= hi for p in a)
    window = [p for p in a if 0 <= p.due_s < 10.0]
    assert len(window) == 10.0 * TINY_MIX["rate_rps"]
    assert all(p.due_s < 10.0 for p in a)


def test_closed_loop_clients():
    a = traffic.generate(TINY_CLOSED, 3, 257, 2.0)
    assert sorted({p.client for p in a}) == list(range(TINY_CLOSED["clients"]))
    assert all(p.temperature == 0 for p in a)
