"""The reference against the serving path, at a tiny size in float32 on
the CPU: prefill at the prompt, then the distilled decode, teacher-forced;
and the fp8 control, which has to read wider gaps than the limit the
program meets, and, put in the program's place, come out not correct
through the harness's own check."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import reference
import run
import weights
from conftest import TINY_CFG

MAX_LEN, T, N = 64, 11, 48


@pytest.fixture(scope="module")
def served():
    """Program logits at positions T-1 .. T+N-2 for a random sequence."""
    program._import_path()
    from repro.models.model import decode_step, prefill
    cfg = program.model_config(TINY_CFG)
    w = weights.make_weights(TINY_CFG, 3)
    p = weights.to_program(w)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (T + N,), 0,
                                        TINY_CFG["vocab"]), np.int32)
    cache, last = prefill(p, jnp.asarray(seq[None, :T]), cfg, MAX_LEN)
    out = [last[0]]
    for t in range(T, T + N - 1):
        cache, lg = decode_step(p, cache, jnp.asarray(seq[None, t:t + 1]), cfg)
        out.append(lg[0, 0])
    return w, seq, jnp.stack(out)


def ref(w, seq, precision="f32"):
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(seq)] = seq
    return reference.logits_at(w, jnp.asarray(padded), T,
                               dims=reference.Dims.of(TINY_CFG),
                               max_len=MAX_LEN, n_out=N, precision=precision)


def test_reference_matches_prefill_and_decode(served):
    w, seq, got = served
    want = ref(w, seq)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-4
    # the decode positions use the modal filter, not the implicit one: a
    # reference that used the implicit filter everywhere would not match
    assert np.max(np.abs(np.asarray(got[1:] - want[1:]))) < 1e-4


def test_program_gap_small_control_gap_large(served):
    w, seq, got = served
    want = ref(w, seq)
    toks = jnp.argmax(got, axis=-1).astype(jnp.int32)
    assert float(jnp.max(reference.gaps(want, toks, N))) < 1e-4
    ctl = jnp.argmax(ref(w, seq, "fp8"), axis=-1).astype(jnp.int32)
    assert float(jnp.max(reference.gaps(want, ctl, N))) > 1e-3


def test_weights_fill_the_programs_tree():
    program._import_path()
    from repro.distributed.sharding import unzip
    from repro.models.model import init_params
    cfg = program.model_config(TINY_CFG)
    theirs = jax.eval_shape(lambda: unzip(init_params(jax.random.PRNGKey(0),
                                                      cfg))[0])
    ours = jax.eval_shape(
        lambda: weights.to_program(weights.make_weights(TINY_CFG, 0)))
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), theirs) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), ours)


def test_weights_from_large_seed_differ():
    a = weights.make_weights(TINY_CFG, 2 ** 31 + 1)
    b = weights.make_weights(TINY_CFG, 2 ** 31 + 1 + 2 ** 32)
    assert not np.array_equal(np.asarray(a["wo"]), np.asarray(b["wo"]))
    c = weights.make_weights(TINY_CFG, 2 ** 31 + 1)
    assert np.array_equal(np.asarray(a["wo"]), np.asarray(c["wo"]))


@pytest.fixture(scope="module")
def greedy_request():
    """A request the program served greedily: prefill, then each argmax
    fed back through the distilled decode."""
    program._import_path()
    from repro.models.model import decode_step, prefill
    cfg = program.model_config(TINY_CFG)
    w = weights.make_weights(TINY_CFG, 5)
    p = weights.to_program(w)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (T,), 0,
                                           TINY_CFG["vocab"]), np.int32)
    cache, last = prefill(p, jnp.asarray(prompt[None]), cfg, MAX_LEN)
    toks = [int(jnp.argmax(last[0]))]
    for _ in range(N - 1):
        cache, lg = decode_step(p, cache, jnp.asarray([[toks[-1]]]), cfg)
        toks.append(int(jnp.argmax(lg[0, 0])))
    served = SimpleNamespace(
        plan=SimpleNamespace(prompt=prompt, temperature=0.0, top_p=1.0),
        req=SimpleNamespace(tokens=toks))
    return w, served


def test_control_in_programs_place_is_not_correct(greedy_request):
    w, served = greedy_request
    mix = {"max_len": MAX_LEN, "output": {"max": N}}
    lim = {"max_gap": 1e-3, "min_checked_tokens": N}
    chk = run.check_served(w, TINY_CFG, mix, [served], control=True)
    assert chk["tokens"] == N
    assert run.verdict(run.compare(chk, lim, 0)), chk
    assert not run.verdict(run.control_compared(chk, lim)), chk
