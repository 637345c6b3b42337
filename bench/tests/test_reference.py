"""Each architecture's reference against the serving path, at a tiny size
in float32 on the CPU: prefill at the prompt, then the served decode,
teacher-forced; and the fp8 control, which has to read wider gaps than the
limit the program meets, and, put in the program's place, come out not
correct through the harness's own check. The architectures are the
benchmark's MultiHyena and the tests' GQA Transformer (`data/gqa.py`)."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import reference
import run
from conftest import ARCHS, TINY_CFG, arch

MAX_LEN, T, N = 64, 11, 48
# how far the served logits may lie from the reference's: MultiHyena serves
# in float32 throughout; the GQA model keeps its KV cache in bfloat16, which
# only its decoded positions read
SERVED_TOL = {"multihyena": 1e-4, "gqa": 1e-2}
MH = arch("multihyena")


def tiny(name):
    """(module, config file's dict, greedy gap limit) of a tiny model."""
    _, cfg, limits = ARCHS[name]
    return arch(name), cfg, limits["max_gap"]


@pytest.fixture(scope="module", params=sorted(ARCHS))
def served(request):
    """Program logits at positions T-1 .. T+N-2 for a random sequence."""
    program._import_path()
    from repro.models.model import decode_step, prefill
    mod, cfg_file, _ = tiny(request.param)
    cfg = mod.model_config(cfg_file)
    w = mod.make_weights(cfg_file, 3)
    p = mod.to_program(w)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (T + N,), 0,
                                        cfg.vocab), np.int32)
    cache, last = prefill(p, jnp.asarray(seq[None, :T]), cfg, MAX_LEN)
    out = [last[0]]
    for t in range(T, T + N - 1):
        cache, lg = decode_step(p, cache, jnp.asarray(seq[None, t:t + 1]), cfg)
        out.append(lg[0, 0])
    return request.param, w, seq, jnp.stack(out)


def ref(name, w, seq, precision="f32"):
    mod, cfg_file, _ = tiny(name)
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(seq)] = seq
    return mod.logits_at(w, jnp.asarray(padded), T, cfg=cfg_file,
                         max_len=MAX_LEN, n_out=N, precision=precision)


def test_reference_matches_prefill_and_decode(served):
    name, w, seq, got = served
    want = ref(name, w, seq)
    # the prompt's last position: the prefill path, float32 everywhere
    assert np.max(np.abs(np.asarray(got[0] - want[0]))) < 1e-4
    # the decoded positions: for MultiHyena the modal filter, not the
    # implicit one (a reference that used the implicit filter everywhere
    # would not match)
    assert np.max(np.abs(np.asarray(got[1:] - want[1:]))) < SERVED_TOL[name]


def test_program_gap_small_control_gap_large(served):
    name, w, seq, got = served
    limit = tiny(name)[2]
    want = ref(name, w, seq)
    toks = jnp.argmax(got, axis=-1).astype(jnp.int32)
    assert float(jnp.max(reference.gaps(want, toks, N))) < limit / 10
    ctl = jnp.argmax(ref(name, w, seq, "fp8"), axis=-1).astype(jnp.int32)
    assert float(jnp.max(reference.gaps(want, ctl, N))) > limit


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_weights_fill_the_programs_tree(name):
    program._import_path()
    from repro.distributed.sharding import unzip
    from repro.models.model import init_params
    mod, cfg_file, _ = tiny(name)
    cfg = mod.model_config(cfg_file)
    theirs = jax.eval_shape(lambda: unzip(init_params(jax.random.PRNGKey(0),
                                                      cfg))[0])
    ours = jax.eval_shape(
        lambda: mod.to_program(mod.make_weights(cfg_file, 0)))
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), theirs) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), ours)


def test_weights_from_large_seed_differ():
    a = MH.make_weights(TINY_CFG, 2 ** 31 + 1)
    b = MH.make_weights(TINY_CFG, 2 ** 31 + 1 + 2 ** 32)
    assert not np.array_equal(np.asarray(a["wo"]), np.asarray(b["wo"]))
    c = MH.make_weights(TINY_CFG, 2 ** 31 + 1)
    assert np.array_equal(np.asarray(a["wo"]), np.asarray(c["wo"]))


@pytest.fixture(scope="module", params=sorted(ARCHS))
def greedy_request(request):
    """A request the program served greedily: prefill, then each argmax
    fed back through the served decode."""
    program._import_path()
    from repro.models.model import decode_step, prefill
    mod, cfg_file, limit = tiny(request.param)
    cfg = mod.model_config(cfg_file)
    w = mod.make_weights(cfg_file, 5)
    p = mod.to_program(w)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (T,), 0,
                                           cfg.vocab), np.int32)
    cache, last = prefill(p, jnp.asarray(prompt[None]), cfg, MAX_LEN)
    toks = [int(jnp.argmax(last[0]))]
    for _ in range(N - 1):
        cache, lg = decode_step(p, cache, jnp.asarray([[toks[-1]]]), cfg)
        toks.append(int(jnp.argmax(lg[0, 0])))
    served = SimpleNamespace(
        plan=SimpleNamespace(prompt=prompt, temperature=0.0, top_p=1.0),
        req=SimpleNamespace(tokens=toks))
    return mod, cfg_file, limit, w, served


def test_control_in_programs_place_is_not_correct(greedy_request):
    mod, cfg_file, limit, w, served = greedy_request
    mix = {"max_len": MAX_LEN, "output": {"max": N}}
    lim = {"max_gap": limit, "min_checked_tokens": N}
    chk = run.check_served(mod, w, cfg_file, mix, [served], control=True)
    assert chk["tokens"] == N
    assert run.verdict(run.compare(chk, lim, 0)), chk
    assert not run.verdict(run.control_compared(chk, lim)), chk
