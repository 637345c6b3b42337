"""Faults planted under the serving program, for the checks that
`correct` has to fail. Each wraps one function of the program where it is
looked up at trace time, so an engine built after `plant` runs it:

  * `state_unchanged`: the decode step returns its modal state unchanged;
  * `token_altered`: the per-slot sampler's token is altered where it is
    produced;
  * `wrong_slot`: admission writes a prefilled state into the wrong slot;
  * `zero_prefill_state`: prefill hands decode a zero modal state;
  * `top_p_skipped`: the sampler draws sampled rows from the whole
    distribution, with no nucleus cut;
  * `temperature_ignored`: the sampler draws sampled rows at temperature 1.

Greedy rows stay greedy under the last two, so only the check of sampled
requests can see them.
"""
from __future__ import annotations

import program


def _modules():
    program._import_path()
    from repro.models import hyena
    from repro.serve import scheduler
    return hyena, scheduler


def _state_unchanged(orig):
    def f(x_re, x_im, *a):
        y, _, _ = orig(x_re, x_im, *a)
        return y, x_re, x_im
    return f


def _token_altered(orig):
    def f(keys, logits, **kw):
        return (orig(keys, logits, **kw) + 1) % logits.shape[-1]
    return f


def _wrong_slot(orig):
    def f(pool, multi, slots):
        import jax.numpy as jnp
        B = pool["pos"].shape[0]
        return orig(pool, multi, jnp.where(slots < B, (slots + 1) % B, slots))
    return f


def _zero_state(orig):
    def f(dp, u, hcfg, lengths=None):
        xr, xi = orig(dp, u, hcfg, lengths=lengths)
        return xr * 0, xi * 0
    return f


def _top_p_skipped(orig):
    def f(keys, logits, *, temperature, top_k, top_p):
        import jax.numpy as jnp
        return orig(keys, logits, temperature=temperature, top_k=top_k,
                    top_p=jnp.ones_like(top_p))
    return f


def _temperature_ignored(orig):
    def f(keys, logits, *, temperature, top_k, top_p):
        import jax.numpy as jnp
        t = jnp.asarray(temperature, jnp.float32)
        return orig(keys, logits, temperature=jnp.where(t > 0.0, 1.0, t),
                    top_k=top_k, top_p=top_p)
    return f


# name -> (module index in _modules(), attribute, wrapper)
FAULTS = {
    "state_unchanged": (0, "ssm_decode", _state_unchanged),
    "token_altered": (1, "sample_token_slots", _token_altered),
    "wrong_slot": (1, "write_cache_slots", _wrong_slot),
    "zero_prefill_state": (0, "modal_prefill_state", _zero_state),
    "top_p_skipped": (1, "sample_token_slots", _top_p_skipped),
    "temperature_ignored": (1, "sample_token_slots", _temperature_ignored),
}


def clear_programs() -> None:
    """Forget every executable traced so far, so none built before a
    fault was planted (or removed) is reused."""
    import jax
    program._import_path()
    from repro.serve import engine, scheduler
    engine._JIT_CACHE.clear()
    scheduler._SLOT_JITS.clear()
    jax.clear_caches()


def plant(name: str, setattr_=setattr):
    """Plant fault `name`; returns a function that removes it. `setattr_`
    lets a test use its monkeypatch instead."""
    idx, attr, wrap = FAULTS[name]
    mod = _modules()[idx]
    orig = getattr(mod, attr)
    setattr_(mod, attr, wrap(orig))
    clear_programs()

    def remove():
        setattr(mod, attr, orig)
        clear_programs()
    return remove
