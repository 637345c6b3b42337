"""Faults planted under the serving program, for the checks that
`correct` has to fail. Each wraps one function of the program where it is
looked up at trace time, so an engine built after `plant` runs it. The
faults of the engine and its sampler serve every architecture:

  * `token_altered`: the per-slot sampler's token is altered where it is
    produced;
  * `wrong_slot`: admission writes a prefilled state into the wrong slot;
  * `top_p_skipped`: the sampler draws sampled rows from the whole
    distribution, with no nucleus cut;
  * `temperature_ignored`: the sampler draws sampled rows at temperature 1.

Greedy rows stay greedy under the last two, so only the check of sampled
requests can see them. Each architecture adds the faults of its own state
(`FAULTS` in `bench/archs/<arch>.py`), in the same form.
"""
from __future__ import annotations

import importlib

import program

SCHEDULER = "repro.serve.scheduler"


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


# name -> (program module, attribute, wrapper)
SHARED = {
    "token_altered": (SCHEDULER, "sample_token_slots", _token_altered),
    "wrong_slot": (SCHEDULER, "write_cache_slots", _wrong_slot),
    "top_p_skipped": (SCHEDULER, "sample_token_slots", _top_p_skipped),
    "temperature_ignored": (SCHEDULER, "sample_token_slots",
                            _temperature_ignored),
}


def table(arch) -> dict:
    """Every fault a cell of this architecture can have."""
    return {**SHARED, **arch.FAULTS}


def clear_programs() -> None:
    """Forget every executable traced so far, so none built before a
    fault was planted (or removed) is reused."""
    import jax
    program._import_path()
    from repro.serve import engine, scheduler
    engine._JIT_CACHE.clear()
    scheduler._SLOT_JITS.clear()
    jax.clear_caches()


def plant(name: str, arch, setattr_=setattr):
    """Plant fault `name` of `arch`; returns a function that removes it.
    `setattr_` lets a test use its monkeypatch instead."""
    modname, attr, wrap = table(arch)[name]
    program._import_path()
    mod = importlib.import_module(modname)
    orig = getattr(mod, attr)
    setattr_(mod, attr, wrap(orig))
    clear_programs()

    def remove():
        setattr(mod, attr, orig)
        clear_programs()
    return remove
