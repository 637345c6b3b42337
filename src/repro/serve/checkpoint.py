"""Engine checkpoint/restore: snapshot a live ContinuousBatchingEngine to
host and resume it bit-exactly in a fresh process.

What makes exact resume cheap here is the same property that makes slot
serving cheap: a request's entire decode state is a fixed-size cache row
plus a handful of per-slot metadata scalars, and the PRNG stream is
position-indexed — fold_in(engine_key, rid) at stream index tok_idx — so
"where every request's randomness is" is fully captured by (rid, tok_idx),
both of which are in the snapshot. Restoring the pooled cache, the device
metadata vectors, and the host bookkeeping therefore continues every
resident request token-for-token as if the process had never died.

Checkpoint format (pickle, `format: 2`): a dict of
  * engine shape/compat: mode, n_slots, max_len, cache_kind
  * mesh: None for a single-device engine, else the slot-pool mesh layout
    (axis names, shape, shard count, per-slot shard ownership) — restore
    refuses a layout mismatch instead of silently resharding, because the
    device buffers in the snapshot are laid out per shard
  * device state (device_get to numpy): cache, draft_cache, meta vectors
    (_temps/_top_ks/_top_ps/_last/_slot_keys/_tok_idx/_spec_len), spec_win
  * host bookkeeping: slots, queue, finished (pickled Request objects —
    object identity between slots/queue entries is preserved), active,
    tick, next_rid, t_admit, stats, resilience counters, buckets_used

Format 1 (pre-sharding) snapshots carry no mesh entry; they still load,
but only into a single-device engine.

Not captured: compiled executables (the restored engine re-warms or
recompiles on demand) and the SlotSpecController's acceptance EMAs (windows
re-adapt from defaults; greedy token-exactness is unaffected because draw
keys are position-indexed, not path-dependent). An in-flight chunked
prefill is requeued whole — its request restarts prefill from scratch.
"""
from __future__ import annotations

import pickle
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_META_KEYS = ("_temps", "_top_ks", "_top_ps", "_last", "_slot_keys",
              "_tok_idx", "_spec_len")
FORMAT = 2


def _mesh_desc(engine) -> Optional[Dict[str, Any]]:
    """Canonical description of the engine's slot-pool layout (None when
    single-device). Compared verbatim at restore: two engines with equal
    descriptions place every slot row on the same shard."""
    if getattr(engine, "mesh", None) is None:
        return None
    mesh = engine.mesh
    return {
        "axis_names": [str(a) for a in mesh.axis_names],
        "shape": [int(s) for s in mesh.devices.shape],
        "n_shards": int(engine._n_shards),
        "slot_shard": [int(engine._shard_of(b))
                       for b in range(engine.n_slots)],
    }


def save_engine(engine, path: Optional[str] = None) -> Dict[str, Any]:
    """Snapshot `engine` to a host-side dict (and pickle it to `path` when
    given). The in-flight overlapped tick is retired first and an in-flight
    chunked prefill is requeued, so the snapshot is a consistent
    between-ticks view; the engine remains usable afterwards."""
    from repro.serve.scheduler import QUEUED

    engine._retire(engine._pending)
    engine._pending = None
    if engine._chunk_state is not None:
        st = engine._chunk_state
        engine._chunk_state = None
        engine.slots[st["slot"]] = None
        req = st["req"]
        req.status = QUEUED
        req.slot = -1
        engine.queue.appendleft(req)
    state: Dict[str, Any] = {
        "format": FORMAT,
        "mode": engine.mode,
        "n_slots": engine.n_slots,
        "max_len": engine.max_len,
        "cache_kind": engine._cache_kind,
        "mesh": _mesh_desc(engine),
        "cache": jax.device_get(engine.cache),
        "draft_cache": (None if engine.draft_cache is None
                        else jax.device_get(engine.draft_cache)),
        "meta": {k: np.asarray(getattr(engine, k)) for k in _META_KEYS},
        "spec_win": engine._spec_win.copy(),
        "active": engine.active.copy(),
        "slots": list(engine.slots),
        "queue": list(engine.queue),
        "finished": list(engine.finished),
        "tick": engine._tick,
        "next_rid": engine._next_rid,
        "t_admit": engine.t_admit,
        "stats": dict(engine.stats),
        "resilience": engine.resilience.snapshot(),
        "buckets_used": sorted(engine._buckets_used),
    }
    engine.resilience.bump("checkpoint_saves")
    engine._record_event("checkpoint_save", path=path)
    if path is not None:
        with open(path, "wb") as f:
            pickle.dump(state, f)
    return state


def load_checkpoint(path: str) -> Dict[str, Any]:
    with open(path, "rb") as f:
        return pickle.load(f)


def restore_engine(engine, state) -> None:
    """Load a `save_engine` snapshot into a freshly constructed engine (same
    arch/params and construction shape). Restoring a snapshot taken after a
    mode-ladder demotion (distilled→cached_conv→epoch) into a higher-mode
    engine replays the demotion first. Resumes bit-exactly: resident slots continue from their
    exact cache rows, stream counters, and last tokens."""
    if isinstance(state, str):
        state = load_checkpoint(state)
    fmt = state.get("format")
    if fmt not in (1, FORMAT):
        raise ValueError(f"unknown checkpoint format {fmt!r}")
    if (state["n_slots"] != engine.n_slots
            or state["max_len"] != engine.max_len):
        raise ValueError(
            f"checkpoint shape (n_slots={state['n_slots']}, "
            f"max_len={state['max_len']}) does not match the engine "
            f"(n_slots={engine.n_slots}, max_len={engine.max_len})")
    here = _mesh_desc(engine)
    if fmt == 1:
        if here is not None:
            raise ValueError(
                "format-1 checkpoint carries no mesh metadata and cannot be "
                "restored into a sharded engine "
                f"(engine slot-pool layout: {here})")
    else:
        saved = state.get("mesh")
        if saved != here:
            raise ValueError(
                f"checkpoint slot-pool mesh layout {saved} does not match "
                f"the engine's {here} — rebuild the engine with the same "
                f"mesh (or restore single-device from a single-device "
                f"snapshot)")
    if state["mode"] != engine.mode:
        from repro.serve.scheduler import MODE_LADDER
        saved_rung = (MODE_LADDER.index(state["mode"])
                      if state["mode"] in MODE_LADDER else -1)
        here_rung = MODE_LADDER.index(engine.mode)
        if saved_rung > here_rung:
            # snapshot was taken after the engine walked down the ladder
            # (fault quarantine or drift alarm): replay the demotion so the
            # restored pool kind matches the saved cache buffers
            engine._demote_engine(state["mode"])
        else:
            raise ValueError(
                f"checkpoint mode {state['mode']!r} does not match engine "
                f"mode {engine.mode!r} (a snapshot only restores into the "
                f"same mode or one higher on the ladder {MODE_LADDER})")
    engine._pending = None
    engine._chunk_state = None
    engine.cache = engine._put_pool(state["cache"], engine._cache_sh)
    if state["draft_cache"] is not None:
        if engine.draft_cache is None:
            raise ValueError("checkpoint has a draft pool but the engine "
                             "was built without one (spec config mismatch)")
        engine.draft_cache = engine._put_pool(state["draft_cache"],
                                              engine._draft_sh)
    for k in _META_KEYS:
        setattr(engine, k, engine._put_slot_vec(state["meta"][k]))
    engine._spec_win[:] = state["spec_win"]
    engine._spec_win_dev[:] = state["spec_win"]
    engine.active[:] = state["active"]
    engine.slots = list(state["slots"])
    engine._n_sampled = sum(
        1 for b, r in enumerate(engine.slots)
        if r is not None and engine.active[b] and r.sampling.temperature > 0)
    from collections import deque
    engine.queue = deque(state["queue"])
    engine.finished = list(state["finished"])
    # the restored engine's dispatch counter starts fresh and no pending
    # exists, so the saved process's staleness marks must not carry over
    for r in list(engine.slots) + list(engine.queue):
        if r is not None:
            r.admit_seq = -1
            r.retry_at = 0
    engine._tick = int(state["tick"])
    engine._next_rid = int(state["next_rid"])
    engine.t_admit = float(state["t_admit"])
    engine.stats.update(state["stats"])
    for k, v in state["resilience"].items():
        engine.resilience.bump(k, v)
    engine._buckets_used.update(state["buckets_used"])
    engine._any_deadline = engine._any_deadline or any(
        r is not None and r.deadline_s is not None
        for r in list(engine.slots) + list(engine.queue))
    engine.resilience.bump("checkpoint_restores")
    engine._record_event("checkpoint_restore")
