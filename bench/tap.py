"""Keeps, for every request the engine completes, what its last hybrid
iterations saw and produced, so that the correctness check can replay
them with the plain reference.

The served density and compliance are on the request. What the surrogate
produced is not: with the residual gate rejecting it, its output enters
the hybrid state only as the gate's relative error (``HybridState.err``).
Replaying that error needs the surrogate's input at the last iteration,
the density history ``x[n-11] .. x[n-2]``; at completion the state holds
``x[n-10] .. x[n-1]``. With the served ``x[n]`` these are the designs of
the last 11 iterations, each the input of the next. So the tap wraps the engine's compiled step: on a
step that is some lane's last, it first keeps the oldest history frame of
the step's input. It wraps the harvest too: on the first harvest after
such a step it keeps the gate errors and histories of the whole batch.
Each is one small device program, launched only on those steps, with no
host sync; the step outputs themselves cannot be kept, since the next
step donates them. Nothing is copied to the host before the window
closes.

Attach it while the engine is idle: between the warm-up waves and the
window.
"""
from __future__ import annotations

import threading

import jax


@jax.jit
def _oldest(hist):
    return hist[:, 0]


@jax.jit
def _snap(err, hist):
    # fresh buffers: the next step donates the state they are read from
    return err * 1.0, hist * 1.0


class Tap:
    def __init__(self):
        # uid -> ((err, hist) of the batch, lane, oldest frames of the batch)
        self.records = {}
        self._kept = {}     # shard -> (step output, its input's oldest frames)
        self._snaps = {}    # shard -> (state snapped, (err, hist))
        self._lock = threading.Lock()
        self.launches = 0   # the tap's own programs launched since attach

    def attach(self, engine):
        """Wrap ``engine``'s step and harvest, and compile the tap's own
        programs for every ladder rung on every shard's device now, so that
        none compiles in the window."""
        import jax.numpy as jnp

        c = engine.cfg
        for shard in engine._shards:
            for width in engine.rungs:
                hist = jax.device_put(
                    jnp.zeros((width, c.hist_len, c.nely, c.nelx)),
                    shard.device)
                err = jax.device_put(jnp.zeros((width,)), shard.device)
                jax.block_until_ready((_oldest(hist), _snap(err, hist)))
        step, harvest = engine.step, engine._harvest_lane
        shards, kept, snaps = engine._shards, self._kept, self._snaps
        lock, records = self._lock, self.records

        def tapped_step(params, bp, load_vol, state):
            shard = next((s for s in shards if s.bp is bp), None)
            last = shard is not None and any(
                a is not None and shard.slot_iters[i] == a.req.n_iter - 1
                for i, a in enumerate(shard.slot_adm))
            oldest = _oldest(state.hist) if last else None
            out = step(params, bp, load_vol, state)
            if last:
                with lock:
                    kept[id(shard)] = (out, oldest)
                    self.launches += 1
            return out

        def tapped_harvest(shard, lane, now):
            st = shard.state
            with lock:
                k = kept.get(id(shard))
                s = snaps.get(id(shard))
                if s is None or s[0] is not st:
                    s = (st, _snap(st.err, st.hist))
                    snaps[id(shard)] = s
                    self.launches += 1
            oldest = k[1] if k is not None and k[0] is st else None
            records[shard.slot_adm[lane].req.uid] = (s[1], lane, oldest)
            return harvest(shard, lane, now)

        engine.step = tapped_step
        engine._harvest_lane = tapped_harvest

    def release(self):
        """Drop the step outputs kept for the harvest (the records stay)."""
        with self._lock:
            self._kept.clear()
            self._snaps.clear()

    def lane_values(self, uid):
        """(gate error, history, oldest frame) of a completed request on the
        host, or None where the tap missed it."""
        rec = self.records.get(uid)
        if rec is None or rec[2] is None:
            return None
        (err, hist), lane, oldest = rec
        return (float(jax.device_get(err)[lane]),
                jax.device_get(hist)[lane], jax.device_get(oldest)[lane])
