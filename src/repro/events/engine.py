"""The scanned continuous-time event engine.

One `event_step` consumes one row of the context's `EventTape` inside a
jitted scan: the state's event cursor reads ``(t, client, kind, valid)``
and dispatches through `lax.switch` onto the three handlers —

  KIND_GRAD   B local batches for the acting client through the Task
              optimizer plane (`core.protocol.local_step` with a one-hot
              grad mask), accumulated into the pending backlog;
  KIND_TX     the acting client broadcasts its pending backlog through
              the (optional) wireless channel into the payload ring,
              subject to event-triggered suppression and the Psi cap;
  KIND_UNIFY  the tape's precomputed rotating hub broadcasts its model.

Before the dispatch, every event **drains**: ring messages whose
continuous delivery deadline ``t_send + gamma_link`` has passed are
mixed into the receivers via the fused `gossip_ops.gossip_drain`
(Pallas on TPU, unrolled GEMM + empty-slot skipping elsewhere) — the
same kernel the windowed engine drains with, reused, not forked. The
ring is deadline-stamped rather than age-bucketed: `w_ring` holds the
undelivered effective weights, `deadline_ring` the per-link absolute
delivery times, and draining zeroes exactly the delivered entries, so a
message's per-link copies can arrive at different events.

Ring semantics: broadcast ``b`` lives in slot ``b % D``; enqueueing
broadcast ``b`` evicts broadcast ``b - D`` (drop-on-overwrite — the
depth-D ring is the same outage bound as the windowed engine's
`quantize_delays` drop). Draining walks the D slots oldest-broadcast
first, so the f32 accumulation order is deterministic and matches the
step-by-step reference `repro.events.replay` bit-for-bit.

With the channel disabled, deadlines equal the send time and messages
arrive at the next strictly-later event — the window->0 limit of the
windowed engine's one-window delay.

Padding rows (``valid == False``) are strict no-ops: the whole proposed
state (RNG key and clocks included) is discarded via a scalar select,
so a padded tape equals its unpadded prefix bit-for-bit.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as channel_lib
from repro.core import flat as flat_lib
from repro.core import protocol as protocol_lib
from repro.core.channel import ChannelConfig
from repro.core.protocol import Overrides
from repro.kernels.gossip import ops as gossip_ops


class EventState(NamedTuple):
    params: Any  # pytree, leaves (N, ...)
    pending: jax.Array  # (N, Dflat) f32 — untransmitted backlog (Lemma A.1)
    buffer: jax.Array  # (D, N, Dflat) f32 — raw broadcast payload ring
    w_ring: jax.Array  # (D, N, N) f32 — undelivered effective weights
    deadline_ring: jax.Array  # (D, N, N) f32 — absolute delivery times (s)
    send_time: jax.Array  # (D,) f32 — slot send timestamps (staleness)
    accept_count: jax.Array  # (N,) msgs accepted this unification period
    total_accept: jax.Array  # (N,) msgs accepted over the whole run
    tx_sent: jax.Array  # (N,) broadcasts actually fired (post-suppression)
    tx_count: jax.Array  # scalar i32 — broadcast counter / slot allocator
    event_idx: jax.Array  # scalar i32 — tape cursor
    time: jax.Array  # scalar f32 — last processed event time
    key: jax.Array
    positions: jax.Array  # (N, 2) node coordinates (channel model)
    opt_state: jax.Array = ()  # (N, Dopt) f32 — flat local optimizer plane


def init_event_state(key, cfg, params0, task=None) -> EventState:
    """Replicate `params0` across N clients; empty rings and counters.

    Same (placement, state) key derivation as `protocol.init_state`, so
    an event run and a windowed run started from the same key see the
    same node positions.
    """
    n, d = cfg.num_clients, cfg.max_delay_windows
    kp, ks = jax.random.split(key)
    params = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape).copy(), params0
    )
    spec = flat_lib.spec_of(params)
    pos = channel_lib.place_nodes(kp, n, cfg.channel or ChannelConfig())
    return EventState(
        params=params,
        pending=jnp.zeros((n, spec.dim), jnp.float32),
        buffer=jnp.zeros((d, n, spec.dim), jnp.float32),
        w_ring=jnp.zeros((d, n, n), jnp.float32),
        deadline_ring=jnp.zeros((d, n, n), jnp.float32),
        send_time=jnp.zeros((d,), jnp.float32),
        accept_count=jnp.zeros((n,), jnp.int32),
        total_accept=jnp.zeros((n,), jnp.int32),
        tx_sent=jnp.zeros((n,), jnp.int32),
        tx_count=jnp.zeros((), jnp.int32),
        event_idx=jnp.zeros((), jnp.int32),
        time=jnp.zeros((), jnp.float32),
        key=ks,
        positions=pos,
        opt_state=protocol_lib._opt_plane(task, params0, n),
    )


def event_step(state: EventState, ctx, *, damping=None,
               trigger: float = 0.0) -> EventState:
    """One tape row: drain due messages, then dispatch on the event kind.

    `ctx` is a `SimContext` carrying an `EventTape` (see
    `repro.events.driver.events_context`). `damping` is the staleness
    closure (None = undamped DRACO semantics, bit-for-bit); `trigger` is
    the static event-triggered suppression threshold (0 = always fire).
    Scenario schedules are honored at the *protocol* clock: the step-t
    snapshot is ``ctx.schedule.at(floor(t / window))``, the same ring
    lookup as the windowed engine.
    """
    tape = ctx.tape
    if tape is None:
        raise ValueError(
            "event algorithms need a ctx carrying an EventTape; build one "
            "with repro.events.events_context(...) or call simulate_events")
    cfg = ctx.cfg
    n, D = cfg.num_clients, cfg.max_delay_windows
    spec = ctx.flat_spec
    if spec is None:
        spec = flat_lib.spec_of(state.params)
    ov = ctx.overrides if ctx.overrides is not None else Overrides()

    e = state.event_idx
    t = tape.t[e]
    ci = tape.client[e]
    kind = tape.kind[e]
    valid = tape.valid[e]
    step_t = jnp.floor(t / cfg.window).astype(jnp.int32)

    if ctx.schedule is None:
        q, adj, sched_pos = ctx.q, ctx.adj, None
    else:
        v = ctx.schedule.at(step_t)
        q, adj, sched_pos = v.q, v.adj, v.positions
    pos = state.positions if sched_pos is None else sched_pos

    keys = jax.random.split(state.key, 4)
    k_next, k_gsel, k_chan, _ = keys

    # --- 1. continuous-time drain: everything due by t ---------------------
    with jax.named_scope("event.drain"):
        slots = jnp.mod(state.tx_count + jnp.arange(D, dtype=jnp.int32), D)
        due = state.deadline_ring <= t  # (D, N, N)
        w_live = state.w_ring * due.astype(state.w_ring.dtype)
        w_stack = w_live[slots]
        if damping is not None:
            dtau = (t - state.send_time[slots]) / cfg.window
            w_stack = w_stack * damping(dtau)[:, None, None]
        arrivals_flat = gossip_ops.gossip_drain(w_stack, state.buffer, slots,
                                                use_kernel=ctx.use_kernel)
        arrivals = flat_lib.unravel_clients(arrivals_flat, spec)
        params = jax.tree_util.tree_map(
            lambda p, a: p + a.astype(p.dtype), state.params, arrivals
        )
        w_ring = state.w_ring * (~due).astype(state.w_ring.dtype)

    carry = (params, state.pending, state.opt_state, w_ring,
             state.deadline_ring, state.buffer, state.send_time,
             state.accept_count, state.total_accept, state.tx_sent,
             state.tx_count)

    # --- 2. dispatch on the event kind -------------------------------------
    @jax.named_scope("event.grad")
    def grad_branch(c):
        (params, pending, opt_state, w_ring, dl_ring, buffer, send_time,
         acc, tot, sent, txc) = c
        gm = jnp.arange(n, dtype=jnp.int32) == ci
        delta, opt_state = protocol_lib.local_step(
            k_gsel, params, gm, cfg, ctx.task, ctx.data, opt_state, step_t,
            lr=ov.lr)
        pending = pending + flat_lib.ravel_clients(delta)
        if cfg.apply_self_update:
            params = jax.tree_util.tree_map(
                lambda p, dl: p + dl.astype(p.dtype), params, delta)
        return (params, pending, opt_state, w_ring, dl_ring, buffer,
                send_time, acc, tot, sent, txc)

    @jax.named_scope("event.tx")
    def tx_branch(c):
        (params, pending, opt_state, w_ring, dl_ring, buffer, send_time,
         acc, tot, sent, txc) = c
        sender = jnp.arange(n, dtype=jnp.int32) == ci
        if cfg.channel is not None and cfg.channel.enabled:
            gamma, success = channel_lib.transmission_delays(
                k_chan, pos, sender, cfg.channel)
            success = success & adj
            deadlines = (t + gamma).astype(jnp.float32)
        else:
            # gamma = 0: due at the next strictly-later event (window->0
            # limit of the windowed engine's one-window delay)
            success = adj & sender[:, None]
            deadlines = jnp.full((n, n), t, jnp.float32)
        if trigger > 0:
            fire = jnp.sum(pending[ci] ** 2) >= jnp.float32(trigger) ** 2
        else:
            fire = jnp.asarray(True)
        # Psi cap: a single sender needs no priority permutation — the
        # receiver either has room this period or it does not
        psi = cfg.psi if ov.psi is None else ov.psi
        if isinstance(psi, (int, np.integer)):
            room = success if psi <= 0 else success & (acc[None, :] < psi)
        else:
            psi_eff = jnp.where(psi <= 0, jnp.iinfo(jnp.int32).max // 2,
                                psi.astype(jnp.int32))
            room = success & (acc[None, :] < psi_eff)
        accept = room & fire
        newly = accept.sum(axis=0).astype(jnp.int32)
        acc = acc + newly
        tot = tot + newly
        w_eff = q * accept.astype(q.dtype)

        slot = jnp.mod(txc, D)  # enqueue evicts broadcast txc - D
        buffer = jnp.where(
            fire,
            jax.lax.dynamic_update_slice(buffer, pending[None], (slot, 0, 0)),
            buffer)
        w_ring = jnp.where(fire, w_ring.at[slot].set(w_eff), w_ring)
        dl_ring = jnp.where(fire, dl_ring.at[slot].set(deadlines), dl_ring)
        send_time = jnp.where(fire, send_time.at[slot].set(t), send_time)
        sent = sent + (sender & fire).astype(jnp.int32)
        txc = txc + fire.astype(jnp.int32)
        keep = ~(sender & fire)  # suppressed senders keep their backlog
        pending = pending * keep.astype(jnp.float32)[:, None]
        return (params, pending, opt_state, w_ring, dl_ring, buffer,
                send_time, acc, tot, sent, txc)

    @jax.named_scope("event.unify")
    def unify_branch(c):
        (params, pending, opt_state, w_ring, dl_ring, buffer, send_time,
         acc, tot, sent, txc) = c
        # hub = tape.client (precomputed rotating hub, `unify_hub`)
        params = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[ci][None], x.shape), params)
        acc = jnp.zeros_like(acc)
        return (params, pending, opt_state, w_ring, dl_ring, buffer,
                send_time, acc, tot, sent, txc)

    out = jax.lax.switch(kind, (grad_branch, tx_branch, unify_branch), carry)
    (params, pending, opt_state, w_ring, dl_ring, buffer, send_time,
     acc, tot, sent, txc) = out
    new_state = EventState(
        params=params, pending=pending, buffer=buffer, w_ring=w_ring,
        deadline_ring=dl_ring, send_time=send_time, accept_count=acc,
        total_accept=tot, tx_sent=sent, tx_count=txc, event_idx=e, time=t,
        key=k_next, positions=pos, opt_state=opt_state)
    # padding rows discard everything (key and clocks included), so a
    # padded tape equals its unpadded prefix bit-for-bit
    state = jax.tree_util.tree_map(
        lambda nw, old: jnp.where(valid, nw, old), new_state, state)
    return state._replace(event_idx=e + 1)
