"""DRACO: the decentralized asynchronous protocol (Algorithm 1/2).

Compiled simulation over *superposition windows* (the paper's own
discretization device, Sec. 2.2): one `draco_window` = one jit step.
Within a window each client independently (Poisson thinning):

  - fires a *gradient event*: B local SGD batches -> accumulates a pending
    update Delta (backups accumulate between transmissions, Lemma A.1);
  - fires a *transmission event*: broadcasts its pending Delta through the
    (optional) unreliable wireless channel; per-link delays are quantized
    to windows and routed through a ring delay-buffer;
  - receives: messages arriving this window are aggregated with the
    row-stochastic weights, x_j += sum_i q[i,j] Delta_i, subject to the
    Psi cap (Definition 1);
  - periodic unification: every P windows a rotating hub broadcasts its
    reference model and every client adopts it (x_j <- x_hub).

Computation and communication schedules are fully decoupled: the grad and
tx processes are independent, and nothing ever waits.

Fused gossip engine (PR 2)
--------------------------
The communication state lives on the *flat parameter plane*
(`repro.core.flat`): `DracoState.buffer` is one contiguous
``(D, N, Dflat)`` f32 ring of **raw broadcast payloads**, and the
delay-bucketed mixing is deferred from enqueue to drain:

  - enqueue (send window w): write the sender's flat pending matrix into
    ring slot ``w % D`` together with that window's effective weights
    ``Q ⊙ accept`` and per-link delay matrix — O(N·Dflat) instead of the
    seed's D-1 full-pytree masked einsums per window;
  - drain (window w): everything arriving now is
    ``sum_j (Q_j ⊙ [delay_j == age_j])^T @ buffer[slot_j]`` over the D-1
    stored broadcasts — one fused pass (`gossip_ops.gossip_drain`):
    a single Pallas grid on TPU, an unrolled GEMM loop with
    empty-bucket skipping elsewhere.

The accumulation order (oldest broadcast first) matches the seed ring
buffer exactly, so the fused engine is bit-for-bit equal to the legacy
path at f32 — enforced by tests/test_protocol_parity.py against the
`*_legacy` reference implementations kept at the bottom of this module.

Task layer (PR 5)
-----------------
The workload slot of every step function accepts either a bare
``loss(params, x, y)`` callable — the legacy plain-SGD path, compiled
graph unchanged — or a `repro.tasks.Task` bundling model init/apply, a
federated dataset, an eval metric, and a **local optimizer** from
`repro.optim` whose per-client state rides a flat ``(N, Dopt)`` plane
(`DracoState.opt_state`) next to the ``(N, Dflat)`` payloads. The
optimizer plane is client-local: it is never gossiped, and hub
unification overwrites params only. Dispatch lives in `local_step`;
the default ``linear-softmax`` + ``sgd(constant)`` task is bit-for-bit
the bare-loss path (tests/test_tasks.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

import jax.flatten_util

from repro.core import channel as channel_lib
from repro.core import flat as flat_lib
from repro.core.channel import ChannelConfig
from repro.core.events import sample_event_masks
from repro.core.topology import adjacency, row_stochastic
from repro.kernels.gossip import ops as gossip_ops
from repro.optim.optimizers import apply_updates


@dataclass(frozen=True)
class DracoConfig:
    num_clients: int = 25
    lr: float = 0.05  # gamma
    local_batches: int = 1  # B
    batch_size: int = 64
    window: float = 1.0  # superposition window length (s)
    lambda_grad: float = 0.1  # Assumption 1 rate (paper default)
    lambda_tx: float = 0.1
    unify_period: int = 50  # P, in windows (0 = no unification)
    psi: int = 0  # max accepted msgs / client / period (0 = unbounded)
    topology: str = "cycle"
    max_delay_windows: int = 4  # ring buffer depth D (>= 2)
    apply_self_update: bool = False  # paper: senders do NOT apply own Delta
    channel: Optional[ChannelConfig] = None

    def __post_init__(self):
        if self.num_clients <= 0:
            raise ValueError(
                f"num_clients must be positive, got {self.num_clients}")
        if self.window <= 0:
            raise ValueError(
                f"window must be positive, got {self.window}")
        if self.max_delay_windows < 2:
            # the drain walks ages 1..D-1; D < 2 leaves no in-flight slot
            # and the ring silently degenerates to "nothing ever arrives"
            raise ValueError(
                "max_delay_windows must be >= 2 (depth-D ring holds D-1 "
                f"in-flight windows), got {self.max_delay_windows}")
        if self.psi < 0:
            raise ValueError(
                f"psi must be >= 0 (0 = unbounded), got {self.psi}")
        if self.unify_period < 0:
            raise ValueError(
                f"unify_period must be >= 0 (0 = never), got {self.unify_period}")

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


class Overrides(NamedTuple):
    """Traced per-run overrides of sweepable `DracoConfig` fields.

    The sweep engine (`repro.api.sweep`) re-binds these inside one
    compiled call, so an lr/Psi/lambda grid shares a single trace instead
    of recompiling per config. `None` fields fall back to the static
    config value — an all-None `Overrides` is bit-for-bit the plain
    config path. `psi` follows the config convention: values <= 0 mean
    unbounded reception.
    """

    lr: Optional[jax.Array] = None
    lambda_grad: Optional[jax.Array] = None
    lambda_tx: Optional[jax.Array] = None
    psi: Optional[jax.Array] = None


class DracoState(NamedTuple):
    params: Any  # pytree, leaves (N, ...)
    pending: jax.Array  # (N, Dflat) f32 — accumulated untransmitted updates
    buffer: jax.Array  # (D, N, Dflat) f32 — raw broadcast payload ring
    w_ring: jax.Array  # (D, N, N) f32 — per-slot effective weights Q ⊙ accept
    delay_ring: jax.Array  # (D, N, N) int32 — per-slot per-link delays
    accept_count: jax.Array  # (N,) messages accepted this period
    total_accept: jax.Array  # (N,) messages accepted over the whole run
    window_idx: jax.Array  # scalar int32
    key: jax.Array
    positions: jax.Array  # (N, 2) node coordinates (channel model)
    opt_state: jax.Array = ()  # (N, Dopt) f32 — flat local optimizer plane


def _opt_plane(task, params0, n) -> jax.Array:
    """Zero-initialized (N, Dopt) optimizer plane for `task` (Dopt=0 for
    bare-loss/plain-SGD workloads — an empty column block)."""
    from repro.tasks.base import opt_width

    return jnp.zeros((n, opt_width(task, params0)), jnp.float32)


def init_state(key, cfg: DracoConfig, params0, task=None) -> DracoState:
    """params0: single-client param pytree -> replicated across N clients.

    `task` (a `repro.tasks.Task`), when given, sizes the flat local
    optimizer plane `opt_state` from its update rule (momentum -> Dflat,
    adamw -> 2*Dflat + a per-client step counter); None or a bare loss
    callable means plain SGD and
    an empty (N, 0) plane — the pre-task layout, bit-for-bit."""
    n, d = cfg.num_clients, cfg.max_delay_windows
    kp, ks = jax.random.split(key)
    params = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape).copy(), params0
    )
    spec = flat_lib.spec_of(params)
    pos = channel_lib.place_nodes(kp, n, cfg.channel or ChannelConfig())
    return DracoState(
        params=params,
        pending=jnp.zeros((n, spec.dim), jnp.float32),
        buffer=jnp.zeros((d, n, spec.dim), jnp.float32),
        w_ring=jnp.zeros((d, n, n), jnp.float32),
        delay_ring=jnp.zeros((d, n, n), jnp.int32),
        accept_count=jnp.zeros((n,), jnp.int32),
        total_accept=jnp.zeros((n,), jnp.int32),
        window_idx=jnp.zeros((), jnp.int32),
        key=ks,
        positions=pos,
        opt_state=_opt_plane(task, params0, n),
    )


def local_updates(key, params, grad_mask, cfg, loss_fn, data, *, lr=None):
    """Per-client B-batch local SGD; returns Delta pytree (N, ...).

    `lr`, when given, is a traced learning-rate override (config sweeps);
    None keeps the static `cfg.lr` bit-for-bit."""
    xs, ys = data
    n = cfg.num_clients
    lr = cfg.lr if lr is None else lr

    def one_client(p_i, key_i, x_i, y_i):
        def body(p, k):
            idx = jax.random.randint(k, (cfg.batch_size,), 0, x_i.shape[0])
            g = jax.grad(loss_fn)(p, x_i[idx], y_i[idx])
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

        keys = jax.random.split(key_i, cfg.local_batches)
        y_b, _ = jax.lax.scan(body, p_i, keys)
        return jax.tree_util.tree_map(lambda yb, p: yb - p, y_b, p_i)

    keys = jax.random.split(key, n)
    delta = jax.vmap(one_client)(params, keys, xs, ys)
    gm = grad_mask.astype(jnp.float32)
    return jax.tree_util.tree_map(
        lambda dl: dl * gm.reshape((n,) + (1,) * (dl.ndim - 1)), delta
    )


def task_local_updates(key, params, grad_mask, cfg, task, data, opt_state,
                       step, *, lr=None):
    """Per-client B-batch local updates through the task's optimizer.

    The task-layer generalization of `local_updates`: each local batch
    computes a gradient and feeds it to the task's `repro.optim` update
    rule instead of the hard-coded ``p - lr*g``. The per-client optimizer
    state lives on the flat plane — `opt_state` is the ``(N, Dopt)`` f32
    matrix; inside the per-client body it is unraveled into the
    optimizer's pytree (exact reshape/concat round-trip) and raveled
    back out. Clients whose `grad_mask` is off fired no gradient event:
    their delta is zeroed (as before) **and** their optimizer state is
    left untouched.

    With the default plain SGD + constant schedule this is bit-for-bit
    `local_updates` (``p + g*(-lr)`` and ``p - lr*g`` are the same f32
    values; tests/test_tasks.py pins the equality through full runs).

    `step` (traced int32) feeds the lr schedule and AdamW bias
    correction — the protocol's window/round counter, shared by the B
    in-window batches. `lr`, when given, is a traced override re-seeding
    the schedule (config sweeps); None keeps the static `cfg.lr`.
    Returns ``(delta pytree (N, ...), new opt_state (N, Dopt))``.
    """
    xs, ys = data
    n = cfg.num_clients
    lr = cfg.lr if lr is None else lr
    opt = task.make_optimizer(lr)
    loss_fn = task.loss_fn

    def one_client(p_i, key_i, x_i, y_i, o_i):
        _, unravel = jax.flatten_util.ravel_pytree(opt.init(p_i))
        o0 = unravel(o_i)

        def body(carry, k):
            p, o = carry
            idx = jax.random.randint(k, (cfg.batch_size,), 0, x_i.shape[0])
            g = jax.grad(loss_fn)(p, x_i[idx], y_i[idx])
            upd, o = opt.update(g, o, p, step)
            return (apply_updates(p, upd), o), None

        keys = jax.random.split(key_i, cfg.local_batches)
        (p_b, o), _ = jax.lax.scan(body, (p_i, o0), keys)
        delta = jax.tree_util.tree_map(lambda pb, p: pb - p, p_b, p_i)
        return delta, jax.flatten_util.ravel_pytree(o)[0]

    keys = jax.random.split(key, n)
    delta, opt_new = jax.vmap(one_client)(params, keys, xs, ys, opt_state)
    gm = grad_mask.astype(jnp.float32)
    delta = jax.tree_util.tree_map(
        lambda dl: dl * gm.reshape((n,) + (1,) * (dl.ndim - 1)), delta
    )
    opt_new = jnp.where(grad_mask[:, None], opt_new, opt_state)
    return delta, opt_new


def local_step(key, params, grad_mask, cfg, task, data, opt_state, step, *,
               lr=None):
    """Dispatch local updates by workload representation.

    A bare loss callable (or None task) runs the seed `local_updates`
    graph unchanged — the exact pre-task compiled path, the `opt_state`
    (N, Dopt) optimizer plane threaded through untouched. A
    `repro.tasks.Task` routes through `task_local_updates` (pluggable
    optimizer, state on the flat plane).
    """
    if task is None or not hasattr(task, "loss_fn"):
        return (local_updates(key, params, grad_mask, cfg, task, data, lr=lr),
                opt_state)
    return task_local_updates(key, params, grad_mask, cfg, task, data,
                              opt_state, step, lr=lr)


def _psi_accept(key, success, accept_count, psi):
    """Per-(sender, receiver) acceptance under the Psi cap.

    Random sender priority; receiver j accepts while its period count +
    rank < psi. Returns (accept mask (N,N), new accept_count).

    `psi` may be a static int (the config path) or a traced int scalar
    (config sweeps). A traced psi <= 0 encodes "unbounded" via a cap no
    run can reach, which reproduces the static unbounded path bit-for-bit
    (the rank test degenerates to `arrivals > 0`)."""
    n = success.shape[0]
    arrivals = success.astype(jnp.int32)
    if isinstance(psi, (int, np.integer)):
        if psi <= 0:
            return success, accept_count + arrivals.sum(axis=0)
    else:
        psi = jnp.where(psi <= 0, jnp.iinfo(jnp.int32).max // 2,
                        psi.astype(jnp.int32))
    perm = jax.random.permutation(key, n)  # sender priority order
    inv = jnp.argsort(perm)
    s_perm = arrivals[perm]  # reorder senders
    rank = jnp.cumsum(s_perm, axis=0) - s_perm  # msgs ahead of me (per recv)
    ok_perm = (rank + accept_count[None, :] < psi) & (s_perm > 0)
    ok = ok_perm[inv]
    new_count = accept_count + ok.sum(axis=0).astype(jnp.int32)
    return ok & success, new_count


def quantize_delays(gamma, window: float, max_delay_windows: int):
    """Per-link delay in superposition windows + deliverability mask.

    ``delay_w = clip(ceil(gamma / window), 1, D-1)`` routes each link
    through the depth-D ring; a link whose true delay spans >= D windows
    cannot be delivered from the ring at its actual age, so it is
    **dropped** (channel-outage semantics) rather than silently delivered
    early at age D-1 — the exact boundary ``gamma = (D-1) * window`` is
    still deliverable. Returns (delay_w (N,N) int32, deliverable (N,N)
    bool)."""
    raw = jnp.ceil(gamma / window).astype(jnp.int32)  # >= 1 typically
    deliverable = raw <= max_delay_windows - 1
    return jnp.clip(raw, 1, max_delay_windows - 1), deliverable


def _tx_and_accept(state, cfg, q, adj, k_tx, k_chan, k_psi, positions=None,
                   tx_rate=None, overrides=None):
    """Transmission events + channel + Psi cap (shared by both engines).

    `positions`/`tx_rate`, when given (scenario schedules), override the
    state-carried node coordinates and scale the per-client Poisson tx
    rate; None means the frozen-path behavior, bit-for-bit. `overrides`
    (an `Overrides`) re-binds lambda_tx/psi with traced values for the
    sweep engine.

    Returns (tx_mask (N,), w_eff (N,N), delay_w (N,N) int32,
    accept_count, total_accept)."""
    n, D = cfg.num_clients, cfg.max_delay_windows
    ov = overrides or Overrides()
    lam_tx = cfg.lambda_tx if ov.lambda_tx is None else ov.lambda_tx
    if tx_rate is not None:
        lam_tx = lam_tx * tx_rate
    tx_mask = sample_event_masks(k_tx, lam_tx, cfg.window, n)
    if cfg.channel is not None and cfg.channel.enabled:
        pos = state.positions if positions is None else positions
        gamma, success = channel_lib.transmission_delays(
            k_chan, pos, tx_mask, cfg.channel
        )
        delay_w, deliverable = quantize_delays(gamma, cfg.window, D)
        success = success & deliverable & adj
    else:
        success = adj & tx_mask[:, None]
        delay_w = jnp.ones((n, n), jnp.int32)

    psi = cfg.psi if ov.psi is None else ov.psi
    accept, accept_count = _psi_accept(k_psi, success, state.accept_count, psi)
    # cumulative counter survives the periodic accept_count reset
    total_accept = state.total_accept + (accept_count - state.accept_count)
    w_eff = q * accept.astype(q.dtype)  # (sender, receiver)
    return tx_mask, w_eff, delay_w, accept_count, total_accept


def _unify(params, accept_count, widx, cfg, n):
    """Periodic unification: rotating hub broadcast + accept-count reset."""

    def unify(args):
        p, cnt = args
        hub = jnp.mod((widx // jnp.maximum(cfg.unify_period, 1)), n)
        p = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[hub][None], x.shape), p
        )
        return p, jnp.zeros_like(cnt)

    do_unify = jnp.mod(widx + 1, cfg.unify_period) == 0
    return jax.lax.cond(do_unify, unify, lambda a: a, (params, accept_count))


def drain_weights(state: DracoState, D: int):
    """`(ages, slots, w_stack)` of the drain at `state.window_idx`.

    The stored broadcast of age j (sent in window widx-j) arrives now iff
    its per-link delay equals j. Stacked oldest-first, so the f32
    accumulation order matches the seed ring buffer exactly; the drain's
    `(J, N, N)` weights against ring rows `slots` (J = D-1)."""
    ages = jnp.arange(D - 1, 0, -1, dtype=jnp.int32)
    slots = jnp.mod(state.window_idx - ages, D)
    w_stack = state.w_ring[slots] * (
        state.delay_ring[slots] == ages[:, None, None]
    ).astype(state.w_ring.dtype)
    return ages, slots, w_stack


def draco_window(state: DracoState, cfg: DracoConfig, q, adj, task, data,
                 spec=None, *, positions=None, compute_rate=None,
                 tx_rate=None, overrides=None, damping=None,
                 use_kernel=None):
    """One superposition window on the fused gossip engine.

    Bit-for-bit equal to `draco_window_legacy` at f32 (the parity suite
    enforces it); see the module docstring for the enqueue/drain design.
    `q` (N, N) is the row-stochastic mixing matrix, `adj` its boolean
    adjacency.
    `task` is the workload: a `repro.tasks.Task` (model + data + local
    optimizer, state on the flat plane) or — the legacy shim — a bare
    ``loss(params, x, y)`` callable, which runs the seed plain-SGD graph
    unchanged. `spec` is the flat-plane layout (`FlatSpec`); pass the
    one stored on `SimContext` to share it across steps, or omit it to
    derive it from `state.params` at trace time.

    The keyword-only trio carries a scenario schedule's step-t snapshot
    (`repro.scenarios`): `positions` (N, 2) overrides the state-carried
    node coordinates for this window's channel draws (and is written
    back to the state, so mobility is visible downstream);
    `compute_rate`/`tx_rate` (N,) scale the per-client Poisson
    grad/transmission rates (straggler profiles modulate the decoupled
    computation schedule without touching the comms schedule). All
    default to None == the frozen-graph path, bit-for-bit.

    `overrides` (an `Overrides`) re-binds lr/lambda/psi with *traced*
    scalars — the sweep engine's config axis; None fields keep the
    static config values bit-for-bit.

    `damping` is an optional age-indexed ``(D,)`` f32 vector scaling the
    drain's per-bucket weights: the bucket whose messages are ``j``
    windows old is multiplied by ``damping[j]`` before the fused drain —
    the staleness-adaptive mixing hook (`repro.events.staleness`
    builds the FedAsync constant/hinge/poly vectors). None keeps the
    undamped drain bit-for-bit.

    `use_kernel` picks the drain's lowering (`gossip_ops.gossip_drain`):
    None chooses by backend, False forces XLA's, which a program
    partitioned over several devices needs.

    The phases run under `jax.named_scope`s (`draco.drain`,
    `draco.local_step`, `draco.tx`, `draco.enqueue`, `draco.unify`),
    which name their ops in a profile and change no arithmetic.
    """
    n, D = cfg.num_clients, cfg.max_delay_windows
    ov = overrides or Overrides()
    keys = jax.random.split(state.key, 8)
    k_next, k_grad, k_gsel, k_tx, k_chan, k_psi, k_hub, _ = keys
    widx = state.window_idx
    if spec is None:
        spec = flat_lib.spec_of(state.params)

    # --- 1. deliveries: fused delay-bucketed drain on the flat plane ------
    with jax.named_scope("draco.drain"):
        ages, slots, w_stack = drain_weights(state, D)
        if damping is not None:
            w_stack = w_stack * damping[ages][:, None, None]
        arrivals_flat = gossip_ops.gossip_drain(w_stack, state.buffer, slots,
                                                use_kernel=use_kernel)
        arrivals = flat_lib.unravel_clients(arrivals_flat, spec)
        params = jax.tree_util.tree_map(
            lambda p, a: p + a.astype(p.dtype), state.params, arrivals
        )

    # --- 2. gradient events ------------------------------------------------
    with jax.named_scope("draco.local_step"):
        lam_g = cfg.lambda_grad if ov.lambda_grad is None else ov.lambda_grad
        if compute_rate is not None:
            lam_g = lam_g * compute_rate
        grad_mask = sample_event_masks(k_grad, lam_g, cfg.window, n)
        delta, opt_state = local_step(k_gsel, params, grad_mask, cfg, task, data,
                                      state.opt_state, widx, lr=ov.lr)
        pending = state.pending + flat_lib.ravel_clients(delta)
        if cfg.apply_self_update:
            params = jax.tree_util.tree_map(
                lambda p, dl: p + dl.astype(p.dtype), params, delta
            )

    # --- 3. transmission events + channel ----------------------------------
    with jax.named_scope("draco.tx"):
        tx_mask, w_eff, delay_w, accept_count, total_accept = _tx_and_accept(
            state, cfg, q, adj, k_tx, k_chan, k_psi, positions=positions,
            tx_rate=tx_rate, overrides=overrides,
        )

    # enqueue: write this window's broadcast (payload + per-link metadata)
    # into ring slot widx % D; the bucketed mixing happens at drain time
    with jax.named_scope("draco.enqueue"):
        slot = jnp.mod(widx, D)
        buffer = jax.lax.dynamic_update_slice(
            state.buffer, pending[None], (slot, 0, 0)
        )
        w_ring = state.w_ring.at[slot].set(w_eff)
        delay_ring = state.delay_ring.at[slot].set(delay_w)

        # senders clear their pending backlog (Lemma A.1 backups are now sent)
        pending = pending * (~tx_mask).astype(jnp.float32)[:, None]

    # --- 4. periodic unification -------------------------------------------
    if cfg.unify_period > 0:
        with jax.named_scope("draco.unify"):
            params, accept_count = _unify(params, accept_count, widx, cfg, n)

    return DracoState(
        params=params,
        pending=pending,
        buffer=buffer,
        w_ring=w_ring,
        delay_ring=delay_ring,
        accept_count=accept_count,
        total_accept=total_accept,
        window_idx=widx + 1,
        key=k_next,
        positions=state.positions if positions is None else positions,
        opt_state=opt_state,
    )


@partial(jax.jit, static_argnames=("cfg", "task", "num_windows"))
def run_windows(state, cfg: DracoConfig, q, adj, task, data, num_windows: int):
    """`task`: a `repro.tasks.Task` or a bare loss callable (legacy);
    `q` (N, N) row-stochastic mixing weights."""
    def step(s, _):
        return draco_window(s, cfg, q, adj, task, data), None

    state, _ = jax.lax.scan(step, state, None, length=num_windows)
    return state


def build_graph(cfg: DracoConfig, key=None):
    adj = adjacency(cfg.topology, cfg.num_clients, key=key)
    q = row_stochastic(adj)
    return q, adj


def virtual_global_model(params):
    """x_bar = E_i[x^(i)] (Sec. 2.1) — evaluation-only."""
    return jax.tree_util.tree_map(lambda p: p.mean(axis=0), params)


# ---------------------------------------------------------------------------
# Seed reference engine (pre-fusion), kept verbatim as the bit-for-bit
# oracle for the fused path (tests/test_protocol_parity.py) and as the
# baseline of `benchmarks.run.bench_draco_window`.  Do not optimize.
# ---------------------------------------------------------------------------


class DracoStateLegacy(NamedTuple):
    params: Any  # leaves (N, ...)
    pending: Any  # accumulated untransmitted local updates (N, ...)
    buffer: Any  # in-flight weighted deltas (D, N, ...)
    accept_count: jax.Array  # (N,) messages accepted this period
    total_accept: jax.Array  # (N,) messages accepted over the whole run
    window_idx: jax.Array  # scalar int32
    key: jax.Array
    positions: jax.Array  # (N, 2) node coordinates (channel model)
    opt_state: jax.Array = ()  # (N, Dopt) f32 — flat local optimizer plane


def init_state_legacy(key, cfg: DracoConfig, params0,
                      task=None) -> DracoStateLegacy:
    """Seed layout: per-leaf pytree buffers of already-mixed deltas.
    `task` sizes the flat optimizer plane exactly as in `init_state`."""
    n, d = cfg.num_clients, cfg.max_delay_windows
    kp, ks = jax.random.split(key)
    params = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape).copy(), params0
    )
    pending = jax.tree_util.tree_map(jnp.zeros_like, params)
    buffer = jax.tree_util.tree_map(
        lambda p: jnp.zeros((d,) + p.shape, p.dtype), params
    )
    pos = channel_lib.place_nodes(kp, n, cfg.channel or ChannelConfig())
    return DracoStateLegacy(
        params=params,
        pending=pending,
        buffer=buffer,
        accept_count=jnp.zeros((n,), jnp.int32),
        total_accept=jnp.zeros((n,), jnp.int32),
        window_idx=jnp.zeros((), jnp.int32),
        key=ks,
        positions=pos,
        opt_state=_opt_plane(task, params0, n),
    )


def draco_window_legacy(state: DracoStateLegacy, cfg: DracoConfig, q, adj,
                        loss_fn, data) -> DracoStateLegacy:
    """Seed window: D-1 per-bucket full-pytree einsums at enqueue time.

    Deliberately self-contained (no code shared with `draco_window`
    beyond the local-update machinery (`local_step`) and `_psi_accept`,
    which predate the fusion), so the parity suite compares two
    independent *gossip engines* rather than one refactor of the other.
    `loss_fn` may be a `repro.tasks.Task` — the oracle for task-layer
    parity runs (the dispatcher keeps the bare-callable graph verbatim).
    `q` (N, N) is the row-stochastic mixing matrix."""
    n, D = cfg.num_clients, cfg.max_delay_windows
    keys = jax.random.split(state.key, 8)
    k_next, k_grad, k_gsel, k_tx, k_chan, k_psi, k_hub, _ = keys
    widx = state.window_idx

    # --- 1. deliveries: drain this window's buffer slot -------------------
    slot = jnp.mod(widx, D)
    arrivals = jax.tree_util.tree_map(lambda b: b[slot], state.buffer)
    params = jax.tree_util.tree_map(
        lambda p, a: p + a.astype(p.dtype), state.params, arrivals
    )
    buffer = jax.tree_util.tree_map(
        lambda b: b.at[slot].set(jnp.zeros_like(b[slot])), state.buffer
    )

    # --- 2. gradient events ------------------------------------------------
    grad_mask = sample_event_masks(k_grad, cfg.lambda_grad, cfg.window, n)
    delta, opt_state = local_step(k_gsel, params, grad_mask, cfg, loss_fn,
                                  data, state.opt_state, widx)
    pending = jax.tree_util.tree_map(lambda a, b: a + b, state.pending, delta)
    if cfg.apply_self_update:
        params = jax.tree_util.tree_map(
            lambda p, dl: p + dl.astype(p.dtype), params, delta
        )

    # --- 3. transmission events + channel ----------------------------------
    tx_mask = sample_event_masks(k_tx, cfg.lambda_tx, cfg.window, n)
    if cfg.channel is not None and cfg.channel.enabled:
        gamma, success = channel_lib.transmission_delays(
            k_chan, state.positions, tx_mask, cfg.channel
        )
        delay_raw = jnp.ceil(gamma / cfg.window).astype(jnp.int32)  # >= 1 typ.
        delay_w = jnp.clip(delay_raw, 1, D - 1)
        # a link spanning >= D windows cannot live in a depth-D ring:
        # dropped (outage), never delivered early at age D-1
        success = success & (delay_raw <= D - 1) & adj
    else:
        success = adj & tx_mask[:, None]
        delay_w = jnp.ones((n, n), jnp.int32)

    accept, accept_count = _psi_accept(k_psi, success, state.accept_count,
                                       cfg.psi)
    # cumulative counter survives the periodic accept_count reset below
    total_accept = state.total_accept + (accept_count - state.accept_count)
    w_eff = q * accept.astype(q.dtype)  # (sender, receiver)

    # enqueue into the ring buffer, bucketed by relative delay
    def enqueue(buf, pend):
        for d in range(1, D):
            w_d = w_eff * (delay_w == d).astype(q.dtype)
            contrib = jnp.einsum("nm,n...->m...", w_d, pend.astype(jnp.float32))
            buf = buf.at[jnp.mod(widx + d, D)].add(contrib.astype(buf.dtype))
        return buf

    buffer = jax.tree_util.tree_map(enqueue, buffer, pending)

    # senders clear their pending backlog (Lemma A.1 backups are now sent)
    keep = (~tx_mask).astype(jnp.float32)
    pending = jax.tree_util.tree_map(
        lambda pnd: pnd * keep.reshape((n,) + (1,) * (pnd.ndim - 1)), pending
    )

    # --- 4. periodic unification -------------------------------------------
    def unify(args):
        p, cnt = args
        hub = jnp.mod((widx // jnp.maximum(cfg.unify_period, 1)), n)
        p = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[hub][None], x.shape), p
        )
        return p, jnp.zeros_like(cnt)

    if cfg.unify_period > 0:
        do_unify = jnp.mod(widx + 1, cfg.unify_period) == 0
        params, accept_count = jax.lax.cond(
            do_unify, unify, lambda a: a, (params, accept_count)
        )

    return DracoStateLegacy(
        params=params,
        pending=pending,
        buffer=buffer,
        accept_count=accept_count,
        total_accept=total_accept,
        window_idx=widx + 1,
        key=k_next,
        positions=state.positions,
        opt_state=opt_state,
    )


@partial(jax.jit, static_argnames=("cfg", "loss_fn", "num_windows"))
def run_windows_legacy(state, cfg: DracoConfig, q, adj, loss_fn, data,
                       num_windows: int):
    """Scan `num_windows` legacy windows; `q` (N, N) row-stochastic."""
    def step(s, _):
        return draco_window_legacy(s, cfg, q, adj, loss_fn, data), None

    state, _ = jax.lax.scan(step, state, None, length=num_windows)
    return state
