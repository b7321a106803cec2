"""The event-engine algorithm family, as registered `Algorithm` plugins.

Three continuous-timeline methods over the same `event_step` scan —

  draco-event       exact-timeline DRACO (Algorithm 2 with no window
                    discretization; the numpy `event_list` reference,
                    compiled);
  fedasync-gossip   DRACO with FedAsync staleness damping: arriving
                    weights scaled by s(delta_tau) at the exact
                    continuous message age (constant/hinge/poly
                    families, `cfg.staleness*` knobs);
  event-triggered   DRACO with Zehtabi-style broadcast suppression: a
                    transmission event only fires when the pending
                    backlog exceeds `cfg.trigger_threshold` in L2 norm
                    (`tx_sent` counts the broadcasts that actually went
                    out — the comms-savings metric);

plus one windowed hybrid, `fedasync-window`, which is plain windowed
DRACO with the staleness vector applied per delay bucket via
`core.protocol.draco_window`'s `damping=` hook — the discrete
counterpart of fedasync-gossip (with `staleness="constant"` it is
bit-for-bit "draco").

All are `simulate_sweep`-able over `lr`/`psi` (the Poisson-rate fields
shape the pre-sampled tape itself, so sweeping them inside one compiled
call is rejected — resample tapes host-side instead).
"""
from __future__ import annotations

import numpy as np

from repro.api.algorithm import register_algorithm
from repro.api.algorithms import Draco, _view
from repro.core import protocol as protocol_lib
from repro.events import engine
from repro.events.staleness import staleness_damping_vector, staleness_fn


class _EventAlgo:
    """Shared scaffolding for the tape-scanned family."""

    # lambda_grad / lambda_tx are baked into the sampled tape; only the
    # per-event knobs can be re-bound as traced scalars
    sweepable = ("lr", "psi")
    use_damping = False
    use_trigger = False

    def init(self, key, cfg, params0, task=None):
        return engine.init_event_state(key, cfg, params0, task=task)

    def step(self, state, ctx):
        cfg = ctx.cfg
        damping = staleness_fn(cfg) if self.use_damping else None
        trigger = (float(getattr(cfg, "trigger_threshold", 0.0))
                   if self.use_trigger else 0.0)
        return engine.event_step(state, ctx, damping=damping,
                                 trigger=trigger)

    def eval_params(self, state):
        return state.params

    def grads_per_step(self, cfg):
        # one tape row is one merged-process event; a fraction
        # lambda_grad / (lambda_grad + lambda_tx) of them are gradient
        # events, each owned by a single client (vs. the windowed
        # engine's per-client thinning). Rates may be per-client arrays
        # (profiled tapes) — reduce to the merged-process totals first.
        lam_g = float(np.sum(cfg.lambda_grad))
        lam = lam_g + float(np.sum(cfg.lambda_tx))
        if lam <= 0:
            return 0.0
        return lam_g / (cfg.num_clients * lam)


@register_algorithm("draco-event")
class DracoEvent(_EventAlgo):
    """Exact-timeline DRACO: the merged Poisson tape, no windows."""


@register_algorithm("fedasync-gossip")
class FedAsyncGossip(_EventAlgo):
    """Staleness-weighted event gossip: drain weights scaled by
    s(delta_tau) at the exact continuous message age."""

    use_damping = True


@register_algorithm("event-triggered")
class EventTriggered(_EventAlgo):
    """Threshold-triggered broadcasting: transmissions below the backlog
    threshold are suppressed (the backlog keeps accumulating)."""

    use_trigger = True


@register_algorithm("fedasync-window")
class FedAsyncWindow(Draco):
    """Windowed DRACO + per-bucket staleness damping (the `damping=`
    hook of `draco_window`); discrete counterpart of fedasync-gossip."""

    def step(self, state, ctx):
        v = _view(ctx, state.window_idx)
        return protocol_lib.draco_window(
            state, ctx.cfg, v.q, v.adj, ctx.task, ctx.data,
            spec=ctx.flat_spec, positions=v.positions,
            compute_rate=v.compute_rate, tx_rate=v.tx_rate,
            overrides=ctx.overrides, use_kernel=ctx.use_kernel,
            damping=staleness_damping_vector(ctx.cfg),
        )
