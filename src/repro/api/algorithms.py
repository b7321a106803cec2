"""DRACO and the four Sec. 5 baselines as registered `Algorithm` plugins.

Each plugin is a thin adapter over the legacy step functions in
`repro.core.protocol` / `repro.core.baselines`, so the unified
`simulate` driver is **bit-for-bit** equivalent to the legacy
`run_windows` / `run_baseline` paths (tests/test_api.py asserts this).
Push-sum de-biasing lives in `eval_params`, not in the step, matching
the paper's evaluation convention.
"""
from __future__ import annotations

import math


from repro.api.algorithm import register_algorithm
from repro.core import baselines as baselines_lib
from repro.core import protocol as protocol_lib
from repro.scenarios.base import Snapshot

# Partial-participation probability for the async baselines (the fig3
# compute-matching assumes this value; it is the legacy default).
P_ACTIVE = 0.5


def _view(ctx, t) -> Snapshot:
    """The step-`t` world: the scenario schedule's ring lookup when the
    context carries one, else the frozen t=0 graph (positions/rates None
    so step functions stay on the frozen path bit-for-bit)."""
    if ctx.schedule is None:
        return Snapshot(q=ctx.q, adj=ctx.adj, w_sym=ctx.w_sym)
    return ctx.schedule.at(t)


@register_algorithm("draco")
class Draco:
    """Paper Algorithm 1/2: decoupled Poisson grad/tx events, row-
    stochastic gossip with Psi cap, delay ring-buffer, unification."""

    # config fields the sweep engine may re-bind as traced scalars
    sweepable = ("lr", "lambda_grad", "lambda_tx", "psi")

    def init(self, key, cfg, params0, task=None):
        return protocol_lib.init_state(key, cfg, params0, task=task)

    def step(self, state, ctx):
        v = _view(ctx, state.window_idx)
        return protocol_lib.draco_window(
            state, ctx.cfg, v.q, v.adj, ctx.task, ctx.data,
            spec=ctx.flat_spec, positions=v.positions,
            compute_rate=v.compute_rate, tx_rate=v.tx_rate,
            overrides=ctx.overrides, use_kernel=ctx.use_kernel,
        )

    def eval_params(self, state):
        return state.params

    def grads_per_step(self, cfg):
        # P(>= 1 Poisson grad event in one superposition window)
        return 1.0 - math.exp(-cfg.lambda_grad * cfg.window)


class _Baseline:
    """Shared init for the four baselines (BaselineState + positions)."""

    # baselines consume cfg.lr only (via local_updates); the Poisson-rate
    # and Psi knobs are DRACO-specific
    sweepable = ("lr",)

    def init(self, key, cfg, params0, task=None):
        return baselines_lib.init_baseline_state(key, cfg, params0, task=task)

    @staticmethod
    def _lr(ctx):
        return None if ctx.overrides is None else ctx.overrides.lr

    def eval_params(self, state):
        return baselines_lib.eval_params(self.name, state)

    def grads_per_step(self, cfg):
        return 1.0


@register_algorithm("sync-symm")
class SyncSymm(_Baseline):
    """Synchronous D-SGD with symmetric Metropolis mixing."""

    def step(self, state, ctx):
        v = _view(ctx, state.round_idx)
        return baselines_lib.sync_symm_round(
            state, ctx.cfg, v.w_sym, v.adj, ctx.task, ctx.data,
            positions=v.positions, compute_rate=v.compute_rate,
            lr=self._lr(ctx),
        )


@register_algorithm("sync-push")
class SyncPush(_Baseline):
    """Synchronous push-sum over the directed graph (gradient push)."""

    def step(self, state, ctx):
        v = _view(ctx, state.round_idx)
        state, _ = baselines_lib.sync_push_round(
            state, ctx.cfg, v.adj, ctx.task, ctx.data,
            positions=v.positions, compute_rate=v.compute_rate,
            lr=self._lr(ctx),
        )
        return state


@register_algorithm("async-symm")
class AsyncSymm(_Baseline):
    """Async partial participation + symmetric mixing among survivors."""

    def step(self, state, ctx):
        v = _view(ctx, state.round_idx)
        return baselines_lib.async_symm_round(
            state, ctx.cfg, v.w_sym, v.adj, ctx.task, ctx.data,
            p_active=P_ACTIVE, positions=v.positions,
            compute_rate=v.compute_rate, lr=self._lr(ctx),
        )

    def grads_per_step(self, cfg):
        return P_ACTIVE


@register_algorithm("async-push")
class AsyncPush(_Baseline):
    """Async push-sum gossip (Digest-style half-mass pushes)."""

    def step(self, state, ctx):
        v = _view(ctx, state.round_idx)
        state, _ = baselines_lib.async_push_round(
            state, ctx.cfg, v.adj, ctx.task, ctx.data,
            p_active=P_ACTIVE, positions=v.positions,
            compute_rate=v.compute_rate, lr=self._lr(ctx),
        )
        return state

    def grads_per_step(self, cfg):
        return P_ACTIVE
