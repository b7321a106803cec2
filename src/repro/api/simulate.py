"""Unified compiled simulation driver for any registered `Algorithm`.

`simulate(algo, cfg, params0, loss_fn, data, num_steps, ...)` runs the
whole protocol inside **one** compiled nested scan with *in-jit* metric
sampling: the outer scan walks the eval points, each inner scan runs
`eval_every` protocol steps and then computes the metric dict (mean
client accuracy on a held-out set, consensus distance) directly on
device, so there are no per-segment host round-trips, no re-dispatch,
and no per-step trace memory — one compile per (algorithm, config,
loss), then a single device call regardless of how often you sample.

`steps_for_budget` converts a compute budget (expected local gradient
events per client, priced at `task.grad_cost` when a task is given)
into a step count for any algorithm, expressing the paper's
compute-matched comparisons in one place.

Workloads are first-class: `simulate(algo, cfg, task="tiny-lm", ...)`
pulls model/data/optimizer/metric from the `repro.tasks` registry —
`params0` and `data` are built from the task when omitted, the local
optimizer state rides the flat plane, and the trace metric is named by
the task ("accuracy", "perplexity"). Bare `loss_fn=` callables remain
the legacy plain-SGD spelling, bit-for-bit.

Time-varying workloads ride the same scan: `simulate(...,
scenario="random-waypoint")` attaches a `repro.scenarios.Schedule` to
the context, and the per-step algorithm adapters index its rings by the
state-carried step counter — no extra scan carry, no recompilation per
step, and `scenario="static"` is bit-for-bit the frozen-graph path.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.algorithm import Algorithm, get_algorithm
from repro.api.context import SimContext, make_context


class SimTrace(NamedTuple):
    """In-jit metric trace, sized to the sampled steps on device.

    `step[k]` is the 1-indexed step count after which `metrics[...][k]`
    was measured; empty arrays when `eval_every == 0`. The arrays have
    `num_steps // eval_every` rows, plus one final row at `num_steps`
    when `num_steps % eval_every != 0` — the trace always reflects the
    end-of-run model (see `_run`). `step` is int32 everywhere (empty and
    scanned traces alike).
    """

    step: np.ndarray  # (num_evals,) int32
    metrics: Dict[str, np.ndarray]  # each (num_evals,) float


def consensus_distance(params) -> jax.Array:
    """RMS distance of per-client params to the virtual global model:
    sqrt(mean_i ||x_i - x_bar||^2) over all coordinates (Sec. 2.1).

    Computed on the flat parameter plane: one (N, Dflat) ravel and a
    single fused reduction instead of a per-leaf loop."""
    from repro.core import flat as flat_lib

    x = flat_lib.ravel_clients(params)
    xbar = x.mean(axis=0, keepdims=True)
    return jnp.sqrt(((x - xbar) ** 2).sum() / x.shape[0])


@jax.named_scope("draco.eval")
def _metrics(algo, state, eval_fn, eval_data, metric_name="accuracy"):
    p = algo.eval_params(state)
    out = {"consensus": consensus_distance(p)}
    if eval_fn is not None:
        ex, ey = eval_data
        out[metric_name] = jax.vmap(lambda pi: eval_fn(pi, ex, ey))(p).mean().astype(jnp.float32)
    return out


# The protocol and its tasks are specified in f32. A TPU matmul at the
# default precision rounds f32 operands to bf16, which on a v5e took the
# EMNIST preset from 0.77 to 0.41 accuracy after 300 windows; every
# simulation program is traced at this precision instead. (CPU matmuls
# are f32 either way.)
MATMUL_PRECISION = "highest"


def _run_body(algo, ctx, state, eval_data, num_steps: int, eval_every: int,
              eval_fn, metric_name: str = "accuracy"):
    """One fused scan over `num_steps` protocol steps + in-jit eval.

    Nested scan: an outer scan over the `num_steps // eval_every` eval
    points, each running `eval_every` protocol steps inline and emitting
    one metrics row — so the device trace is `(num_evals,)` rather than
    a dense `(num_steps,)` carry that is mostly thrown away host-side
    (the pre-PR2 `lax.cond` sampling traced every step: ~8 bytes/metric/
    step of wasted HBM and a scan carry that grew with the eval cadence
    ignored). The `num_steps % eval_every` leftover steps past the last
    eval point run in a trailing metric-free scan followed by one final
    metrics row at step `num_steps`, so the trace always reflects the
    end-of-run model.

    Un-jitted on purpose: `_run` wraps it for solo `simulate` calls, and
    `repro.api.sweep` nests it under vmap (seed axis) and scan (config
    axis) inside its own jit. Traced at `MATMUL_PRECISION`."""
    obs.count("repro.trace._run_body")
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return _scan_body(algo, ctx, state, eval_data, num_steps, eval_every,
                          eval_fn, metric_name)


def _scan_body(algo, ctx, state, eval_data, num_steps, eval_every, eval_fn,
               metric_name):
    def step_only(s, _):
        return algo.step(s, ctx), None

    if eval_every <= 0:
        state, _ = jax.lax.scan(step_only, state, None, length=num_steps)
        return state, None

    chunks, rem = divmod(num_steps, eval_every)

    def chunk_body(s, k):
        s, _ = jax.lax.scan(step_only, s, None, length=eval_every)
        m = _metrics(algo, s, eval_fn, eval_data, metric_name)
        return s, dict(m, step=(k + 1) * eval_every)

    state, trace = jax.lax.scan(chunk_body, state,
                                jnp.arange(chunks, dtype=jnp.int32))
    if rem:
        state, _ = jax.lax.scan(step_only, state, None, length=rem)
        last = dict(_metrics(algo, state, eval_fn, eval_data, metric_name),
                    step=jnp.asarray(num_steps, jnp.int32))
        trace = jax.tree_util.tree_map(
            lambda rows, row: jnp.concatenate(
                [rows, row[None].astype(rows.dtype)]), trace, last)
    return state, trace


_run = jax.jit(_run_body,
               static_argnames=("algo", "num_steps", "eval_every", "eval_fn",
                                "metric_name"))


def simulate(
    algo: Union[str, Algorithm],
    cfg,
    params0=None,
    loss_fn: Optional[Callable] = None,
    data: Any = None,
    num_steps: int = 1,
    *,
    task=None,
    task_key=None,
    key=None,
    eval_every: int = 0,
    eval_fn: Optional[Callable] = None,
    eval_data: Any = None,
    ctx: Optional[SimContext] = None,
    state: Any = None,
    graph_key=None,
    scenario=None,
    scenario_key=None,
    scenario_kwargs=None,
):
    """Run `num_steps` of any registered algorithm in one compiled call.

    Records the `repro.obs` span `repro.simulate`, with `.prepare` (the
    host entry up to the compiled call), `.run` (the call) and `.sync`
    (the trace rows' copy to the host, which waits for the device).

    Args:
      algo: registry name (e.g. "draco", "sync-push") or an `Algorithm`.
      cfg: `DracoConfig`-style frozen config (static: hashable).
      params0: single-client param pytree (ignored when `state` given;
        built by the task's model init when omitted and `task=` given).
      loss_fn: `loss(params_i, x, y)` used by local SGD (static). The
        legacy workload spelling — a `Task` supersedes it.
      data: federated train shards `(xs, ys)` with leading client axis
        (built by the task's dataset builder when omitted and `task=`
        given).
      num_steps: protocol steps (DRACO windows / baseline rounds).
      task: `repro.tasks.Task` or registry name ("linear-softmax",
        "mlp", "small-cnn", "tiny-lm"): the (model x optimizer x
        dataset) workload. Its local optimizer state rides the flat
        plane on the algorithm state; its `eval_fn`/`metric_name` are
        used when `eval_fn` is omitted. The default "linear-softmax" +
        sgd(constant) task is bit-for-bit the bare-`loss_fn` path.
      task_key: PRNGKey seeding the task's model/data builders when
        params0/data are omitted (defaults to PRNGKey(0), so repeated
        calls see the same workload).
      key: PRNGKey for state init (required unless `state` is given).
      eval_every: sample metrics every k steps, on device, via a nested
        scan that materializes one metrics row per sample (the trace is
        `(num_steps // eval_every,)`, plus a final row at `num_steps`
        when the division leaves a remainder — nothing is traced at the
        other steps); 0 disables in-jit eval entirely.
      eval_fn: `metric(params_i, ex, ey) -> scalar` (e.g. accuracy);
        vmapped over clients and averaged. Requires `eval_data`.
      eval_data: held-out `(ex, ey)` for `eval_fn`.
      ctx: prebuilt `SimContext` to share graph/channel/schedule
        construction across runs; built from (cfg, loss_fn, data) when
        omitted.
      state: resume from an existing algorithm state.
      graph_key: PRNGKey for random topologies (passed to `make_context`).
      scenario: `repro.scenarios` generator name (e.g.
        "markov-edge-flip") or prebuilt `Schedule` — attaches
        time-varying `(q_t, adj_t, positions_t, compute_rate_t)` rings.
        The scan itself carries no extra schedule index: each method's
        state already counts steps (`window_idx`/`round_idx`) and the
        per-step adapter looks up `schedule.at(step)` in-jit. Only valid
        when `ctx` is omitted (a prebuilt ctx brings its own schedule).
      scenario_key / scenario_kwargs: generator seed and knobs
        (see `make_context`).

    Returns:
      (final_state, SimTrace) — the trace holds exactly the sampled
      steps (sized on device; no host-side filtering).
    """
    with obs.span("repro.simulate"):
        with obs.span("repro.simulate.prepare"):
            from repro.tasks import is_task

            if isinstance(algo, str):
                algo = get_algorithm(algo)
            # params0 feeds state init and the ctx flat-spec (a warm restart with
            # a prebuilt ctx needs neither); data feeds the ctx (a prebuilt ctx
            # brings its own shards)
            task, workload, params0, data, eval_data = resolve_workload(
                cfg, task, task_key, loss_fn, params0, data, eval_data,
                need_params=state is None or ctx is None, need_data=ctx is None)
            if ctx is None:
                ctx = make_context(cfg, workload, data, params0=params0,
                                   graph_key=graph_key, scenario=scenario,
                                   scenario_key=scenario_key,
                                   scenario_kwargs=scenario_kwargs)
            elif scenario is not None:
                raise ValueError(
                    "pass scenario to make_context when prebuilding ctx; a ctx "
                    "already carries its schedule")
            elif ctx.cfg != cfg:
                # steps read ctx.cfg, init reads cfg — a silent mismatch would run
                # the wrong config; rebind with ctx.replace(cfg=...) to share the
                # traced graph arrays across config variants (e.g. a Psi sweep)
                raise ValueError(
                    "ctx.cfg differs from cfg; pass ctx.replace(cfg=cfg) to reuse "
                    "a context across config variants")
            elif workload is not None and ctx.task != workload:
                # equality, not identity: equal Task instances (e.g. two
                # with_optimizer() copies) are the same static jit key
                raise ValueError(
                    "ctx.task differs from the task/loss_fn argument; pass "
                    "ctx.replace(task=...) to rebind the workload")
            metric_name = "accuracy"
            if eval_fn is None and is_task(ctx.task) and eval_data is not None:
                eval_fn = ctx.task.eval_fn
            if is_task(ctx.task) and eval_fn is ctx.task.eval_fn:
                metric_name = ctx.task.metric_name
            if state is None:
                if key is None:
                    raise ValueError("key is required when no state is given")
                state = algo.init(key, cfg, params0, task=ctx.task)
            if eval_fn is not None and eval_data is None:
                raise ValueError("eval_fn requires eval_data=(ex, ey)")

        with obs.span("repro.simulate.run"):
            state, raw = _run(algo, ctx, state, eval_data, int(num_steps),
                              int(eval_every), eval_fn, metric_name)

        if raw is None:
            return state, SimTrace(np.zeros((0,), np.int32), {})
        with obs.span("repro.simulate.sync"):
            step = np.asarray(raw["step"])
            metrics = {k: np.asarray(v) for k, v in raw.items() if k != "step"}
        return state, SimTrace(step, metrics)


def resolve_workload(cfg, task, task_key, loss_fn, params0, data, eval_data,
                     *, need_params: bool, need_data: bool):
    """Shared task plumbing for `simulate` / `simulate_sweep`.

    Resolves registry names, promotes a `Task` passed in the legacy
    loss position, rejects conflicting spellings, and builds only the
    *missing, actually-consumed* pieces from the task's builders
    (`need_params` is False on a warm restart with a prebuilt ctx;
    `need_data` is False whenever a prebuilt ctx supplies the shards —
    regenerating a dataset that the scan never reads would also inject
    an eval set drawn from different mixture anchors).

    Returns ``(task, workload, params0, data, eval_data)`` where
    `workload` is what the context carries (the task, or the bare loss
    callable on the legacy path).
    """
    from repro.tasks import get_task, is_task

    if isinstance(task, str):
        task = get_task(task)
    if task is None and is_task(loss_fn):
        task = loss_fn  # Task passed in the legacy loss position
    if task is not None:
        if loss_fn is not None and loss_fn is not task:
            raise ValueError("pass the workload as either task= or "
                             "loss_fn=, not both")
        need_params = need_params and params0 is None
        need_data = need_data and data is None
        if need_params or need_data:
            tk = task_key if task_key is not None else jax.random.PRNGKey(0)
            kp, kd = jax.random.split(tk)  # Task.setup's key derivation
            if need_params:
                params0 = task.init_params(kp)
            if need_data:
                data, ev = task.make_data(kd, cfg.num_clients)
                if eval_data is None:
                    eval_data = ev
    elif task_key is not None:
        raise ValueError("task_key given without task=")
    workload = task if task is not None else loss_fn
    return task, workload, params0, data, eval_data


def steps_for_budget(algo: Union[str, Algorithm], cfg, budget_grads: float,
                     task=None) -> int:
    """Steps matching a per-client compute budget for any algorithm.

    Without `task` (legacy), `budget_grads` counts expected local
    gradient *events* per client and every event is priced uniformly —
    the compute-matched budget of the paper's Fig. 3 (DRACO fires
    1-exp(-lambda*w) grads/client/window, sync baselines 1/round, async
    baselines p_active/round). That uniform pricing is only correct
    when every method runs the same workload.

    With `task` (a `repro.tasks.Task` or registry name), each event is
    priced at `task.grad_cost` (relative FLOPs per local gradient
    event), so `budget_grads` is a *FLOP* budget in the same units and
    budget-matched runs equalize expected FLOPs — across algorithms
    *and* across tasks of different model sizes:

        steps = budget / (grads_per_step(cfg) * grad_cost)

    tests/test_tasks.py pins the equalization.
    """
    if isinstance(algo, str):
        algo = get_algorithm(algo)
    cost = 1.0
    if task is not None:
        from repro.tasks import get_task

        t = get_task(task) if isinstance(task, str) else task
        cost = t.grad_cost
    rate = algo.grads_per_step(cfg) * cost
    return max(1, int(round(budget_grads / max(rate, 1e-12))))
