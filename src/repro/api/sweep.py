"""`simulate_sweep`: whole experiment grids in one compiled device call.

The paper's headline figures are statements about *sweeps* — seeds x
configs x scenarios — but driving `simulate()` from a Python loop pays
one dispatch per cell and one re-compile per config variant (every
distinct `DracoConfig` is a fresh static jit key). This module batches
the whole grid into a single compiled call built from three orthogonal
axes over the same `repro.api.simulate._run_body` nested scan:

  - **seed axis (vmap).** Per-seed states are init-stacked and the run
    is `jax.vmap`-ed over them. XLA batches the per-step GEMMs; row `k`
    of the result is bit-for-bit the solo `simulate()` run with seed `k`
    (enforced by tests/test_sweep.py).
  - **config axis (scan over traced overrides).** Grid configs may
    differ only in *sweepable* fields (`lr`, `lambda_grad`, `lambda_tx`,
    `psi`) — those are stacked into `(G,)` arrays and re-bound per grid
    row as traced scalars (`repro.core.protocol.Overrides`, carried on
    `ctx.overrides`), so an lr/Psi/lambda sweep shares ONE trace instead
    of compiling `G` variants.
  - **scenario axis (scan over stacked schedules).** A list of
    same-shape `repro.scenarios.Schedule`s is tree-stacked and sliced
    per grid row — churn/straggler sweeps ride the same scan.

Client-axis sharding: pass `mesh=` (e.g. `launch.mesh.make_sweep_mesh()`)
and the client axis `N` of the states and federated data shards is laid
out over the mesh's client axes (the `sharding/axes.py` `"clients"`
rule: `("data",)` single-pod, `("pod", "data")` multi-pod). XLA's SPMD
partitioner then tiles the gossip `Q^T @ payload` contractions per
device with one reduce-scatter on the receiver axis — the explicit
`shard_map` lowering of that contraction ships as
`repro.kernels.gossip.ops.gossip_drain_sharded` (per-device Pallas tiles
on TPU, one `psum_scatter`), and the auto-SPMD path is checked against
it in tests/test_sweep_mesh.py.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.algorithm import Algorithm, get_algorithm
from repro.api.context import SimContext, make_context
from repro.api.simulate import _run_body
from repro.core.protocol import Overrides

# Config fields the engine knows how to re-bind as traced scalars.  An
# algorithm additionally declares which of these it actually consumes
# via its `sweepable` attribute; sweeping a field an algorithm ignores
# would silently produce G identical rows, so it is rejected.
SWEEPABLE = ("lr", "lambda_grad", "lambda_tx", "psi")
_OVERRIDE_DTYPES = {"lr": jnp.float32, "lambda_grad": jnp.float32,
                    "lambda_tx": jnp.float32, "psi": jnp.int32}


class SweepTrace(NamedTuple):
    """Grid-shaped metric trace of one `simulate_sweep` call.

    `step` is shared by every cell (same cadence everywhere); each
    metric is `(G, K, num_evals)` — grid rows x seeds x eval points.
    """

    step: np.ndarray  # (num_evals,) int32
    metrics: Dict[str, np.ndarray]  # each (G, K, num_evals)


def stack_configs(cfg_grid: Sequence) -> tuple:
    """Split a config grid into (base_cfg, stacked `Overrides`).

    Every config must equal the first one after normalizing the
    `SWEEPABLE` fields; fields that actually vary are stacked into
    `(G,)` arrays, constant fields stay static (None override) so the
    compiled call specializes on them.
    """
    cfgs = list(cfg_grid)
    if not cfgs:
        raise ValueError("empty config grid")
    base = cfgs[0]
    varying = {}
    for f in SWEEPABLE:
        vals = [getattr(c, f) for c in cfgs]
        if any(v != vals[0] for v in vals):
            varying[f] = jnp.asarray(vals, _OVERRIDE_DTYPES[f])
    norm = {f: getattr(base, f) for f in varying}
    for i, c in enumerate(cfgs):
        if c.replace(**norm) != base:
            bad = [f for f in c.__dataclass_fields__
                   if f not in varying and getattr(c, f) != getattr(base, f)]
            raise ValueError(
                f"cfg_grid[{i}] differs from cfg_grid[0] in non-sweepable "
                f"field(s) {bad}; only {SWEEPABLE} can vary inside one "
                "compiled sweep — split the grid or loop host-side")
    return base, Overrides(**varying)


def stack_schedules(schedules: Sequence):
    """Tree-stack same-shape `Schedule`s along a new leading grid axis."""
    scheds = list(schedules)
    structs = {jax.tree_util.tree_structure(s) for s in scheds}
    if len(structs) > 1:
        raise ValueError(
            "schedules must share one pytree structure (same fields "
            f"present, same ring periods); got {len(structs)} distinct")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *scheds)


def _client_sharding(x, num_clients: int, mesh, client_ax, skip_leading=0):
    """NamedSharding laying the first client-sized dim (past the leading
    `skip_leading` axes) over the mesh client axes; replicated when no
    dim matches or the mesh size does not divide N."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.specs import filter_divisible

    axes = [None] * x.ndim
    for d in range(skip_leading, x.ndim):
        if x.shape[d] == num_clients:
            axes[d] = client_ax
            break
    spec = filter_divisible(P(*axes), x.shape, mesh)
    return NamedSharding(mesh, spec)


def shard_grid_inputs(states, data, num_clients: int, mesh):
    """Lay the client axis of seed-stacked states + federated data over
    the mesh ("clients" rule from `sharding/axes.py`). Returns sharded
    (states, data); sharding is layout only — results are unchanged up
    to f32 reduction order."""
    from repro.sharding.axes import default_rules

    client_ax = default_rules(mesh).rules["clients"]
    states = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, _client_sharding(x, num_clients, mesh, client_ax,
                                skip_leading=1)), states)
    if data is not None:
        data = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, _client_sharding(x, num_clients, mesh, client_ax)), data)
    return states, data


@partial(jax.jit, static_argnames=("algo", "num_steps", "eval_every",
                                   "eval_fn", "grid", "final_fn",
                                   "metric_name"))
def _run_sweep(algo, ctx, states, eval_data, num_steps: int, eval_every: int,
               eval_fn, overrides, schedules, grid: int, final_fn,
               metric_name: str = "accuracy"):
    """scan(config/scenario grid) x vmap(seeds) x `_run_body` — one XLA
    program for the whole grid. `final_fn` slims each final state before
    it is stacked across the grid (a (G, K, D, N, Dflat) ring buffer
    stack is pure waste when the caller only wants `total_accept`)."""

    def one_row(_, row):
        ov, sched = row
        ctx_g = ctx
        # repro-lint: disable-next-line=TRACED-PY-BRANCH(structural: iterating the Overrides NamedTuple and testing `is not None` reads trace-time pytree structure, never traced values)
        if any(f is not None for f in ov):
            ctx_g = ctx_g.replace(overrides=ov)
        if sched is not None:
            ctx_g = ctx_g.replace(schedule=sched)
        finals, trace = jax.vmap(
            lambda st: _run_body(algo, ctx_g, st, eval_data, num_steps,
                                 eval_every, eval_fn, metric_name))(states)
        if final_fn is not None:
            finals = final_fn(finals)
        return None, (finals, trace)

    _, out = jax.lax.scan(one_row, None, (overrides, schedules), length=grid)
    return out


def simulate_sweep(
    algo: Union[str, Algorithm],
    cfg_grid,
    params0=None,
    loss_fn: Optional[Callable] = None,
    data: Any = None,
    num_steps: int = 1,
    *,
    task=None,
    task_key=None,
    keys=None,
    key=None,
    num_seeds: int = 1,
    eval_every: int = 0,
    eval_fn: Optional[Callable] = None,
    eval_data: Any = None,
    ctx: Optional[SimContext] = None,
    graph_key=None,
    schedules=None,
    mesh=None,
    final_fn: Optional[Callable] = None,
):
    """Run a whole (config x scenario) x seed grid in one compiled call.

    Args:
      algo: registry name or `Algorithm` (one method per sweep; loop
        methods host-side — they are distinct compiled programs anyway).
      cfg_grid: one config, or a sequence differing only in `SWEEPABLE`
        fields the algorithm declares sweepable (`algo.sweepable`).
      params0 / loss_fn / data / num_steps: as in `simulate`.
      task / task_key: the (model x optimizer x dataset) workload, as in
        `simulate` — params0/data/eval default to the task's builders,
        the local optimizer state rides the flat plane on every seed
        row, and the trace metric takes the task's name ("perplexity"
        for tiny-lm). Sweeping `lr` re-seeds the task's lr schedule per
        grid row (the optimizer hyperparameter axis); the task must
        declare it in `task.sweepable`.
      keys: (K, ...) stacked PRNGKeys, one per seed row; or pass `key` +
        `num_seeds` to split one. Row `k` is bit-identical to a solo
        `simulate(..., key=keys[k])` on one device.
      eval_every / eval_fn / eval_data: in-jit metric cadence, as in
        `simulate` (incl. the final partial-chunk eval row).
      ctx: prebuilt base `SimContext`; its cfg must equal the grid's
        base config (rebind with `ctx.replace(cfg=...)`). Built from
        (base cfg, loss_fn, data) when omitted.
      graph_key: seeds random topologies when building the context.
      schedules: optional sequence of same-shape scenario `Schedule`s —
        the grid's scenario axis. Length must match `cfg_grid` when both
        sweep (a grid row re-binds config overrides AND its schedule).
      mesh: optional `jax.sharding.Mesh`; shards the client axis N of
        states/data over the mesh's client axes (see module docstring).
      final_fn: optional per-row reducer applied to the vmapped final
        states before grid stacking, e.g. ``lambda s: s.total_accept``
        — pass a module-level function (it is a static jit key).

    Returns:
      (finals, SweepTrace): `finals` is `final_fn`'s output (or the full
      states) with leading (G, K) axes; the trace metrics are
      (G, K, num_evals).
    """
    from repro.api.simulate import resolve_workload
    from repro.tasks import is_task

    if isinstance(algo, str):
        algo = get_algorithm(algo)
    cfgs = cfg_grid if isinstance(cfg_grid, (list, tuple)) else [cfg_grid]
    base, overrides = stack_configs(cfgs)
    # params0 always feeds the vmapped state init; data only feeds a
    # freshly-built ctx (a prebuilt one brings its own shards)
    task, workload, params0, data, eval_data = resolve_workload(
        base, task, task_key, loss_fn, params0, data, eval_data,
        need_params=True, need_data=ctx is None)
    swept = [f for f in SWEEPABLE if getattr(overrides, f) is not None]
    if len(cfgs) > 1 and not swept:
        raise ValueError(
            f"cfg_grid has {len(cfgs)} entries but no field varies — the "
            "sweep would scan identical rows; pass one config (seeds/"
            "schedules are separate axes)")
    unsupported = sorted(set(swept) - set(getattr(algo, "sweepable", ())))
    if unsupported:
        raise ValueError(
            f"{algo.name!r} does not consume override field(s) "
            f"{unsupported} (sweepable: {getattr(algo, 'sweepable', ())}); "
            "sweeping them would return identical rows")

    sched_stack = None
    if schedules is not None:
        schedules = list(schedules)
        sched_stack = stack_schedules(schedules)
    grid = max(len(cfgs), len(schedules) if schedules is not None else 1)
    if len(cfgs) not in (1, grid) or (
            schedules is not None and len(schedules) != grid):
        raise ValueError(
            f"grid axes disagree: {len(cfgs)} config(s) vs "
            f"{len(schedules)} schedule(s); a scanned axis must cover "
            "every grid row (use a ctx-carried schedule for a constant "
            "scenario)")

    if keys is None:
        if key is None:
            raise ValueError("pass keys=(K,...) or key= + num_seeds=")
        keys = jax.random.split(key, num_seeds)
    keys = jnp.asarray(keys)

    if ctx is None:
        ctx = make_context(base, workload, data, params0=params0,
                           graph_key=graph_key)
    elif ctx.cfg != base:
        raise ValueError(
            "ctx.cfg differs from the grid's base config; pass "
            "ctx.replace(cfg=cfg_grid[0]) to reuse a context")
    elif workload is not None and ctx.task != workload:
        # equality, not identity: equal Task instances (e.g. two
        # with_optimizer() copies) are the same static jit key
        raise ValueError(
            "ctx.task differs from the task/loss_fn argument; pass "
            "ctx.replace(task=...) to rebind the workload")
    if ctx.overrides is not None:
        raise ValueError("ctx already carries overrides; sweeps own them")
    if sched_stack is not None and ctx.schedule is not None:
        raise ValueError(
            "pass either schedules= or a ctx with a schedule, not both")
    if (is_task(ctx.task) and "lr" in swept
            and "lr" not in ctx.task.sweepable):
        # the built-in optimizers all honor the traced lr (the schedule
        # is re-seeded per grid row), but a custom task whose
        # make_optimizer ignores its lr argument must say so — its grid
        # rows would be silently identical
        raise ValueError(
            f"task {ctx.task.name!r} does not declare 'lr' sweepable "
            f"(task.sweepable={ctx.task.sweepable}): its make_optimizer "
            "does not consume the per-row lr override, so the grid rows "
            "would be identical")
    metric_name = "accuracy"
    if eval_fn is None and is_task(ctx.task) and eval_data is not None:
        eval_fn = ctx.task.eval_fn
    if is_task(ctx.task) and eval_fn is ctx.task.eval_fn:
        metric_name = ctx.task.metric_name
    if eval_fn is not None and eval_data is None:
        raise ValueError("eval_fn requires eval_data=(ex, ey)")

    states = jax.vmap(lambda k: algo.init(k, base, params0,
                                          task=ctx.task))(keys)
    if mesh is not None:
        states, shard_data = shard_grid_inputs(states, ctx.data,
                                               base.num_clients, mesh)
        # the partitioner splits the XLA drain; it cannot split a Mosaic
        # kernel
        ctx = ctx.replace(data=shard_data,
                          use_kernel=False if mesh.size > 1 else None)

    finals, raw = _run_sweep(algo, ctx, states, eval_data, int(num_steps),
                             int(eval_every), eval_fn, overrides, sched_stack,
                             grid, final_fn, metric_name)
    if raw is None:
        return finals, SweepTrace(np.zeros((0,), np.int32), {})
    step = np.asarray(raw["step"][0, 0])
    metrics = {k: np.asarray(v) for k, v in raw.items() if k != "step"}
    return finals, SweepTrace(step, metrics)
