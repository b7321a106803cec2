"""`SimContext`: the immutable per-run simulation context.

Bundles everything a protocol step needs besides its own state — the
gossip graph (boolean adjacency, row-stochastic Q, symmetric Metropolis
weights), the *task* (model + loss + eval metric + local optimizer; see
`repro.tasks`), the federated data shards, the flat-plane layout
(`FlatSpec`: per-leaf shapes/offsets into the contiguous (N, Dflat)
buffer plus the (N, Dopt) optimizer plane, computed once per run),
optional node positions, and an optional scenario `schedule`
(`repro.scenarios.Schedule`: precomputed rings of time-varying
`(q_t, adj_t, positions_t, compute_rate_t)`, indexed by
``step % period`` inside the jitted scan) — so graph/channel/schedule
construction happens **once** per run instead of once per method (the
legacy `run_baseline` rebuilt the graph inside every jit).

`SimContext` is registered as a pytree: `(q, adj, w_sym, data,
positions, schedule, overrides, tape)` are traced children, while
`(cfg, task, flat_spec)` ride as static aux data. The `tape` slot
carries a `repro.events.EventTape` for the continuous-time event
engine (None everywhere else); like the schedule, it is device data —
equal-capacity tapes share one compiled scan. Passing a context through
`jax.jit` therefore recompiles only when the config, task, parameter
layout or schedule *structure* changes, exactly like the legacy
`static_argnames=("cfg", "loss_fn")` entry points.

Legacy shim: the `task` slot accepts either a `repro.tasks.Task` or a
bare ``loss(params, x, y)`` callable — pre-task call sites
(`make_context(cfg, loss_fn, data)`) keep working bit-for-bit, and
`ctx.loss_fn` always exposes the bare callable view.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Union

import jax

from repro.core import channel as channel_lib
from repro.core import flat as flat_lib
from repro.core.channel import ChannelConfig
from repro.core.protocol import build_graph
from repro.core.topology import metropolis


@jax.tree_util.register_pytree_node_class
class SimContext:
    """Immutable bundle of (cfg, task, q, adj, w_sym, data, positions,
    flat_spec, schedule, overrides, tape, use_kernel).

    `task` is the workload: a `repro.tasks.Task` or — the legacy shim —
    a bare loss callable (plain SGD). `overrides` is a
    `repro.core.protocol.Overrides` of traced config re-bindings
    (lr/lambda/psi), set per grid row by the sweep engine; None (the
    default everywhere else) is the plain static-config path.
    `use_kernel` is the gossip drain's lowering: None chooses by backend
    (`gossip_ops.default_use_kernel`); the sweep engine sets False on a
    multi-device mesh, where XLA partitions the drain and could not
    partition the Pallas kernel.
    """

    __slots__ = ("cfg", "task", "q", "adj", "w_sym", "data", "positions",
                 "flat_spec", "schedule", "overrides", "tape", "use_kernel")

    def __init__(self, cfg, task, q, adj, w_sym, data, positions=None,
                 flat_spec=None, schedule=None, overrides=None, tape=None,
                 use_kernel=None):
        object.__setattr__(self, "cfg", cfg)
        object.__setattr__(self, "task", task)
        object.__setattr__(self, "q", q)
        object.__setattr__(self, "adj", adj)
        object.__setattr__(self, "w_sym", w_sym)
        object.__setattr__(self, "data", data)
        object.__setattr__(self, "positions", positions)
        object.__setattr__(self, "flat_spec", flat_spec)
        object.__setattr__(self, "schedule", schedule)
        object.__setattr__(self, "overrides", overrides)
        object.__setattr__(self, "tape", tape)
        object.__setattr__(self, "use_kernel", use_kernel)

    def __setattr__(self, name, value):
        raise AttributeError("SimContext is immutable")

    @property
    def loss_fn(self):
        """The bare loss callable view of the task (legacy accessor)."""
        t = self.task
        return t.loss_fn if hasattr(t, "loss_fn") else t

    def replace(self, **kw) -> "SimContext":
        if "loss_fn" in kw:  # legacy field name keeps working
            kw["task"] = kw.pop("loss_fn")
        fields = {s: getattr(self, s) for s in self.__slots__}
        fields.update(kw)
        return SimContext(**fields)

    def tree_flatten(self):
        children = (self.q, self.adj, self.w_sym, self.data, self.positions,
                    self.schedule, self.overrides, self.tape)
        aux = (self.cfg, self.task, self.flat_spec, self.use_kernel)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, task, flat_spec, use_kernel = aux
        q, adj, w_sym, data, positions, schedule, overrides, tape = children
        return cls(cfg, task, q, adj, w_sym, data, positions, flat_spec,
                   schedule, overrides, tape, use_kernel)

    def __repr__(self):
        n = self.q.shape[0] if self.q is not None else "?"
        sched = ""
        if self.schedule is not None:
            sched = f", schedule_period={self.schedule.period}"
        t = self.task
        tname = getattr(t, "name", None) or getattr(t, "__name__", t)
        return (f"SimContext(n={n}, topology={getattr(self.cfg, 'topology', '?')}, "
                f"task={tname!r}{sched})")


def make_context(cfg, loss_fn: Optional[Union[Callable, str, Any]] = None,
                 data: Any = None, *, task=None, params0: Any = None,
                 graph_key=None, place_key=None, scenario=None,
                 scenario_key=None, scenario_kwargs=None) -> SimContext:
    """Build a `SimContext` from a `DracoConfig`-style config.

    Constructs the adjacency once and derives both weight matrices from
    it: row-stochastic Q (DRACO, push methods) and symmetric Metropolis
    weights (the *-symm baselines). `params0`, when given, fixes the
    flat parameter plane layout (`FlatSpec` shapes/offsets, plus the
    optimizer-plane width `opt_dim` when the workload is a task) once
    per run. `graph_key` seeds random topologies (e.g. "erdos");
    `place_key`, when given, additionally samples node positions for
    the wireless channel model (methods that carry positions in their
    own state may ignore it).

    The workload slot: pass `task=` (a `repro.tasks.Task` or registry
    name like ``"tiny-lm"``), or — the legacy shim — a bare loss
    callable in the `loss_fn` position. The two spellings may not
    disagree; a bare callable keeps the pre-task plain-SGD compiled
    path bit-for-bit.

    `scenario` (a `repro.scenarios` generator name or a prebuilt
    `Schedule`) attaches time-varying rings: the context's `q`/`adj`/
    `w_sym` become the schedule's step-0 snapshot and step functions
    read step-`t` graphs/rates via `ctx.schedule.at(t)`. `scenario_key`
    seeds the generator (defaults to `graph_key`, so a "static" scenario
    reproduces the frozen graph bit-for-bit); `scenario_kwargs` are the
    generator's knobs (churn rate, mobility speed, straggler fraction,
    ...).
    """
    from repro.tasks import get_task, is_task, opt_width

    if task is not None and loss_fn is not None and task is not loss_fn:
        raise ValueError("pass the workload as either task= or the loss_fn "
                         "position, not both")
    task = task if task is not None else loss_fn
    if isinstance(task, str):
        task = get_task(task)
    schedule = None
    if scenario is None:
        if scenario_key is not None or scenario_kwargs:
            # a forgotten scenario= would otherwise run the frozen graph
            # and silently produce frozen-graph numbers for a churn sweep
            raise ValueError(
                "scenario_key/scenario_kwargs given without scenario=")
        q, adj = build_graph(cfg, key=graph_key)
        w_sym = metropolis(adj)
    else:
        from repro.scenarios import make_schedule

        key = scenario_key if scenario_key is not None else graph_key
        schedule = make_schedule(scenario, cfg, key=key,
                                 **(scenario_kwargs or {}))
        if schedule.num_clients != cfg.num_clients:
            raise ValueError(
                f"schedule is for {schedule.num_clients} clients, "
                f"cfg.num_clients={cfg.num_clients}")
        q, adj, w_sym = schedule.q[0], schedule.adj[0], schedule.w_sym[0]
    positions = None
    if place_key is not None:
        positions = channel_lib.place_nodes(
            place_key, cfg.num_clients, cfg.channel or ChannelConfig()
        )
    flat_spec = None
    if params0 is not None:
        flat_spec = flat_lib.spec_for(params0, cfg.num_clients)
        if is_task(task):
            flat_spec = flat_spec.with_opt(opt_width(task, params0))
    return SimContext(cfg, task, q, adj, w_sym, data, positions, flat_spec,
                      schedule)
