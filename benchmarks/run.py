"""Benchmark harness — one function per paper table/figure.

Protocol-level benches run through the unified ``repro.api`` interface
(``simulate`` + the algorithm registry); ``bench_simulate_fused`` tracks
the in-jit-eval speedup of the fused driver vs the legacy segment loop.

Prints ``name,us_per_call,derived`` CSV and mirrors the timings to
``BENCH_gossip.json`` (name -> us_per_call; uploaded as a CI artifact so
the perf trajectory is tracked across PRs). Measured numbers and knob
guidance live in EXPERIMENTS.md.

  PYTHONPATH=src python -m benchmarks.run            # full set
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn


def bench_gossip_mix(quick=False):
    """Kernel-layer: row-stochastic mixing at paper scale (25 clients,
    0.57 MB model = ~149k f32 params)."""
    from repro.core.mixing import mix_dense
    from repro.kernels.gossip.ops import gossip_mix

    n, d = 25, 149_194
    key = jax.random.PRNGKey(0)
    q = jax.nn.softmax(jax.random.normal(key, (n, n)))
    deltas = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    f = jax.jit(lambda q, x: mix_dense(q, {"w": x})["w"])
    us = time_fn(f, q, deltas)
    emit("gossip_mix_xla_25x149k", us, f"{n*n*d*2/us*1e6/1e9:.1f}GFLOPs")
    if not quick:
        # interpret auto-selects by backend: compiled kernel on TPU, the
        # (slow, correctness-only) interpreter elsewhere — hence tiny D.
        # Name the row by what actually ran so cross-machine trajectories
        # never mix interpreter and compiled-kernel timings.
        from repro.kernels.gossip.ops import default_use_kernel

        us_k = time_fn(lambda: gossip_mix(q, deltas[:, :4096]),
                       warmup=1, iters=3)
        if default_use_kernel():
            emit("gossip_mix_pallas_4k", us_k, "kernel-path")
        else:
            emit("gossip_mix_pallas_interpret_4k", us_k, "correctness-path")


def bench_ssd(quick=False):
    """SSD chunked (dual form) vs sequential recurrence — the Mamba2 layer
    speed story on the paper's assigned ssm archs."""
    from repro.models.ssm import ssd_chunked, ssd_reference

    B, T, H, P, G, N = (1, 512, 8, 32, 1, 32) if quick else (2, 1024, 16, 64, 1, 64)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B_ = jax.random.normal(ks[3], (B, T, G, N))
    C_ = jax.random.normal(ks[4], (B, T, G, N))
    D = jnp.ones((H,))
    f_chunk = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    f_seq = jax.jit(ssd_reference)
    us_c = time_fn(f_chunk, x, dt, A, B_, C_, D, iters=5)
    us_s = time_fn(f_seq, x, dt, A, B_, C_, D, iters=5)
    emit("ssd_chunked_T%d" % T, us_c, f"speedup_vs_seq={us_s/us_c:.2f}x")
    emit("ssd_sequential_T%d" % T, us_s, "oracle")


def bench_draco_window(quick=False):
    """Protocol-layer: the fused delay-bucketed gossip engine vs the seed
    per-bucket-einsum loop, at the paper's experiment scale (N=25 clients,
    EMNIST-like MLP ~146k params, wireless channel, deep D=8 ring).

    Both paths are timed per window inside their compiled `run_windows`
    scan — the production shape. The acceptance bar for PR 2 is >= 2x on
    the fused/legacy pair below (see EXPERIMENTS.md for the knob sweep).
    """
    from benchmarks.fig3_convergence import setup
    from repro.core.protocol import (
        build_graph,
        init_state,
        init_state_legacy,
        run_windows,
        run_windows_legacy,
    )

    n = 8 if quick else 25
    D = 4 if quick else 8
    windows = 6 if quick else 16
    iters = 3 if quick else 5
    cfg, train, test, params0, loss, acc, key = setup("emnist", num_clients=n)
    cfg = cfg.replace(max_delay_windows=D)
    q, adj = build_graph(cfg)

    st_f = init_state(key, cfg, params0)
    st_l = init_state_legacy(key, cfg, params0)
    fused = lambda: run_windows(st_f, cfg, q, adj, loss, train, windows)
    legacy = lambda: run_windows_legacy(st_l, cfg, q, adj, loss, train, windows)
    us_f = time_fn(fused, warmup=1, iters=iters) / windows
    us_l = time_fn(legacy, warmup=1, iters=iters) / windows
    emit(f"draco_window_fused_N{n}_D{D}", us_f,
         f"speedup_vs_seed_loop={us_l/us_f:.2f}x")
    emit(f"draco_window_legacy_N{n}_D{D}", us_l, "seed-path")


def bench_simulate_fused(quick=False):
    """API-layer: fused `repro.api.simulate` (one nested scan, in-jit
    eval at each eval point) vs the legacy segment loop (host round-trip
    eval between `run_windows` calls). Same protocol, same eval cadence."""
    from benchmarks.fig3_convergence import setup
    from repro.api import simulate
    from repro.core.protocol import build_graph, init_state, run_windows

    n = 8 if quick else 16
    windows = 60 if quick else 200
    every = 10 if quick else 25
    cfg, train, test, params0, loss, acc, key = setup("emnist", num_clients=n)

    def fused():
        st, trace = simulate("draco", cfg, params0, loss, train,
                             num_steps=windows, key=key, eval_every=every,
                             eval_fn=acc, eval_data=test)
        return st.params

    q, adj = build_graph(cfg)

    def segment_loop():
        st = init_state(key, cfg, params0)
        for _ in range(windows // every):
            st = run_windows(st, cfg, q, adj, loss, train, every)
            float(jax.vmap(lambda p: acc(p, test[0], test[1]))(st.params).mean())
        return st.params

    us_f = time_fn(fused, warmup=1, iters=3)
    us_l = time_fn(segment_loop, warmup=1, iters=3)
    emit(f"simulate_fused_W{windows}_N{n}", us_f,
         f"speedup_vs_segment_loop={us_l/us_f:.2f}x")
    emit(f"segment_loop_W{windows}_N{n}", us_l, "legacy-path")


def _sweep_total_accept(state):
    return state.total_accept


def bench_sweep(quick=False, json_path="BENCH_sweep.json"):
    """Sweep-engine acceptance bench: an 8-seed x 6-config Psi grid at
    N=25 run (a) as ONE `simulate_sweep` device call and (b) as the
    per-cell Python loop it replaces (`simulate` per (config, seed) —
    which recompiles per config, since every distinct `DracoConfig` is a
    fresh static jit key). Wall clock is end-to-end *including*
    compilation — exactly the cost a fig3/fig4 grid run pays — plus
    steady-state (pre-compiled) timings for the dispatch-only view.

    The per-cell math is identical FLOPs on both paths, so the task is
    deliberately small (25 clients, ~3k-param MLP): what this bench
    isolates is the *grid driver* — 1 compile + 1 dispatch vs 6 compiles
    + 48 dispatch/sync round-trips. (At the full ~146k-param fig3 model
    the same grid is compute-bound and the sweep's edge shrinks to the
    batching gain, ~1.3x end-to-end on CPU — see EXPERIMENTS.md.)
    Writes BENCH_sweep.json; the PR-4 acceptance bar is >= 2x end-to-end
    on CPU."""
    import json as json_lib
    import time

    from repro.api import make_context, simulate, simulate_sweep
    from repro.core.channel import ChannelConfig
    from repro.core.protocol import DracoConfig
    from repro.data.synthetic import federated_classification, make_mlp

    n, seeds = 25, 8
    psis = (1, 2, 4, 8, 16, 24)
    windows = 8 if quick else 24
    every = 4 if quick else 8
    key = jax.random.PRNGKey(0)
    k1, k2, key = jax.random.split(key, 3)
    train, test = federated_classification(k1, n, input_dim=16,
                                           num_classes=5, per_client=64)
    params0, _, loss, acc = make_mlp(k2, 16, (32,), 5)
    cfg0 = DracoConfig(num_clients=n, lr=0.05, local_batches=1, batch_size=16,
                       lambda_grad=0.3, lambda_tx=0.3, unify_period=50,
                       topology="cycle", max_delay_windows=4,
                       channel=ChannelConfig(message_bytes=13_000,
                                             gamma_max=10.0))
    grid = [cfg0.replace(psi=int(p)) for p in psis]
    keys = jax.random.split(key, seeds)
    ctx = make_context(grid[0], loss, train, params0=params0)

    def sweep_once():
        _, trace = simulate_sweep(
            "draco", grid, params0, loss, train, windows, keys=keys,
            eval_every=every, eval_fn=acc, eval_data=test, ctx=ctx,
            final_fn=_sweep_total_accept)
        return trace  # numpy: already blocked on device results

    def loop_once():
        out = []
        for cfg in grid:
            ctx_g = ctx.replace(cfg=cfg)
            for k in keys:
                _, tr = simulate("draco", cfg, params0, loss, train, windows,
                                 key=k, eval_every=every, eval_fn=acc,
                                 eval_data=test, ctx=ctx_g)
                out.append(tr.metrics["accuracy"])
        return out

    t0 = time.perf_counter()
    sweep_once()
    sweep_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop_once()
    loop_s = time.perf_counter() - t0
    # steady state: both paths now hit their jit caches
    sweep_steady = time_fn(sweep_once, warmup=0, iters=2) / 1e6
    loop_steady = time_fn(loop_once, warmup=0, iters=2) / 1e6

    emit(f"sweep_grid_{seeds}x{len(psis)}_N{n}_W{windows}", sweep_s * 1e6,
         f"end2end_speedup_vs_loop={loop_s / sweep_s:.2f}x")
    emit(f"sweep_loop_{seeds}x{len(psis)}_N{n}_W{windows}", loop_s * 1e6,
         "python-loop-path")
    emit(f"sweep_grid_steady_{seeds}x{len(psis)}_N{n}", sweep_steady * 1e6,
         f"steady_speedup_vs_loop={loop_steady / sweep_steady:.2f}x")
    if json_path:
        with open(json_path, "w") as f:
            json_lib.dump({
                "grid": f"{seeds}seeds_x_{len(psis)}configs",
                "num_clients": n, "windows": windows, "eval_every": every,
                "sweep_s": sweep_s, "loop_s": loop_s,
                "speedup": loop_s / sweep_s,
                "sweep_steady_s": sweep_steady, "loop_steady_s": loop_steady,
                "steady_speedup": loop_steady / sweep_steady,
            }, f, indent=1, sort_keys=True)
        print(f"# wrote {json_path}")


def bench_tasks(quick=False, json_path="BENCH_tasks.json"):
    """Task-layer: per-task DRACO step time at the paper scale (N=25)
    through `simulate(task=...)` — the whole zoo (linear-softmax / mlp /
    small-cnn / tiny-lm) plus one stateful-optimizer row (mlp + adamw:
    the flat (N, 2*Dflat) optimizer plane riding the scan carry).
    Writes BENCH_tasks.json (CI artifact) so per-workload step cost is
    tracked across PRs like the gossip/scenario/sweep benches."""
    import json as json_lib

    from repro.api import simulate
    from repro.core.protocol import DracoConfig
    from repro.tasks import get_task, list_tasks

    n = 8 if quick else 25
    windows = 6 if quick else 12
    iters = 2 if quick else 5
    cfg = DracoConfig(num_clients=n, lr=0.05, local_batches=1, batch_size=16,
                      lambda_grad=0.3, lambda_tx=0.3, unify_period=50,
                      topology="cycle", max_delay_windows=4)
    key = jax.random.PRNGKey(0)
    rows = {}
    variants = [(name, "sgd") for name in list_tasks()] + [("mlp", "adamw")]
    for name, opt in variants:
        task = get_task(name, optimizer=opt)

        def one_run():
            st, _ = simulate("draco", cfg, task=task, num_steps=windows,
                             key=key)
            return st.window_idx

        us = time_fn(one_run, warmup=1, iters=iters) / windows
        tag = f"task_{name}" + (f"_{opt}" if opt != "sgd" else "")
        emit(f"{tag}_draco_window_N{n}", us,
             f"grad_cost={task.grad_cost:.3g}MFLOP")
        rows[f"{tag}_us_per_window"] = us
    if json_path:
        rows.update({"num_clients": n, "windows": windows})
        with open(json_path, "w") as f:
            json_lib.dump(rows, f, indent=1, sort_keys=True)
        print(f"# wrote {json_path} ({len(rows)} entries)")


def bench_fig3(quick=False):
    """Fig. 3 (both panels): DRACO vs baselines final accuracy."""
    from benchmarks.fig3_convergence import run

    for task in (("emnist",) if quick else ("emnist", "poker")):
        curves = run(task, segments=3 if quick else 6,
                     seg_windows=60 if quick else 100,
                     seg_rounds=20 if quick else 30,
                     num_clients=10 if quick else 25)
        draco = curves["draco"][-1]
        best_base = max(c[-1] for m, c in curves.items() if m != "draco")
        emit(f"fig3_{task}_draco_final_acc", 0.0,
             f"draco={draco:.3f}_bestbase={best_base:.3f}")


def bench_fig4(quick=False):
    """Fig. 4: Psi sweep — accuracy and oscillation vs message cap."""
    from benchmarks.fig4_psi_sweep import run

    res = run("emnist", psis=(1, 4, 24) if quick else (1, 2, 4, 8, 24),
              windows=240 if quick else 600,
              num_clients=10 if quick else 25)
    best_psi = max(res, key=lambda p: res[p]["final_acc"])
    emit("fig4_best_psi", 0.0, f"psi={best_psi}_acc={res[best_psi]['final_acc']:.3f}")


def bench_fig_dynamic(quick=False):
    """Scenario engine: accuracy/consensus vs topology churn and
    straggler fraction (writes BENCH_scenarios.json for the CI artifact)."""
    from benchmarks.fig_dynamic import run

    res = run("emnist", quick=quick)
    frozen = res["churn"][0.0]["final_acc"]
    worst_churn = min(r["final_acc"] for r in res["churn"].values())
    worst_strag = min(r["final_acc"] for r in res["straggler"].values())
    emit("fig_dynamic_churn_robustness", 0.0,
         f"frozen={frozen:.3f}_worstchurn={worst_churn:.3f}")
    emit("fig_dynamic_straggler_robustness", 0.0,
         f"worstfrac={worst_strag:.3f}")


def bench_events(quick=False, json_path="BENCH_events.json"):
    """Event engine: per-event dispatch cost vs the windowed engine's
    per-window cost at the paper scale (N=25), plus the staleness-damped
    variant. One tape row does strictly less work than one window (one
    client acts, not a Poisson thinning of all N), but there are ~N x
    (lambda_grad + lambda_tx) x window more rows per simulated second —
    BENCH_events.json records both unit costs and the resulting
    us-per-simulated-second ratio so the speed/fidelity trade is tracked
    across PRs like the other BENCH_* artifacts."""
    import json as json_lib

    from repro.api import simulate
    from repro.events import EventConfig, events_context, simulate_events
    from repro.tasks import get_task

    n = 8 if quick else 25
    horizon = 4.0 if quick else 10.0
    iters = 2 if quick else 5
    cfg = EventConfig(num_clients=n, lr=0.05, local_batches=1, batch_size=16,
                      lambda_grad=0.3, lambda_tx=0.3, unify_period=50,
                      topology="cycle", max_delay_windows=4,
                      staleness="poly")
    task = get_task("linear-softmax")
    key = jax.random.PRNGKey(0)
    data, _ = task.make_data(jax.random.PRNGKey(1), n)
    ctx = events_context(cfg, task=task, data=data, horizon=horizon,
                         params0=task.init_params(key))
    n_events = max(ctx.tape.num_valid, 1)
    rows = {}

    def windowed():
        st, _ = simulate("draco", cfg, task=task, data=data,
                         num_steps=int(horizon / cfg.window), key=key)
        return st.window_idx

    us_w = time_fn(windowed, warmup=1, iters=iters) / (horizon / cfg.window)
    emit(f"draco_window_N{n}", us_w, "us_per_window")
    rows["draco_us_per_window"] = us_w

    for algo in ("draco-event", "fedasync-gossip"):

        def run(algo=algo):
            st, _ = simulate_events(algo, cfg, ctx=ctx, key=key)
            return st.event_idx

        us_e = time_fn(run, warmup=1, iters=iters) / n_events
        emit(f"{algo}_N{n}", us_e, "us_per_event")
        rows[f"{algo.replace('-', '_')}_us_per_event"] = us_e
        rows[f"{algo.replace('-', '_')}_us_per_sim_s"] = (
            us_e * n_events / horizon)
    rows["draco_us_per_sim_s"] = us_w / cfg.window
    rows.update({"num_clients": n, "horizon_s": horizon,
                 "tape_events": n_events,
                 "tape_capacity": ctx.tape.capacity})
    if json_path:
        with open(json_path, "w") as f:
            json_lib.dump(rows, f, indent=1, sort_keys=True)
        print(f"# wrote {json_path} ({len(rows)} entries)")


def bench_decode(quick=False):
    """Serving-layer: single-token decode latency, reduced dense arch."""
    from repro.configs.base import get_reduced
    from repro.models import model as M

    cfg = get_reduced("qwen2-1.5b")
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    B = 4
    state = M.init_decode_state(cfg, B, 128)
    tok = jnp.zeros((B,), jnp.int32)
    step = jax.jit(lambda p, t, s: M.decode_step(p, cfg, t, s))
    logits, state = step(params, tok, state)  # warm
    us = time_fn(step, params, tok, state, iters=10)
    emit("decode_step_reduced_qwen2", us, f"{B/us*1e6:.0f}tok_s")


BENCHES = {
    "gossip": bench_gossip_mix,
    "ssd": bench_ssd,
    "draco_window": bench_draco_window,
    "simulate_fused": bench_simulate_fused,
    "sweep": bench_sweep,
    "tasks": bench_tasks,
    "events": bench_events,
    "fig3": bench_fig3,
    "fig4": bench_fig4,
    "fig_dynamic": bench_fig_dynamic,
    "decode": bench_decode,
}


def main() -> None:
    from benchmarks.common import write_json
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--json", default="BENCH_gossip.json",
                    help="machine-readable results path ('' to skip)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(quick=args.quick)
    # a partial (--only) run must not clobber the tracked full-results
    # file; write it only for full sweeps or an explicit --json override
    if args.json and not (args.only and args.json == "BENCH_gossip.json"):
        write_json(args.json)


if __name__ == "__main__":
    main()
