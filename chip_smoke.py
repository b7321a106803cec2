#!/usr/bin/env python3
"""Bring-up smoke run of DRACO on a TPU: each main path once, checked.

  python chip_smoke.py             # one chip: phases A, B and C
  python chip_smoke.py --chips 4   # four chips: only the cross-chip paths

Phase A  `repro.api.simulate("draco")` at the paper's scale: EMNIST-like
         784->(160,100)->47 MLP, N=25, cycle graph, wireless channel,
         ring depth 8. Checks that the Pallas drain kernel is compiled
         into the program `simulate` runs, that the drain matches the
         XLA fallback and the f32 reference, and that the final accuracy
         matches the same run on the host CPU.
Phase B  `repro.api.simulate_events("draco-event")` at N=25 for a few
         hundred events; checks the final loss is finite.
Phase C  `repro.launch.train` on qwen2-1.5b at its published width with
         the depth cut; checks the loss is finite, the params moved and
         the Pallas `gossip_mix` matches the einsum at the run's width.

`--chips 4` runs `gossip_drain_sharded` against `gossip_drain`,
`simulate_sweep` on the sweep mesh against the unsharded grid, and the
trainer on a (4, 1) client mesh against its one-device step.

Needs a TPU: exits non-zero, with no result line, when JAX finds none.
Prints per-phase compile and run seconds (bring-up facts, not
benchmarks). The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process; it starts no other.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# A Pallas result against its f32 reference (precision=HIGHEST), per
# element, as a share of the largest magnitude the sum can reach
# (max |payload| x the largest column sum of |weights|). The kernels
# multiply at HIGHEST, so only f32 rounding and summation order remain
# (about 1e-7); CPU bit-parity does not carry over. A dot left at the
# default precision rounds its operands to bf16 and is off by about
# 1e-3, a wrong weight or slot by O(1).
KERNEL_TOL = 1e-5
# Phase A's final accuracy against the same run on the host CPU. The
# simulation traces its matmuls at HIGHEST; at the default precision the
# run ends about 0.36 lower.
ACC_MARGIN = 0.02
# mesh run against the one-device run of the same seed: relative to the
# largest param update (trainer), or to the param magnitude and the
# consensus trace (sweep).
MESH_TOL = 1e-2
# the sweep's accuracy trace, mesh against one device: one flipped
# prediction of the 24 x 2000 is 2e-5.
ACC_TOL = 1e-3

# Phase C cut, chosen from `compiled.memory_analysis()` of the one-chip
# train step compiled for a described v5e (15.75 GB usable HBM):
# N=2 clients at 1/2/3/4 layers need 6.73/7.85/8.98/10.10 GB, N=3 at 2
# layers 14.39 GB. Two layers leave room for the mix check after the
# run (the flat plane, the kernel output and the einsum reference).
TRAIN_CLIENTS, TRAIN_DEPTH = 2, 2

_COMPILE_S = [0.0]


def _count_compile(event, duration_secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration_secs


@contextlib.contextmanager
def timed(name):
    """Print the phase's XLA compile seconds and the rest of its wall time."""
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    compile_s = _COMPILE_S[0] - c0
    wall = time.perf_counter() - t0
    print(f"{name}: compile_s={compile_s:.1f} run_s={wall - compile_s:.1f}",
          flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def has_kernel(lowered) -> bool:
    """True when a lowered program holds a Mosaic kernel, not interpret."""
    from repro.kernels.gossip import ops as gossip_ops

    return ("tpu_custom_call" in lowered.as_text()
            and not gossip_ops.default_interpret())


def drain_errors(w_stack, ring, slots, out):
    """`out` against the f32 reference and the XLA fallback, in units of
    the sum's largest reachable magnitude (see KERNEL_TOL)."""
    from repro.kernels.gossip import ops as gossip_ops
    from repro.kernels.gossip.ref import gossip_drain_ref

    ref = gossip_drain_ref(w_stack, ring[slots])
    xla = gossip_ops.gossip_drain(w_stack, ring, slots, use_kernel=False)
    scale = (jnp.abs(ring[slots]).max()
             * jnp.abs(w_stack).sum(axis=(0, 1)).max())
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    return (float(jnp.abs(out - ref).max() / scale),
            float(jnp.abs(xla - ref).max() / scale))


def paper_setup(n):
    """The EMNIST preset at N clients, as `examples/quickstart.py` runs it
    with ring depth 8: (cfg, params0, loss, acc, train, test, chance)."""
    from repro.configs.draco_paper import EMNIST as t
    from repro.core.channel import ChannelConfig
    from repro.core.protocol import DracoConfig
    from repro.data.synthetic import federated_classification, make_mlp

    k_data, k_model = jax.random.split(jax.random.PRNGKey(0))
    train, test = federated_classification(
        k_data, n, input_dim=t.input_dim, num_classes=t.num_classes,
        per_client=t.samples_per_client)
    params0, _, loss, acc = make_mlp(k_model, t.input_dim, t.hidden,
                                     t.num_classes)
    cfg = DracoConfig(
        num_clients=n, lr=t.lr, local_batches=t.local_batches,
        batch_size=t.batch_size, lambda_grad=0.3, lambda_tx=0.3,
        unify_period=50, psi=6, topology="cycle", max_delay_windows=8,
        channel=ChannelConfig(message_bytes=t.message_bytes, gamma_max=10.0))
    return cfg, params0, loss, acc, train, test, 1.0 / t.num_classes


def phase_a(windows=310):
    # ends 10 windows into a unification period: the Psi cap (6 per
    # 50-window period) has silenced every link by a period's end, which
    # would leave the ring with nothing in flight to check the drain on
    from repro.api import get_algorithm, make_context, simulate
    from repro.api.simulate import _run
    from repro.core.protocol import drain_weights
    from repro.kernels.gossip import ops as gossip_ops

    cfg, params0, loss, acc, train, test, chance = paper_setup(25)
    algo, key, every = get_algorithm("draco"), jax.random.PRNGKey(1), windows // 3
    ctx = make_context(cfg, loss, train, params0=params0)
    # the program `simulate` runs, lowered at the run's own arguments
    compiled_in = has_kernel(_run.lower(
        algo, ctx, algo.init(key, cfg, params0, task=ctx.task), test,
        windows, every, acc, "accuracy"))
    st, trace = simulate(algo, cfg, params0, loss, train, num_steps=windows,
                         key=key, eval_every=every, eval_fn=acc,
                         eval_data=test, ctx=ctx)
    accuracy = float(trace.metrics["accuracy"][-1])
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        p0, tr, te = jax.device_put((params0, train, test), cpu)
        # the backend default is still the TPU: no Mosaic kernel on the CPU
        ctx_cpu = make_context(cfg, loss, tr, params0=p0).replace(
            use_kernel=False)
        _, cpu_trace = simulate(algo, cfg, p0, loss, tr, num_steps=windows,
                                key=key, eval_every=every, eval_fn=acc,
                                eval_data=te, ctx=ctx_cpu)
    cpu_accuracy = float(cpu_trace.metrics["accuracy"][-1])
    _, slots, w_next = drain_weights(st, cfg.max_delay_windows)
    in_flight = st.w_ring[slots]  # every stored broadcast, unmasked
    drain = jax.jit(gossip_ops.gossip_drain)
    errs = {name: drain_errors(w, st.buffer, slots, drain(w, st.buffer, slots))
            for name, w in (("next", w_next), ("in_flight", in_flight))}
    print(f"phase A: draco N={cfg.num_clients} D={cfg.max_delay_windows} "
          f"windows={windows} Dflat={st.pending.shape[1]} "
          f"accuracy={accuracy:.4f} (host CPU run {cpu_accuracy:.4f}, margin "
          f"{ACC_MARGIN}; chance {chance:.4f}); drain kernel in the "
          f"simulate program={compiled_in}; drain vs f32 ref / XLA fallback "
          f"vs f32 ref (tol {KERNEL_TOL}): "
          + ", ".join(f"{k} {a:.3e} / {b:.3e}" for k, (a, b) in errs.items())
          + f"; nonzero weights next={int((w_next != 0).sum())} "
          f"in_flight={int((in_flight != 0).sum())}", flush=True)
    check(compiled_in, "the Pallas drain kernel is not in simulate's program")
    check(int((in_flight != 0).sum()) > 0, "nothing in flight to drain")
    check(all(max(e) <= KERNEL_TOL for e in errs.values()),
          f"drain off its f32 reference: {errs}")
    check(abs(accuracy - cpu_accuracy) <= ACC_MARGIN,
          f"accuracy {accuracy} off the host CPU run's {cpu_accuracy}")
    check(accuracy > chance, f"accuracy {accuracy} not above chance {chance}")


def phase_b(horizon=20.0):
    from repro.api import events_context, simulate_events

    cfg, params0, loss, acc, train, test, _ = paper_setup(25)
    ctx = events_context(cfg, loss, train, params0=params0, horizon=horizon)
    st, _ = simulate_events("draco-event", cfg, params0, ctx=ctx,
                            key=jax.random.PRNGKey(1))
    losses = jax.vmap(loss, (0, None, None))(st.params, *test)
    mean_loss = float(losses.mean())
    accuracy = float(jax.vmap(acc, (0, None, None))(st.params, *test).mean())
    print(f"phase B: draco-event N={cfg.num_clients} events="
          f"{ctx.tape.num_valid} (tape capacity {ctx.tape.capacity}, "
          f"processed {int(st.event_idx)}) mean test loss={mean_loss:.4f} "
          f"accuracy={accuracy:.4f}", flush=True)
    check(np.isfinite(np.asarray(losses)).all(), "non-finite event loss")
    check(int(st.event_idx) > 0, "no event processed")


def _train_argv(extra):
    return ["--arch", "qwen2-1.5b", "--seq", "128", "--batch-per-client", "1",
            "--unify-every", "2", "--log-every", "1", *extra]


def _changed(params0, params):
    """Whether any client's params differ from the single-client start."""
    return bool(jax.jit(lambda p0, p: jnp.any(jnp.stack([
        jnp.any(b != a[None]) for a, b in zip(
            jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p))])))(
        params0, params))


def phase_c(steps=4):
    from repro.configs.base import get_config
    from repro.core import flat as flat_lib
    from repro.kernels.gossip import ops as gossip_ops
    from repro.launch import train as train_lib

    full = get_config("qwen2-1.5b")
    print(f"phase C: qwen2-1.5b at published width (d_model {full.d_model}, "
          f"heads {full.num_heads}/{full.num_kv_heads}, d_ff {full.d_ff}, "
          f"vocab {full.vocab_size}); cut: depth {full.num_layers} -> "
          f"{TRAIN_DEPTH} layers; N={TRAIN_CLIENTS} clients on one chip",
          flush=True)
    args = train_lib.parse_args(_train_argv(
        ["--depth", str(TRAIN_DEPTH), "--clients", str(TRAIN_CLIENTS),
         "--steps", str(steps)]))
    params0, params, losses = train_lib.run(args, devices=jax.devices()[:1])
    changed = _changed(params0, params)
    del params0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    def mix_error(q, params):
        flat = flat_lib.ravel_clients(params, dtype=jnp.float32)
        out = gossip_ops.gossip_mix(q, flat)
        ref = jnp.einsum("nm,nk->mk", q, flat,
                         precision=jax.lax.Precision.HIGHEST)
        scale = jnp.abs(flat).max() * jnp.abs(q).sum(axis=0).max()
        return jnp.abs(out - ref).max() / scale

    n = TRAIN_CLIENTS
    q = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(5), (n, n)), axis=1)
    compiled_in = has_kernel(jax.jit(gossip_ops.gossip_mix).lower(
        q, jax.ShapeDtypeStruct((n, n_params // n), jnp.float32)))
    err = float(jax.jit(mix_error)(q, params))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase C: params/client={n_params // n} losses="
          f"{[round(x, 4) for x in losses]} params changed={changed}; "
          f"gossip_mix compiled in={compiled_in}, vs einsum at HIGHEST "
          f"{err:.3e} (tol {KERNEL_TOL}); peak device bytes="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    check(np.isfinite(losses).all(), f"non-finite train loss {losses}")
    check(changed, "params did not change")
    check(compiled_in, "the Pallas gossip_mix kernel is not compiled in")
    check(err <= KERNEL_TOL, f"gossip_mix off the einsum: {err}")


def cross_drain(n=32, j=7, k=146_447):
    """`gossip_drain_sharded` over 4 chips against the one-chip drain."""
    from repro.kernels.gossip.ops import gossip_drain, gossip_drain_sharded
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh()
    key = jax.random.PRNGKey(0)
    w = jax.random.uniform(key, (j, n, n)) * (
        jax.random.uniform(jax.random.fold_in(key, 1), (j, n, n)) < 0.3)
    ring = jax.random.normal(jax.random.fold_in(key, 2), (j + 1, n, k))
    slots = (jnp.arange(j) + 3) % (j + 1)
    one = jax.jit(gossip_drain)(w, ring, slots)
    sharded = jax.jit(lambda w, r, s: gossip_drain_sharded(
        w, r, s, mesh, ("data",)))(w, ring, slots)
    e_sharded, e_xla = drain_errors(w, ring, slots, sharded)
    e_one, _ = drain_errors(w, ring, slots, one)
    diff = float(jnp.abs(sharded - one).max())
    print(f"cross drain: J={j} N={n} K={k} over {mesh.shape}; sharded vs f32 "
          f"ref {e_sharded:.3e}, one-chip vs f32 ref {e_one:.3e}, XLA vs f32 "
          f"ref {e_xla:.3e} (tol {KERNEL_TOL}); max |sharded - one-chip| "
          f"{diff:.3e}; out spec {sharded.sharding.spec}", flush=True)
    check(max(e_sharded, e_one) <= KERNEL_TOL, "sharded drain off its reference")
    check("data" in str(sharded.sharding.spec), "drain output not sharded")


def cross_sweep(n=24, windows=12):
    """`simulate_sweep` on the sweep mesh against the unsharded grid."""
    from repro.api import simulate_sweep
    from repro.launch.mesh import make_sweep_mesh

    cfg, params0, loss, acc, train, test, _ = paper_setup(n)
    grid = [cfg.replace(psi=p) for p in (0, 6)]
    kw = dict(keys=jax.random.split(jax.random.PRNGKey(7), 2),
              eval_every=windows // 2, eval_fn=acc, eval_data=test)
    f_one, t_one = simulate_sweep("draco", grid, params0, loss, train,
                                  windows, **kw)
    f_mesh, t_mesh = simulate_sweep("draco", grid, params0, loss, train,
                                    windows, mesh=make_sweep_mesh(), **kw)
    pairs = list(zip(jax.tree_util.tree_leaves(f_one.params),
                     jax.tree_util.tree_leaves(f_mesh.params)))
    rel = max(float(jnp.abs(a - b).max()) for a, b in pairs) / max(
        float(jnp.abs(a).max()) for a, _ in pairs)
    cons = t_one.metrics["consensus"]
    cons_rel = float(np.abs(cons - t_mesh.metrics["consensus"]).max()
                     / np.abs(cons).max())
    acc_diff = float(np.abs(t_one.metrics["accuracy"]
                            - t_mesh.metrics["accuracy"]).max())
    specs = {str(b.sharding.spec) for _, b in pairs}
    print(f"cross sweep: 2 configs x 2 seeds, N={n}, {windows} windows; "
          f"params max |mesh - one| / max |param| {rel:.3e}, consensus "
          f"trace {cons_rel:.3e} (tol {MESH_TOL}); accuracy trace max diff "
          f"{acc_diff:.3e} (tol {ACC_TOL}); param specs {sorted(specs)}",
          flush=True)
    check(rel <= MESH_TOL and cons_rel <= MESH_TOL, "mesh sweep off the grid")
    check(acc_diff <= ACC_TOL, f"mesh sweep accuracy trace off by {acc_diff}")
    check(any("data" in s for s in specs), "sweep params not sharded")


def cross_train(steps=3):
    """The trainer on a (4, 1) client mesh against the one-device step."""
    from repro.launch import train as train_lib

    args = train_lib.parse_args(_train_argv(
        ["--reduced", "--clients", "4", "--steps", str(steps)]))
    with jax.default_matmul_precision("highest"):
        p0, p_mesh, l_mesh = train_lib.run(args)
        _, p_one, l_one = train_lib.run(args, devices=jax.devices()[:1])
    specs = {str(x.sharding.spec) for x in jax.tree_util.tree_leaves(p_mesh)}
    # compared on the host: the two runs live on different device sets
    p0, p_mesh, p_one = ([np.asarray(x, np.float32)
                          for x in jax.tree_util.tree_leaves(t)]
                         for t in (p0, p_mesh, p_one))
    upd = max(np.abs(b - a[None]).max() for a, b in zip(p0, p_one))
    rel = float(max(np.abs(m - o).max() for m, o in zip(p_mesh, p_one)) / upd)
    loss_rel = float(np.max(np.abs(np.subtract(l_mesh, l_one))
                            / np.abs(l_one)))
    print(f"cross train: reduced qwen2-1.5b, 4 clients, {steps} steps; "
          f"losses mesh {[round(x, 4) for x in l_mesh]} one-device "
          f"{[round(x, 4) for x in l_one]} (max rel diff {loss_rel:.3e}); "
          f"params max |mesh - one| / max update {rel:.3e} (tol {MESH_TOL}); "
          f"mesh param specs {sorted(specs)[:3]}", flush=True)
    check(loss_rel <= MESH_TOL and rel <= MESH_TOL, "mesh trainer off one device")
    check(any("data" in s for s in specs), "trainer params not on the mesh")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    phases = ((("A", phase_a), ("B", phase_b), ("C", phase_c))
              if args.chips == 1 else
              (("drain", cross_drain), ("sweep", cross_sweep),
               ("train", cross_train)))
    failed = []
    for name, fn in phases:
        with timed(f"phase {name}"):
            try:
                fn()
            except Exception:  # run every phase, then fail
                traceback.print_exc()
                failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
