"""End-to-end DRACO trainer on a device mesh.

Trains an assigned architecture (usually a reduced variant on CPU; the
full config on a real mesh) with the production-plane DRACO window step:
per-client local grads, row-stochastic gossip mixing with per-window
event/Psi masks, periodic unification and checkpointing.

Protocol-plane construction (gossip graph, row-stochastic Q, Metropolis
weights) goes through `repro.api.make_context`, the same context the
simulation driver uses, so the trainer and the paper-figure benchmarks
share one graph/channel setup path.

The mesh is built from the visible devices: (data=clients,
model=rest) when every client gets its own device group, else one
device holds every client replica.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 200 --clients 4
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --depth 2 \
      --clients 2 --steps 10    # published width, 2 layers, one chip
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_lib
from repro import obs
from repro.api import make_context
from repro.configs.base import ShapeConfig, get_config, get_reduced
from repro.core import mixing
from repro.core.events import sample_event_masks
from repro.core.protocol import DracoConfig
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def make_batches(key, cfg, n_clients: int, per_client: int, seq: int):
    """Synthetic LM token shards per client."""
    data = {}
    if cfg.embeds_in:
        data["embeds"] = jax.random.normal(
            key, (n_clients, per_client, seq, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
        data["labels"] = jax.random.randint(
            jax.random.fold_in(key, 1), (n_clients, per_client, seq), 0, cfg.vocab_size
        )
    else:
        data["tokens"] = jax.random.randint(
            key, (n_clients, per_client, seq), 0, cfg.vocab_size
        )
    if cfg.family == "vlm":
        data["cross_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2),
            (n_clients, per_client, cfg.num_patch_tokens, cfg.d_model),
        ).astype(jnp.dtype(cfg.dtype))
    return data


def select_batch(data, idx, batch_per_client: int):
    n = next(iter(data.values())).shape[0]
    start = (idx * batch_per_client) % max(
        next(iter(data.values())).shape[1] - batch_per_client + 1, 1
    )
    return {k: jax.lax.dynamic_slice_in_dim(v, start, batch_per_client, axis=1)
            for k, v in data.items()}


def client_mesh(n_clients: int, devices=None):
    """(data=n, model=rest) mesh over `devices` (default: all) when every
    client gets its own device group; else a (1, 1) mesh on the first
    device, which then holds all n client replicas."""
    devices = jax.devices() if devices is None else list(devices)
    model_par = len(devices) // n_clients
    shape = (n_clients, model_par) if model_par else (1, 1)
    return mesh_lib.make_mesh(shape, ("data", "model"),
                              devices=devices[:shape[0] * shape[1]])


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--depth", type=int, default=0,
                    help="cut the model to this many layer groups at full "
                         "width (0 = published depth)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mix", default="dense", choices=["dense", "ring", "none"])
    ap.add_argument("--psi", type=int, default=0)
    ap.add_argument("--topology", default="cycle")
    ap.add_argument("--unify-every", type=int, default=50)
    ap.add_argument("--lambda-tx", type=float, default=1.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args, devices=None):
    """Train per `args` (`parse_args`) on `devices` (default: all).

    Returns `(params0, params, losses)`: the single-client initial
    params, the final client-stacked params and the per-step losses.

    Records `repro.obs` spans: the root `repro.train.run`, its
    `repro.train.entry` (everything before the first step) and one
    `repro.train.step` per step, whose children split the step's host
    work (`events`, `batch`, `dispatch`) from the wait for the device
    (`sync`) and the periodic `unify` and `ckpt`."""
    with obs.span("repro.train.run"):
        with obs.span("repro.train.entry"):
            cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
            if args.depth:
                cfg = steps_lib.depth_config(cfg, args.depth)
            n = args.clients
            key = jax.random.PRNGKey(args.seed)
            k_init, k_data, k_ev = jax.random.split(key, 3)
            k_graph = jax.random.fold_in(key, 3)  # keeps legacy k_* streams intact

            mesh = client_mesh(n, devices)
            shape = ShapeConfig("train", args.seq, n * args.batch_per_client, "train")
            param_sh, batch_sh, q_sh = steps_lib.make_shardings(mesh, cfg, shape)
            jit_step = jax.jit(
                steps_lib.make_train_step(cfg, mesh, lr=args.lr, mix_mode=args.mix),
                in_shardings=(param_sh, batch_sh, q_sh),
                out_shardings=(param_sh, None))
            unify_fn = jax.jit(steps_lib.make_unify_step(cfg, mesh),
                               in_shardings=(param_sh, None), out_shardings=param_sh)

            params0 = M.init_params(k_init, cfg)

            def stack_clients(p0):
                obs.count("repro.trace.stack_clients")
                return jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), p0)

            # stacked straight onto the param shardings: no full copy on one device
            stack_fn = jax.jit(stack_clients, out_shardings=param_sh)
            params = stack_fn(params0)
            # protocol-plane context: graph + weights built once, same path the
            # unified simulation driver uses (repro.api)
            proto_cfg = DracoConfig(num_clients=n, topology=args.topology,
                                    psi=args.psi, unify_period=args.unify_every,
                                    lambda_tx=args.lambda_tx, channel=None)
            ctx = make_context(proto_cfg, graph_key=k_graph)
            q = ctx.q
            data = jax.device_put(make_batches(k_data, cfg, n,
                                               per_client=8 * args.batch_per_client,
                                               seq=args.seq), batch_sh)

            start = 0
            if args.ckpt_dir:
                latest = ckpt_lib.latest_step(args.ckpt_dir)
                if latest is not None:
                    params = ckpt_lib.restore(args.ckpt_dir, params, latest)
                    params = jax.device_put(params, param_sh)
                    start = latest
                    print(f"restored step {latest}")

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            with obs.span("repro.train.step"):
                with obs.span("repro.train.events"):
                    k_s = jax.random.fold_in(k_ev, step)
                    tx = sample_event_masks(k_s, args.lambda_tx, 1.0, n)
                    q_eff = q * tx[:, None].astype(q.dtype)
                    if args.psi > 0:
                        q_eff = mixing.psi_cap_mask(jax.random.fold_in(k_s, 7), q_eff,
                                                    args.psi)
                    q_eff = jax.device_put(q_eff, q_sh)
                with obs.span("repro.train.batch"):
                    batch = jax.device_put(
                        select_batch(data, step, args.batch_per_client), batch_sh)
                with obs.span("repro.train.dispatch"):
                    params, loss = jit_step(params, batch, q_eff)
                with obs.span("repro.train.sync"):
                    losses.append(float(loss))
                if args.unify_every and (step + 1) % args.unify_every == 0:
                    with obs.span("repro.train.unify"):
                        hub = jnp.asarray((step // args.unify_every) % n, jnp.int32)
                        params = unify_fn(params, hub)
                if (step + 1) % args.log_every == 0:
                    dt = time.time() - t0
                    print(f"step {step+1:5d} loss {np.mean(losses[-args.log_every:]):.4f} "
                          f"({dt/args.log_every:.2f}s/step)")
                    t0 = time.time()
                if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                    with obs.span("repro.train.ckpt"):
                        ckpt_lib.save(args.ckpt_dir, step + 1, jax.device_get(params))
                    print(f"saved checkpoint @ {step+1}")

        print(f"final loss {np.mean(losses[-10:]):.4f} "
              f"(first 10: {np.mean(losses[:10]):.4f})")
        return params0, params, losses


def main(argv=None):
    enable_compile_cache()
    return run(parse_args(argv))[2]


if __name__ == "__main__":
    main()
