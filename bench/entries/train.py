"""Entry `train`: one `repro.launch.train.run` call per window.

Set-up makes two calls of the same public entry with the same seed: one
step (it compiles, and gives the state after the first step) and three
steps (warm; they give the state after three steps and the per-step
time that `train.run` prints). Every call draws the same weights, data
and transmit masks from the seed, so the window's own call follows the
same first steps. The window is one call whose step count comes from
the warm call's rate, so that it lasts about `--seconds`; its entry
(trace, initialization, placement) and its per-step host loop count,
because a user's call pays them. The metric is the tokens that the
call's steps consumed over all clients per wall second of the call.

The check follows the first three steps in the plain Qwen2 reference
(bench/configs/<config>.ref.py) in f32 with the update arithmetic the
trainer states: bf16 weights, each client's update -lr*g rounded to
bf16, the row-stochastic mix of the transmitting clients' updates in
f32, the sum rounded to bf16, and unification (every client takes the
rotating hub's weights). It compares the window's first four losses,
and per leaf the norm of each client's change after one step (the first
gradient as the update rule gets it) and after three. The three-step
call unifies after its last step (`--unify-every 3`, the same compiled
unification the window runs every `unify_every` steps), so its change
norms and the clients' disagreement after it (zero in the reference)
cover the unification and its hub.
"""
from __future__ import annotations

import contextlib
import io
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

_STEP_S = re.compile(r"\(([0-9.]+)s/step\)")
# a leaf whose reference gradient is below this share of the median
# leaf's moves by round-off alone (a key bias under softmax): not compared
NEGLIGIBLE = 1e-3
TIMING_STEPS = 9  # steps of the warm call that sizes the window


def _program_name(path) -> str:
    """Program leaf path -> reference leaf name (layer index added by
    the caller for the stacked layer groups)."""
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    if keys[0] != "groups":
        return keys[0]
    kind, leaf = keys[1].split(":")[1], keys[-1]
    return f"{kind}_norm" if leaf == "norm" else leaf


def change_norms(params0, params) -> dict:
    """Per leaf and client, the f32 norm of `params - params0`:
    {"embed": (N,), "layers.<g>.<leaf>": (N,), ...}."""
    out = {}
    flat0 = jax.tree_util.tree_flatten_with_path(params0)[0]
    flat = jax.tree_util.tree_leaves(params)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b[None].astype(jnp.float32)),
        axis=tuple(range(1, a.ndim)))))
    for (path, p0), p in zip(flat0, flat):
        name = _program_name(path)
        if path[0].key == "groups":
            for g in range(p0.shape[0]):
                out[f"layers.{g}.{name}"] = np.asarray(norm(p[:, g], p0[g]))
        else:
            out[name] = np.asarray(norm(p, p0))
    return out


class Reference:
    """The trainer's first steps, in the plain reference."""

    def __init__(self, cfg, model, traffic, seed31):
        self.cfg, self.model, self.tr = cfg, model, traffic
        self.n, self.b = traffic["clients"], traffic["batch_per_client"]
        key = jax.random.PRNGKey(seed31)
        self.k_init, self.k_data, self.k_ev = jax.random.split(key, 3)
        adj = np.zeros((self.n, self.n), bool)
        for i in range(self.n):
            adj[i, (i + 1) % self.n] = adj[i, (i - 1) % self.n] = True
        np.fill_diagonal(adj, False)
        self.q = np.where(adj, 1.0 / np.maximum(adj.sum(1, keepdims=True), 1),
                          0.0).astype(np.float32)

    def tx(self, step):
        p = 1.0 - jnp.exp(-jnp.asarray(self.tr["lambda_tx"]) * 1.0)
        return np.asarray(jax.random.uniform(
            jax.random.fold_in(self.k_ev, step), (self.n,)) < p)

    def q_eff(self, step):
        return self.q * self.tx(step)[:, None]

    def batch(self, tokens, step):
        per = tokens.shape[1]
        start = (step * self.b) % max(per - self.b + 1, 1)
        return tokens[:, start:start + self.b]

    def run(self, steps, quant=None):
        """Losses of steps 0..steps (unified every `unify_every` steps, as
        the window runs), reference gradient norms of step 0, the change
        norms after one step and after `steps` steps and a unification
        by the hub of step `steps` - 1 (as a call of `steps` steps with
        `--unify-every steps` ends), and the clients' disagreement norms
        after that unification."""
        cfg, model, n = self.cfg, self.model, self.n
        tokens = jax.random.randint(self.k_data, (n, 8 * self.b, self.tr["seq"]),
                                    0, cfg["vocab_size"])
        p0 = jax.jit(lambda k: model.init_params(k, cfg))(self.k_init)
        vg = jax.jit(jax.value_and_grad(
            lambda p, t: model.loss(p, t, cfg, quant)))
        dt = jnp.dtype(cfg["torch_dtype"])
        # the trainer adds the mixed f32 update, rounded to the weights'
        # dtype, to the weights in that dtype
        up = jax.jit(lambda p, d: jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) + b.astype(dt).astype(jnp.float32)
                          ).astype(dt), p, d))
        lr = self.tr["lr"]
        params = [p0] * n
        every = self.tr["unify_every"]
        losses, grad0, changes, spread = [], None, {}, None
        for s in range(steps + 1):
            batch = self.batch(tokens, s)
            deltas = []
            step_loss = []
            for j in range(n):
                p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                             params[j])
                loss, g = vg(p32, batch[j])
                step_loss.append(float(loss))
                if s == 0 and j == 0:
                    grad0 = _named_norms(g)
                deltas.append(jax.tree_util.tree_map(
                    lambda x: (-lr * x).astype(dt).astype(jnp.float32), g))
                del p32, g
            losses.append(float(np.mean(step_loss)))
            if s == steps:
                break
            q = self.q_eff(s)
            mixed = [jax.tree_util.tree_map(
                lambda *ds, j=j: sum(float(q[i, j]) * ds[i] for i in range(n)),
                *deltas) for j in range(n)]
            params = [up(params[j], mixed[j]) for j in range(n)]
            del deltas, mixed
            snap = params
            if s + 1 == steps:  # the check call unifies after its last step
                snap = [params[hub_of(s, steps, n)]] * n
                spread = _client_norms(snap, snap[0])
            if s + 1 in (1, steps):
                changes[s + 1] = _client_norms(snap, p0)
            if every and (s + 1) % every == 0:
                params = [params[hub_of(s, every, n)]] * n
        return losses, grad0, changes, spread


def hub_of(step, every, n):
    """The hub that unification after step `step` (0-based) copies: the
    hub rotates over the clients, one per period of `every` steps."""
    return (step // every) % n


def _client_norms(params, base) -> dict:
    """Per leaf, (N,) norms of each client's `params[j] - base` in f32."""
    return {k: np.stack(v) for k, v in _stack_clients(
        [_named_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, base)) for p in params]).items()}


def _named_norms(tree) -> dict:
    out = {"embed": float(jnp.linalg.norm(tree["embed"].astype(jnp.float32))),
           "final_norm": float(jnp.linalg.norm(
               tree["final_norm"].astype(jnp.float32)))}
    for g, layer in enumerate(tree["layers"]):
        for k, v in layer.items():
            out[f"layers.{g}.{k}"] = float(jnp.linalg.norm(v.astype(jnp.float32)))
    return out


def _stack_clients(per_client: list) -> dict:
    return {k: [d[k] for d in per_client] for k in per_client[0]}


def gaps(prog: dict, ref: dict, grad0: dict, scale: dict = None) -> float:
    """Worst leaf: |norm_prog - norm_ref| against the larger of the leaf's
    `scale` norm (default: the reference norm) and the median leaf's,
    leaves with a negligible reference gradient left out. A norm that is
    not finite makes it inf."""
    from bench.common import worst_of

    scale = ref if scale is None else scale
    g_med = float(np.median(list(grad0.values())))
    names = [k for k in ref if grad0[k] >= NEGLIGIBLE * g_med]
    r_med = float(np.median([np.max(scale[k]) for k in names]))
    worst = []
    for k in names:
        denom = np.maximum(np.maximum(np.asarray(scale[k]), r_med), 1e-30)
        worst.append(np.max(np.abs(np.asarray(prog[k]) - np.asarray(ref[k]))
                            / denom))
    return worst_of(worst)


class Cell:
    def __init__(self, cfg, model, traffic, seed, devices):
        self.cfg, self.model, self.tr = cfg, model, traffic
        self.seed31 = seed % 2**31
        self.devices = devices
        self.n, self.b = traffic["clients"], traffic["batch_per_client"]
        self.tokens_per_step = self.n * self.b * traffic["seq"]

    def args(self, steps: int, log_every: int, unify_every: int = None):
        from repro.launch import train as train_lib

        t = self.tr
        unify_every = t["unify_every"] if unify_every is None else unify_every
        return train_lib.parse_args([
            *(["--reduced"] if self.cfg.get("program_reduced") else []),
            "--arch", self.cfg["program_arch"],
            "--depth", str(self.cfg["program_depth"]),
            "--clients", str(self.n), "--batch-per-client", str(self.b),
            "--seq", str(t["seq"]), "--lr", str(t["lr"]), "--mix", t["mix"],
            "--psi", str(t["psi"]), "--topology", t["topology"],
            "--unify-every", str(unify_every),
            "--lambda-tx", str(t["lambda_tx"]), "--log-every", str(log_every),
            "--seed", str(self.seed31), "--steps", str(steps)])

    def call(self, steps: int, log_every: int, unify_every: int = None):
        from repro.launch import train as train_lib

        out = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out):
            p0, params, losses = train_lib.run(
                self.args(steps, log_every, unify_every), devices=self.devices)
            jax.block_until_ready(params)
        return p0, params, losses, out.getvalue(), time.perf_counter() - t0

    def check_config(self):
        """The program runs at the sizes the configuration file states."""
        from repro.configs.base import get_config, get_reduced
        from repro.launch import steps as steps_lib

        get = get_reduced if self.cfg.get("program_reduced") else get_config
        pc = steps_lib.depth_config(get(self.cfg["program_arch"]),
                                    self.cfg["program_depth"])
        c = self.cfg
        want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
                "num_heads": c["num_attention_heads"],
                "num_kv_heads": c["num_key_value_heads"],
                "num_layers": c["num_hidden_layers"],
                "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
                "norm_eps": c["rms_norm_eps"],
                "tie_embeddings": c["tie_word_embeddings"], "qkv_bias": True,
                "dtype": c["torch_dtype"]}
        off = {k: (getattr(pc, k), v) for k, v in want.items()
               if getattr(pc, k) != v}
        if off:
            raise ValueError(f"the trainer's config departs from {c['name']}: {off}")

    def setup(self):
        self.check_config()
        p0, params, _, _, _ = self.call(1, 1)
        self.change1 = change_norms(p0, params)
        del p0, params
        k = self.tr["check_steps"]
        p0, params, _, _, _ = self.call(k, 1, unify_every=k)
        self.change3 = change_norms(p0, params)
        self.spread3 = change_norms(
            jax.tree_util.tree_map(lambda a: a[0], params), params)
        del p0, params
        # the window's step count: the median printed step time of a warm
        # call (its first step holds the call's trace), and the rest of
        # that call's wall time as the per-call entry
        _, _, _, log, wall = self.call(TIMING_STEPS, 1)
        times = [float(x) for x in _STEP_S.findall(log)][1:]
        self.step_s = max(float(np.median(times)), 0.01)  # printed to 0.01 s
        self.overhead_s = max(wall - TIMING_STEPS * self.step_s, 0.0)

    def window(self, seconds, span):
        steps = max(self.tr["check_steps"] + 1,
                    int(round((seconds - self.overhead_s) / self.step_s)))
        with span("bench.train_run"):
            _, params, losses, log, wall = self.call(steps, 1)
        del params
        self.losses = losses
        self.steps = steps
        # where the call's time went, by the per-step times it printed
        # (to 0.01 s): its entry before the first step, the first step
        # (the step's trace and cache load) and the slowest other step
        times = [float(x) for x in _STEP_S.findall(log)]
        return ({"train_tokens_per_s": steps * self.tokens_per_step / wall},
                {"attempted": steps, "wall_s": wall, "step_s": self.step_s,
                 "overhead_s": self.overhead_s,
                 "entry_s": wall - sum(times), "first_step_s": times[0],
                 "slowest_step_s": max(times[1:], default=0.0)})

    def counts(self) -> dict:
        ref = Reference(self.cfg, self.model, self.tr, self.seed31)
        P = work.qwen2_params(self.cfg)
        read = written = links = 0
        for s in range(self.steps):
            q = ref.q_eff(s)
            r, w = work.contraction_rows(q)
            read, written, links = read + r, written + w, links + int((q != 0).sum())
        return {"model_flops": work.train_flops(P, self.steps * self.tokens_per_step),
                "mix_bytes": work.contraction_bytes(read, written, P),
                "mix_flops": work.contraction_flops(links, P)}

    def release(self):
        pass  # nothing of the program's state is kept past the window

    def readings(self, outputs=None):
        """Program (or `outputs`: losses, change1, change3 and the
        disagreement after unification, from elsewhere) against the
        reference in f32."""
        from bench.common import worst_of

        k = self.tr["check_steps"]
        ref = Reference(self.cfg, self.model, self.tr, self.seed31)
        losses_ref, grad0, ch, spread_ref = ref.run(k)
        losses, c1, c3, spread = (
            outputs if outputs is not None
            else (self.losses, self.change1, self.change3, self.spread3))
        return {"loss_gap": worst_of(abs(a - b) / abs(b) for a, b in
                                     zip(losses[:k + 1], losses_ref)),
                "grad1_gap": gaps(c1, ch[1], grad0),
                "change3_gap": gaps(c3, ch[k], grad0),
                "unify_gap": gaps(spread, spread_ref, grad0, scale=ch[k])}

    def control_outputs(self, quant="fp8"):
        ref = Reference(self.cfg, self.model, self.tr, self.seed31)
        k = self.tr["check_steps"]
        losses, _, ch, spread = ref.run(k, quant=quant)
        return losses, ch[1], ch[k], spread


# -- faults planted in the program, for the control runs and the tests ----


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _fault_unchanged():
    from repro.launch import steps

    real = steps.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda params, batch, q: (params, step(params, batch, q)[1])

    return _patch(steps, "make_train_step", make)


def _fault_half_batch():
    from repro.launch import steps

    real = steps.M.lm_loss

    def half(params, cfg, batch, **k):
        # half of the rows, or of the positions of a single row
        def cut(v):
            if v.shape[0] > 1:
                return v[: v.shape[0] // 2]
            return v[:, : v.shape[1] // 2]

        return real(params, cfg, {key: cut(v) for key, v in batch.items()}, **k)

    return _patch(steps.M, "lm_loss", half)


def _fault_no_exchange():
    from repro.core import mixing

    return _patch(mixing, "mix_dense", lambda q, deltas, **k: jax.tree_util.tree_map(
        jnp.zeros_like, deltas))


def _fault_altered():
    from repro.core import mixing

    real = mixing.mix_dense
    return _patch(mixing, "mix_dense", lambda q, deltas, **k: jax.tree_util.tree_map(
        lambda a: a * 1.2, real(q, deltas, **k)))


def _fault_nan():
    from repro.core import mixing

    real = mixing.mix_dense
    return _patch(mixing, "mix_dense", lambda q, deltas, **k: jax.tree_util.tree_map(
        lambda a: a * jnp.nan, real(q, deltas, **k)))


def _fault_no_unify():
    from repro.launch import steps

    return _patch(steps, "make_unify_step", lambda cfg, mesh: lambda p, hub: p)


def _fault_wrong_hub():
    from repro.launch import steps

    real = steps.make_unify_step

    def make(cfg, mesh):
        unify = real(cfg, mesh)
        return lambda p, hub: unify(p, (hub + 1) % jax.tree_util.tree_leaves(
            p)[0].shape[0])

    return _patch(steps, "make_unify_step", make)


FAULTS = {"unchanged": _fault_unchanged, "no_exchange": _fault_no_exchange,
          "altered": _fault_altered, "half_batch": _fault_half_batch,
          "nan": _fault_nan, "no_unify": _fault_no_unify,
          "wrong_hub": _fault_wrong_hub}
