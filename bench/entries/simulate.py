"""Entry `simulate`: whole `repro.api.simulate` jobs back to back.

Set-up makes the data and the initial model from the seed on the
device, builds one `SimContext` and runs one job to warm every program
the window uses. The window runs jobs, each with a new key folded from
the seed, until `--seconds` have passed; every job ends in a host sync
(`simulate` returns its eval trace as numpy). The end-to-end metric is
simulated client-seconds per wall second over all jobs.

The check replays a seeded sample of the window's jobs in a plain
reference of the windowed DRACO protocol (paper Algorithm 1 on
superposition windows: Poisson-thinned gradient and transmission
events, the wireless channel, delays quantized to windows, the Psi cap
with random sender priority, delivery when a message's age equals its
delay, unification by a rotating hub). The reference draws its events
from the job's key in the order the protocol specifies them, and
computes in f32 at the configuration's matmul precision; it imports
nothing of the program. Its event draws alone (no model arithmetic)
also give the work counts of the traced run.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

JOB_FOLD = 1  # job keys: fold_in(fold_in(seed, JOB_FOLD), job index)
WARM_JOB = 2**30  # the warm-up job's index: never a window job's


def keep_bits(x, mantissa_bits: int):
    """`x` (f32) with its mantissa cut to `mantissa_bits`, by masking the
    bits: a float type conversion could be dropped by the compiler
    (XLA may keep excess precision), a mask cannot. Gradients pass
    straight through."""
    drop = 23 - mantissa_bits
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    cut = jax.lax.bitcast_convert_type(
        bits & jnp.uint32((0xFFFFFFFF >> drop) << drop), jnp.float32)
    return x + jax.lax.stop_gradient(cut - x)


def matmul_at(precision: str):
    """The matrix product at a stated precision: "highest" is f32 (six
    bf16 passes on a TPU); "high" the three-pass bf16 product, emulated
    so that it rounds alike on every backend: each f32 operand is split
    into a bf16 head and a bf16 tail (mantissas cut to 7 bits), and the
    tail-by-tail term dropped."""
    hi = jax.lax.Precision.HIGHEST

    def highest(a, b):
        return jnp.matmul(a, b, precision=hi)

    def bf16x3(a, b):
        def split(x):
            head = keep_bits(x, 7)
            return head, keep_bits(x - head, 7)

        (ah, al), (bh, bl) = split(a), split(b)
        return highest(ah, bh) + (highest(ah, bl) + highest(al, bh))

    return {"highest": highest, "high": bf16x3}[precision]


class Protocol:
    """Plain reference of the windowed protocol at one configuration."""

    def __init__(self, cfg, traffic, model, data, test, params0):
        self.n = cfg["num_clients"]
        self.window = float(cfg["window_s"])
        self.period = int(cfg["unify_period"])
        ch = cfg["channel"]
        self.radius = ch["radius_m"]
        self.p_tx_w = 10 ** (ch["tx_power_dbm"] / 10) / 1e3
        self.noise_w = 10 ** (ch["noise_dbm_hz"] / 10) / 1e3 * ch["bandwidth_hz"]
        self.ch = ch
        self.channel_on = bool(traffic["channel"])
        t = cfg["training"]
        self.bs, self.B, self.lr = t["batch_size"], t["local_batches"], t["lr"]
        self.lam_g, self.lam_tx = traffic["lambda_grad"], traffic["lambda_tx"]
        self.psi = int(traffic["psi"])
        self.D = int(traffic["max_delay_windows"])
        self.W = int(traffic["windows_per_job"])
        self.E = int(traffic["eval_every"])
        self.model, self.data, self.test = model, data, test
        leaves, self.treedef = jax.tree_util.tree_flatten(params0)
        self.shapes = [x.shape for x in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.K = sum(self.sizes)
        self.flat0 = self.ravel(params0)
        adj = np.zeros((self.n, self.n), bool)
        for i in range(self.n):
            if cfg["topology"] != "cycle":
                raise ValueError(f"topology {cfg['topology']!r}: the "
                                 "reference knows the cycle only")
            adj[i, (i + 1) % self.n] = adj[i, (i - 1) % self.n] = True
        np.fill_diagonal(adj, False)
        self.adj = jnp.asarray(adj)
        deg = adj.sum(axis=1, keepdims=True)
        self.q = jnp.asarray(np.where(adj, 1.0 / deg, 0.0), jnp.float32)
        self._draws = jax.jit(self._draws_body)
        self._replay = {}

    # -- flat layout of one client's params (leaf order of the pytree) --
    def ravel(self, p):
        return jnp.concatenate([x.reshape(-1) for x in
                                jax.tree_util.tree_leaves(p)])

    def unravel(self, flat):
        out, off = [], 0
        for shape, size in zip(self.shapes, self.sizes):
            out.append(flat[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # -- event draws ------------------------------------------------------
    def positions(self, key):
        """Node positions and the key of the first window, as the
        protocol's initial state draws them (uniform in a disk)."""
        kp, ks = jax.random.split(key)
        k1, k2 = jax.random.split(kp)
        r = self.radius * jnp.sqrt(jax.random.uniform(k1, (self.n,)))
        th = 2 * jnp.pi * jax.random.uniform(k2, (self.n,))
        return jnp.stack([r * jnp.cos(th), r * jnp.sin(th)], axis=-1), ks

    def _channel(self, key, pos, tx):
        """Per-link delay (s) and success: Shannon rate under Rayleigh
        fading and the interference of close concurrent senders."""
        ch, n = self.ch, self.n
        dist = jnp.maximum(jnp.linalg.norm(pos[:, None, :] - pos[None, :, :],
                                           axis=-1), 1.0)
        h = jax.random.exponential(key, (n, n))
        p_rx = self.p_tx_w * h * dist ** (-ch["path_loss_exp"])
        close = dist <= ch["interference_radius_frac"] * ch["radius_m"]
        contrib = jnp.where(close & tx[:, None], p_rx, 0.0)
        interf = jnp.maximum(contrib.sum(axis=0)[None, :] - contrib, 0.0)
        sinr = p_rx / (interf + self.noise_w)
        rate = ch["bandwidth_hz"] * jnp.log2(1.0 + sinr)
        gamma = (ch["message_bytes"] * 8) / jnp.maximum(rate, 1e-9) + dist / 3.0e8
        return gamma, (gamma <= ch["gamma_max_s"]) & tx[:, None]

    def _psi(self, key, success, count):
        """Receivers accept in a random sender order while their count in
        this period stays under Psi."""
        n = self.n
        arrivals = success.astype(jnp.int32)
        if self.psi <= 0:
            return success, count + arrivals.sum(axis=0)
        perm = jax.random.permutation(key, n)
        inv = jnp.argsort(perm)
        s_perm = arrivals[perm]
        ahead = jnp.cumsum(s_perm, axis=0) - s_perm
        ok = ((ahead + count[None, :] < self.psi) & (s_perm > 0))[inv]
        return ok & success, count + ok.sum(axis=0).astype(jnp.int32)

    def _draws_body(self, pos, key):
        n, D = self.n, self.D
        p_grad = 1.0 - jnp.exp(-jnp.asarray(self.lam_g) * self.window)
        p_tx = 1.0 - jnp.exp(-jnp.asarray(self.lam_tx) * self.window)
        n_samples = self.data[0].shape[1]

        def body(carry, w):
            key, count = carry
            keys = jax.random.split(key, 8)
            k_next, k_grad, k_gsel, k_tx, k_chan, k_psi = keys[:6]
            grad = jax.random.uniform(k_grad, (n,)) < p_grad
            idx = jax.vmap(lambda kc: jax.vmap(
                lambda kb: jax.random.randint(kb, (self.bs,), 0, n_samples))(
                    jax.random.split(kc, self.B)))(jax.random.split(k_gsel, n))
            tx = jax.random.uniform(k_tx, (n,)) < p_tx
            if self.channel_on:
                gamma, success = self._channel(k_chan, pos, tx)
                raw = jnp.ceil(gamma / self.window).astype(jnp.int32)
                delay = jnp.clip(raw, 1, D - 1)
                success = success & (raw <= D - 1) & self.adj
            else:
                success = self.adj & tx[:, None]
                delay = jnp.ones((n, n), jnp.int32)
            accept, count = self._psi(k_psi, success, count)
            if self.period > 0:
                count = jnp.where((w + 1) % self.period == 0, 0, count)
            return (k_next, count), (grad, idx, tx, accept, delay)

        _, out = jax.lax.scan(body, (key, jnp.zeros((n,), jnp.int32)),
                              jnp.arange(self.W))
        return out

    def draws(self, job_key):
        pos, k0 = self.positions(job_key)
        return self._draws(pos, k0)

    # -- model arithmetic ---------------------------------------------------
    def _replay_body(self, draws, xs, ys, flat0, precision):
        n, D, K = self.n, self.D, self.K
        grad_m, idx, tx, accept, delay = draws
        mm = matmul_at(precision)

        def loss(p, x, y):
            return self.model.loss(p, x, y, mm)

        def client_delta(x, idx_c, xs_c, ys_c):
            p = self.unravel(x)

            def sgd(p, ib):
                g = jax.grad(loss)(p, xs_c[ib], ys_c[ib])
                return jax.tree_util.tree_map(lambda a, b: a - self.lr * b,
                                              p, g), None

            pb, _ = jax.lax.scan(sgd, p, idx_c)
            return self.ravel(pb) - x

        def window(carry, w):
            x, pending, ring, w_ring, d_ring, total = carry
            arrivals = jnp.zeros((n, K), jnp.float32)
            for age in range(D - 1, 0, -1):  # oldest broadcast first
                s = (w - age) % D
                wa = w_ring[s] * (d_ring[s] == age)
                arrivals = arrivals + mm(wa.T, ring[s])
            x = x + arrivals
            delta = jax.vmap(client_delta)(x, idx[w], xs, ys)
            pending = pending + delta * grad_m[w][:, None].astype(jnp.float32)
            s = w % D
            ring = ring.at[s].set(pending)
            w_ring = w_ring.at[s].set(self.q * accept[w])
            d_ring = d_ring.at[s].set(delay[w])
            total = total + accept[w].sum(axis=0)
            pending = pending * (~tx[w]).astype(jnp.float32)[:, None]
            if self.period > 0:
                hub = (w // self.period) % n
                x = jnp.where((w + 1) % self.period == 0,
                              jnp.broadcast_to(x[hub], x.shape), x)
            return (x, pending, ring, w_ring, d_ring, total), None

        carry = (jnp.broadcast_to(flat0, (n, K)),
                 jnp.zeros((n, K), jnp.float32), jnp.zeros((D, n, K), jnp.float32),
                 jnp.zeros((D, n, n), jnp.float32), jnp.zeros((D, n, n), jnp.int32),
                 jnp.zeros((n,), jnp.int32))
        carry, _ = jax.lax.scan(window, carry, jnp.arange(self.W))
        return carry[0], carry[5]

    def replay(self, draws, precision):
        """Final flat params (N, K) and accepted-message counts (N,) of
        the job whose event draws are `draws`."""
        if precision not in self._replay:
            self._replay[precision] = jax.jit(
                lambda *a: self._replay_body(*a, precision))
        return self._replay[precision](draws, *self.data, self.flat0)

    # -- work counts from the draws -----------------------------------------
    def counts(self, draws) -> dict:
        """Necessary work of one job: fired gradients, eval samples, and
        the drain's rows and links, from the job's own event draws."""
        grad, _, _, accept, delay = (np.asarray(x) for x in draws)
        W, D, n = self.W, self.D, self.n
        # (window, age, sender, receiver): delivered at window w at age a
        stack = np.zeros((W, D - 1, n, n), bool)
        for a in range(1, D):
            stack[a:, a - 1] = accept[:W - a] & (delay[:W - a] == a)
        read, written = work.contraction_rows(stack)
        return {"grads": int(grad.sum()), "links": int(stack.sum()),
                "drain_rows_read": read, "drain_rows_written": written,
                "eval_samples": (W // self.E) * n * self.test[0].shape[0]}


class Cell:
    def __init__(self, cfg, model, traffic, seed, devices):
        self.cfg, self.model, self.traffic = cfg, model, traffic
        self.seed, self.devices = seed, devices
        self.n = cfg["num_clients"]
        self.jobs = []

    def job_key(self, j):
        from bench.common import seed_key

        return jax.random.fold_in(jax.random.fold_in(seed_key(self.seed),
                                                     JOB_FOLD), j)

    def setup(self):
        from bench.common import seed_key
        from repro.api import make_context
        from repro.core.channel import ChannelConfig
        from repro.core.protocol import DracoConfig

        cfg, tr = self.cfg, self.traffic
        k_data, k_model = jax.random.split(seed_key(self.seed))
        self.data, self.test = self.model.make_data(k_data, cfg)
        self.params0 = jax.jit(lambda k: self.model.init_params(k, cfg))(k_model)
        ch = cfg["channel"]
        channel = None
        if tr["channel"]:
            channel = ChannelConfig(
                radius=ch["radius_m"], tx_power_dbm=ch["tx_power_dbm"],
                path_loss_exp=ch["path_loss_exp"], bandwidth_hz=ch["bandwidth_hz"],
                noise_dbm_hz=ch["noise_dbm_hz"],
                interference_radius_frac=ch["interference_radius_frac"],
                message_bytes=ch["message_bytes"], gamma_max=ch["gamma_max_s"])
        t = cfg["training"]
        self.dcfg = DracoConfig(
            num_clients=self.n, lr=t["lr"], local_batches=t["local_batches"],
            batch_size=t["batch_size"], window=cfg["window_s"],
            lambda_grad=tr["lambda_grad"], lambda_tx=tr["lambda_tx"],
            unify_period=cfg["unify_period"], psi=tr["psi"],
            topology=cfg["topology"], max_delay_windows=tr["max_delay_windows"],
            channel=channel)
        self.ctx = make_context(self.dcfg, self.model.loss, self.data,
                                params0=self.params0)
        self.run_job(self.job_key(WARM_JOB))

    def run_job(self, key):
        from repro.api import simulate

        state, trace = simulate(
            self.traffic["algorithm"], self.dcfg, self.params0, self.model.loss,
            self.data, num_steps=self.traffic["windows_per_job"], key=key,
            eval_every=self.traffic["eval_every"], eval_fn=self.model.accuracy,
            eval_data=self.test, ctx=self.ctx)
        jax.block_until_ready(state.params)
        return state, trace

    def window(self, seconds, span):
        jobs = []
        t0 = time.perf_counter()
        while True:
            j = len(jobs)
            with span("bench.job"):
                state, _ = self.run_job(self.job_key(j))
            jobs.append((j, state.params, state.total_accept))
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        self.jobs = jobs
        sim_s = self.traffic["windows_per_job"] * self.cfg["window_s"]
        return {"sim_client_s_per_s": self.n * sim_s * len(jobs) / wall}, {
            "attempted": len(jobs), "wall_s": wall}

    def protocol(self):
        return Protocol(self.cfg, self.traffic, self.model, self.data,
                        self.test, self.params0)

    def counts(self) -> dict:
        """Work of every job in the window, for the per-layer readers."""
        ref = self.protocol()
        total = {}
        for j, *_ in self.jobs:
            for k, v in ref.counts(ref.draws(self.job_key(j))).items():
                total[k] = total.get(k, 0) + v
        t = self.cfg["training"]
        drain = work.contraction_flops(total["links"], ref.K)
        samples = total["grads"] * t["batch_size"] * t["local_batches"]
        total.update(
            model_flops=(work.train_flops(ref.K, samples) + drain
                         + work.eval_flops(ref.K, total["eval_samples"])),
            drain_flops=drain,
            drain_bytes=work.contraction_bytes(
                total["drain_rows_read"], total["drain_rows_written"], ref.K))
        return total

    def sample(self) -> list:
        """The jobs the check replays: drawn from the seed, the last job of
        the window always among them."""
        m = len(self.jobs)
        k = min(self.traffic["check_jobs"], m)
        rng = np.random.default_rng(self.seed)
        rest = rng.permutation(m - 1)[:k - 1].tolist() if m > 1 else []
        return sorted(rest + [m - 1])

    def release(self):
        """Keep the sampled jobs' results on the host; drop the program's
        state and the rest."""
        keep = set(self.sample())
        self.kept = [(j, jax.device_get((p, a)))
                     for j, p, a in self.jobs if j in keep]
        self.jobs = [(j, None, None) for j, *_ in self.jobs]
        self.ctx = None

    def readings(self, outputs=None):
        """The numbers compared, for the kept jobs: the program's results
        (or `outputs`, results of the same jobs from elsewhere) against
        the reference at the configuration's precision."""
        from bench.common import worst_of

        ref = self.protocol()
        prec = self.cfg["matmul_precision"]
        worst = {"accept_gap": 0.0, "params_err": 0.0}
        for i, (j, (params, total)) in enumerate(self.kept):
            dr = ref.draws(self.job_key(j))
            x_ref, tot_ref = jax.device_get(ref.replay(dr, prec))
            if outputs is not None:
                params, total = outputs[i]
            leaves = jax.tree_util.tree_leaves(params)
            x0 = np.asarray(ref.flat0)
            off = 0
            for leaf, size in zip(leaves, ref.sizes):
                prog = np.asarray(leaf, np.float64).reshape(ref.n, size)
                want = np.asarray(x_ref[:, off:off + size], np.float64)
                moved = np.linalg.norm(want - x0[None, off:off + size])
                err = np.linalg.norm(prog - want) / max(moved, 1e-30)
                worst["params_err"] = worst_of([worst["params_err"], err])
                off += size
            worst["accept_gap"] = worst_of([worst["accept_gap"], np.abs(
                np.asarray(total, np.float64) - tot_ref).max()])
        return worst

    def control_outputs(self, precision):
        """The reference at `precision` in the program's place, for the
        kept jobs."""
        ref = self.protocol()
        out = []
        for j, _ in self.kept:
            x, tot = jax.device_get(ref.replay(ref.draws(self.job_key(j)),
                                               precision))
            leaves = [x[:, o:o + s].reshape((ref.n,) + shape) for o, s, shape
                      in zip(np.cumsum([0] + ref.sizes[:-1]), ref.sizes,
                             ref.shapes)]
            out.append((leaves, tot))
        return out


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
    from repro.core import protocol

    return _patch(protocol, "draco_window", lambda state, *a, **k: state._replace(
        window_idx=state.window_idx + 1, key=jax.random.split(state.key)[0]))


def _fault_no_exchange():
    from repro.kernels.gossip import ops

    real = ops.gossip_drain
    return _patch(ops, "gossip_drain", lambda w, ring, slots, **k: jnp.zeros_like(
        real(w, ring, slots, **k)))


def _fault_altered():
    from repro.kernels.gossip import ops

    real = ops.gossip_drain
    return _patch(ops, "gossip_drain", lambda *a, **k: real(*a, **k) * 1.01)


def _fault_nan():
    from repro.kernels.gossip import ops

    real = ops.gossip_drain
    return _patch(ops, "gossip_drain", lambda *a, **k: real(*a, **k) * jnp.nan)


def _fault_half_batch():
    from repro.core import protocol

    real = protocol.local_updates

    def half(key, params, grad_mask, cfg, loss_fn, data, **k):
        return real(key, params, grad_mask, cfg.replace(
            batch_size=cfg.batch_size // 2), loss_fn, data, **k)

    return _patch(protocol, "local_updates", half)


FAULTS = {"unchanged": _fault_unchanged, "no_exchange": _fault_no_exchange,
          "altered": _fault_altered, "half_batch": _fault_half_batch,
          "nan": _fault_nan}
