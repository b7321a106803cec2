"""The built-in task zoo: four workloads spanning the model registry.

  - ``linear-softmax`` — single dense softmax on the Gaussian-mixture
    classification data: exactly the workload the simulator hard-coded
    before the task layer, kept bit-for-bit (the default task).
  - ``mlp`` — the paper-style 2-hidden-layer relu MLP on the same
    non-IID mixture (the fig3 EMNIST/Poker stand-in family).
  - ``small-cnn`` — a 2-conv + pooled-head network over the mixture
    reshaped as single-channel images (the paper's 0.57 MB CNN shape).
  - ``tiny-lm`` — a one-block pre-norm transformer decoder (RoPE
    attention + SwiGLU MLP from `repro.models.layers`) over the
    deterministic synthetic token streams; metric is perplexity.

Every builder returns a `Task` with plain SGD + constant schedule as
the local update rule; swap the optimizer with
``get_task("mlp", optimizer="adamw")`` or ``task.with_optimizer(...)``
— optimizer state lands on the flat plane automatically.

`grad_cost` is the relative FLOP price of one local gradient event per
sample: ``6 * n_params`` (fwd + ~2x bwd, 2 FLOPs per MAC), times
``seq_len`` for the LM (every sample is a full sequence), in MFLOPs.
`repro.api.steps_for_budget` uses it so budget-matched runs equalize
FLOPs across tasks, not just event counts.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (apply_rope, cross_entropy, dense_init,
                                  init_mlp, top1_accuracy)
from repro.models.layers import mlp as swiglu_mlp
from repro.models.layers import rms_norm
from repro.tasks.base import Task, register_task


def _param_count(init_params) -> int:
    shapes = jax.eval_shape(init_params, jax.random.PRNGKey(0))
    return int(sum(np.prod(l.shape, dtype=np.int64)
                   for l in jax.tree_util.tree_leaves(shapes)))


def _mflops_per_grad(n_params: int, tokens: int = 1) -> float:
    return 6.0 * n_params * tokens / 1e6


def _opt_variant(base: Task, optimizer, schedule, opt_kwargs,
                 schedule_kwargs) -> Task:
    """Optimizer variant of a cached base workload.

    Every spelling of the same workload shares ONE base task per knob
    set (the `@lru_cache`d `_*_base` builders below), so
    ``get_task("mlp", optimizer="adamw")`` and
    ``get_task("mlp").with_optimizer("adamw")`` produce *equal* tasks —
    same loss/eval/data closures, hence one static jit key and no
    spurious ctx-task mismatches.
    """
    return base.with_optimizer(optimizer, schedule=schedule,
                               schedule_kwargs=schedule_kwargs,
                               **(opt_kwargs or {}))


# ---------------------------------------------------------------------------
# Classification family (Gaussian mixture, Dirichlet non-IID shards)
# ---------------------------------------------------------------------------


def _classification_data(key, num_clients, *, input_dim, num_classes,
                         per_client, alpha, noise, test_size):
    from repro.data.synthetic import federated_classification

    return federated_classification(
        key, num_clients, input_dim=input_dim, num_classes=num_classes,
        per_client=per_client, alpha=alpha, test_size=test_size, noise=noise)


@lru_cache(maxsize=None)
def _mlp_base(name, hidden, input_dim, num_classes, per_client, alpha,
              noise) -> Task:
    from repro.data.synthetic import make_mlp

    # apply/loss/accuracy close over dims only, not over the params the
    # throwaway key produces — one build gives the stable jit-key closures
    _, _, loss, acc = make_mlp(jax.random.PRNGKey(0), input_dim, hidden,
                               num_classes)
    init = partial(_mlp_init, input_dim=input_dim, hidden=hidden,
                   num_classes=num_classes)
    return Task(
        name=name, init_params=init, loss_fn=loss, eval_fn=acc,
        make_data=partial(_classification_data, input_dim=input_dim,
                          num_classes=num_classes, per_client=per_client,
                          alpha=alpha, noise=noise, test_size=2000),
        metric_name="accuracy",
        grad_cost=_mflops_per_grad(_param_count(init)),
    )


def _mlp_init(key, *, input_dim, hidden, num_classes):
    from repro.data.synthetic import make_mlp

    return make_mlp(key, input_dim, hidden, num_classes)[0]


@register_task("linear-softmax")
def build_linear_softmax(input_dim: int = 16, num_classes: int = 5,
                         per_client: int = 256, alpha: float = 0.5,
                         noise: float = 0.6, optimizer: str = "sgd",
                         schedule: str = "constant", opt_kwargs=None,
                         schedule_kwargs=None) -> Task:
    """Single dense layer + softmax CE — the pre-task-layer default
    workload, bit-for-bit (tests/test_tasks.py pins it)."""
    base = _mlp_base("linear-softmax", (), input_dim, num_classes,
                     per_client, alpha, noise)
    return _opt_variant(base, optimizer, schedule, opt_kwargs,
                        schedule_kwargs)


@register_task("mlp")
def build_mlp(input_dim: int = 16, num_classes: int = 5,
              hidden: tuple = (32, 32), per_client: int = 256,
              alpha: float = 0.5, noise: float = 0.6,
              optimizer: str = "sgd", schedule: str = "constant",
              opt_kwargs=None, schedule_kwargs=None) -> Task:
    """Paper-style relu MLP (fig3's EMNIST/Poker stand-in family)."""
    base = _mlp_base("mlp", tuple(hidden), input_dim, num_classes,
                     per_client, alpha, noise)
    return _opt_variant(base, optimizer, schedule, opt_kwargs,
                        schedule_kwargs)


# ---------------------------------------------------------------------------
# small-cnn: 2 conv blocks + dense head over mixture "images"
# ---------------------------------------------------------------------------


def _cnn_init(key, *, side, channels, num_classes):
    c1, c2 = channels
    k1, k2, k3 = jax.random.split(key, 3)
    feat = (side // 4) * (side // 4) * c2
    return {
        "conv1": dense_init(k1, (3, 3, 1, c1), 9),
        "b1": jnp.zeros((c1,)),
        "conv2": dense_init(k2, (3, 3, c1, c2), 9 * c1),
        "b2": jnp.zeros((c2,)),
        "w_head": dense_init(k3, (feat, num_classes), feat),
        "b_head": jnp.zeros((num_classes,)),
    }


def _avg_pool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def _cnn_apply(p, x, *, side):
    h = x.reshape(-1, side, side, 1)
    for w, b in ((p["conv1"], p["b1"]), (p["conv2"], p["b2"])):
        h = jax.lax.conv_general_dilated(
            h, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = _avg_pool2(jax.nn.relu(h + b))
    return h.reshape(h.shape[0], -1) @ p["w_head"] + p["b_head"]


@lru_cache(maxsize=None)
def _cnn_base(side, num_classes, channels, per_client, alpha, noise) -> Task:
    init = partial(_cnn_init, side=side, channels=channels,
                   num_classes=num_classes)
    apply = partial(_cnn_apply, side=side)

    def loss(params, x, y):
        return cross_entropy(apply(params, x), y)

    def accuracy(params, x, y):
        return top1_accuracy(apply(params, x), y)

    return Task(
        name="small-cnn", init_params=init, loss_fn=loss, eval_fn=accuracy,
        make_data=partial(_classification_data, input_dim=side * side,
                          num_classes=num_classes, per_client=per_client,
                          alpha=alpha, noise=noise, test_size=1000),
        metric_name="accuracy",
        # conv FLOPs dominate the tiny head: count them spatially
        # (params alone undercounts weight reuse by H*W)
        grad_cost=_mflops_per_grad(
            9 * 1 * channels[0] * side * side
            + 9 * channels[0] * channels[1] * (side // 2) * (side // 2)
            + (side // 4) * (side // 4) * channels[1] * num_classes),
    )


@register_task("small-cnn")
def build_small_cnn(side: int = 8, num_classes: int = 5,
                    channels: tuple = (8, 16), per_client: int = 256,
                    alpha: float = 0.5, noise: float = 0.6,
                    optimizer: str = "sgd", schedule: str = "constant",
                    opt_kwargs=None, schedule_kwargs=None) -> Task:
    """2-conv + pooled head over `side x side` single-channel mixture
    images (flat `(B, side*side)` inputs, reshaped inside apply — the
    data pipeline is shared with the dense classification tasks)."""
    if side % 4 != 0:
        raise ValueError(f"side must be divisible by 4 (two 2x2 pools), "
                         f"got {side}")
    base = _cnn_base(side, num_classes, tuple(channels), per_client, alpha,
                     noise)
    return _opt_variant(base, optimizer, schedule, opt_kwargs,
                        schedule_kwargs)


# ---------------------------------------------------------------------------
# tiny-lm: one-block pre-norm transformer decoder on synthetic tokens
# ---------------------------------------------------------------------------


def _lm_init(key, *, vocab, d_model, num_heads, d_ff):
    ke, kq, kk, kv, ko, km, kh = jax.random.split(key, 7)
    hd = d_model // num_heads
    return {
        "emb": dense_init(ke, (vocab, d_model), d_model),
        "ln1": jnp.zeros((d_model,)),
        "attn": {
            "wq": dense_init(kq, (d_model, num_heads * hd), d_model),
            "wk": dense_init(kk, (d_model, num_heads * hd), d_model),
            "wv": dense_init(kv, (d_model, num_heads * hd), d_model),
            "wo": dense_init(ko, (num_heads * hd, d_model), num_heads * hd),
        },
        "ln2": jnp.zeros((d_model,)),
        "mlp": init_mlp(km, d_model, d_ff, jnp.float32),
        "lnf": jnp.zeros((d_model,)),
        "head": dense_init(kh, (d_model, vocab), d_model),
    }


def _lm_apply(p, toks, *, num_heads, rope_theta=10_000.0, eps=1e-5):
    """toks (B, S) int32 -> logits (B, S, V); causal RoPE attention."""
    B, S = toks.shape
    d = p["emb"].shape[1]
    hd = d // num_heads
    h = p["emb"][toks]  # (B, S, d)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    a = rms_norm(h, p["ln1"], eps)
    q = apply_rope((a @ p["attn"]["wq"]).reshape(B, S, num_heads, hd),
                   pos, rope_theta)
    k = apply_rope((a @ p["attn"]["wk"]).reshape(B, S, num_heads, hd),
                   pos, rope_theta)
    v = (a @ p["attn"]["wv"]).reshape(B, S, num_heads, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + out.reshape(B, S, d) @ p["attn"]["wo"]
    h = h + swiglu_mlp(p["mlp"], rms_norm(h, p["ln2"], eps))
    return rms_norm(h, p["lnf"], eps) @ p["head"]


def _lm_data(key, num_clients, *, per_client, seq_len, vocab, eval_size):
    from repro.data.synthetic import lm_token_batches

    kt, ke = jax.random.split(key)
    toks = lm_token_batches(kt, num_clients, per_client, seq_len + 1, vocab)
    ev = lm_token_batches(ke, 1, eval_size, seq_len + 1, vocab)[0]
    return (toks[..., :-1], toks[..., 1:]), (ev[:, :-1], ev[:, 1:])


@lru_cache(maxsize=None)
def _lm_base(vocab, d_model, num_heads, d_ff, seq_len, per_client,
             eval_size) -> Task:
    init = partial(_lm_init, vocab=vocab, d_model=d_model,
                   num_heads=num_heads, d_ff=d_ff)
    apply = partial(_lm_apply, num_heads=num_heads)

    def loss(params, x, y):
        return cross_entropy(apply(params, x), y)

    def perplexity(params, ex, ey):
        return jnp.exp(jnp.minimum(loss(params, ex, ey), 20.0))

    return Task(
        name="tiny-lm", init_params=init, loss_fn=loss, eval_fn=perplexity,
        make_data=partial(_lm_data, per_client=per_client, seq_len=seq_len,
                          vocab=vocab, eval_size=eval_size),
        metric_name="perplexity",
        grad_cost=_mflops_per_grad(_param_count(init), tokens=seq_len),
    )


@register_task("tiny-lm")
def build_tiny_lm(vocab: int = 64, d_model: int = 32, num_heads: int = 2,
                  d_ff: int = 64, seq_len: int = 16, per_client: int = 128,
                  eval_size: int = 64, optimizer: str = "sgd",
                  schedule: str = "constant", opt_kwargs=None,
                  schedule_kwargs=None) -> Task:
    """One-block pre-norm decoder (RoPE attention + SwiGLU from
    `repro.models.layers`) on the deterministic synthetic token streams.
    Metric: per-client perplexity on a held-out stream (lower is
    better); the grad cost scales with `seq_len` — every local batch
    sample is a full sequence."""
    if d_model % num_heads != 0:
        raise ValueError(f"d_model={d_model} not divisible by "
                         f"num_heads={num_heads}")
    base = _lm_base(vocab, d_model, num_heads, d_ff, seq_len, per_client,
                    eval_size)
    return _opt_variant(base, optimizer, schedule, opt_kwargs,
                        schedule_kwargs)
