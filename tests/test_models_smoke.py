"""Per-arch smoke tests: reduced variant of each assigned architecture runs
one forward + one train step + one decode step on CPU; shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, get_config, get_reduced
from repro.models.registry import build_model

# tier-2: heavy reduced-arch smoke battery (~95s) (ROADMAP tier-1 runs -m "not slow")
pytestmark = pytest.mark.slow


def _batch(cfg, key, B=2, S=32):
    batch = {}
    if cfg.embeds_in:
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model))
        batch["labels"] = jax.random.randint(
            jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    if cfg.family == "vlm":
        batch["cross_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (B, cfg.num_patch_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init(key)
    batch = _batch(cfg, key)
    B, S = 2, 32
    logits, aux = m.apply(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())

    loss, grads = jax.value_and_grad(m.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    new_params = jax.tree_util.tree_map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2 = m.loss(new_params, batch)
    assert bool(jnp.isfinite(loss2))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    cfg = get_reduced(arch)
    m = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = m.init(key)
    B = 2
    st = m.init_decode_state(B, 16)
    cross_kv = None
    if cfg.family == "vlm":
        pe = jax.random.normal(key, (B, cfg.num_patch_tokens, cfg.d_model))
        cross_kv = m.init_cross_kv(params, pe)
    tok = (jax.random.normal(jax.random.fold_in(key, 1), (B, 1, cfg.d_model))
           if cfg.embeds_in else jnp.zeros((B,), jnp.int32))
    for _ in range(3):
        logits, st = m.decode_step(params, tok, st, cross_kv)
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        if not cfg.embeds_in:
            tok = jnp.argmax(logits, axis=-1)
    assert int(st.pos) == 3


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_exact_assignment(arch):
    """The full configs match the assigned table (never instantiated)."""
    cfg = get_config(arch)
    table = {
        "mamba2_2p7b": (64, 2560, 0, 0, 0, 50280),
        "qwen3_moe_30b_a3b": (48, 2048, 32, 4, 768, 151936),
        "stablelm_3b": (32, 2560, 32, 32, 6912, 50304),
        "zamba2_2p7b": (54, 2560, 32, 32, 10240, 32000),
        "qwen2p5_32b": (64, 5120, 40, 8, 27648, 152064),
        "qwen2_1p5b": (28, 1536, 12, 2, 8960, 151936),
        "yi_34b": (60, 7168, 56, 8, 20480, 64000),
        "olmoe_1b_7b": (16, 2048, 16, 16, 1024, 50304),
        "llama3p2_vision_11b": (40, 4096, 32, 8, 14336, 128256),
        "musicgen_large": (48, 2048, 32, 32, 8192, 2048),
    }
    L, d, h, kv, ff, v = table[arch]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch
    assert cfg.source  # every config cites its source
    if arch == "mamba2_2p7b":
        assert cfg.ssm_state == 128
    if arch == "zamba2_2p7b":
        assert cfg.ssm_state == 64
    if arch == "qwen3_moe_30b_a3b":
        assert (cfg.num_experts, cfg.experts_per_token) == (128, 8)
    if arch == "olmoe_1b_7b":
        assert (cfg.num_experts, cfg.experts_per_token) == (64, 8)
    if arch in ("qwen2p5_32b", "qwen2_1p5b"):
        assert cfg.qkv_bias
