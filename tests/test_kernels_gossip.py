"""Gossip Pallas kernel vs jnp oracle: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.gossip import gossip  # noqa: E402
from repro.kernels.gossip.ops import gossip_mix  # noqa: E402
from repro.kernels.gossip.ref import gossip_mix_ref  # noqa: E402

# the VPU body up to MIX_VPU_MAX_N (4) clients, the MXU above; K below
# one block (a block of all K lanes), and K past one budget block with a
# ragged last block (262,144 lanes at N = 2, 131,072 at 4, 65,536 at 8,
# 32,768 at 16, 16,384 at 25, 8,192 at 64), so that several grid steps run
SHAPES = [(4, 64), (16, 512), (25, 513), (32, 1000), (7, 129), (64, 2048),
          (2, 5000), (3, 1000), (2, 262_144 + 1000), (4, 131_072 + 77),
          (8, 65_536 + 129), (16, 32_768 + 513), (25, 16_384 + 77),
          (64, 2 * 8_192 + 300)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_kernel_matches_oracle(shape, dtype):
    n, d = shape
    key = jax.random.PRNGKey(n * d)
    k1, k2 = jax.random.split(key)
    q = jax.nn.softmax(jax.random.normal(k1, (n, n)), axis=1)
    deltas = jax.random.normal(k2, (n, d)).astype(dtype)
    out = gossip_mix(q, deltas, interpret=True)
    ref = gossip_mix_ref(q, deltas)
    assert out.dtype == deltas.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


def test_mix_blocks_the_train_plane_within_vmem():
    """The trainer's plane (2 clients x 326,970,880 f32) takes at most
    5,000 grid steps, not (2, 512) tiles' 638,615, and every block's two
    input and two output buffers fit the 16 MiB a v5e kernel may use."""
    k = 326_970_880
    block = gossip.mix_block_d(2, k, jnp.float32)
    assert block % 128 == 0
    assert -(-k // block) <= 5_000
    for n, dtype in [(2, jnp.float32), (2, jnp.bfloat16), (3, jnp.float32),
                     (8, jnp.bfloat16), (25, jnp.float32), (64, jnp.float32)]:
        block = gossip.mix_block_d(n, k, dtype)
        rows = gossip._tile_rows(n)
        assert rows >= n and block % 128 == 0
        assert 4 * rows * block * max(jnp.dtype(dtype).itemsize, 4) <= 16 << 20


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 24), d=st.integers(1, 300), seed=st.integers(0, 2**16))
def test_kernel_property_random(n, d, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    q = jax.random.uniform(k1, (n, n))
    q = q / q.sum(1, keepdims=True)
    deltas = jax.random.normal(k2, (n, d))
    out = gossip_mix(q, deltas, interpret=True)
    ref = gossip_mix_ref(q, deltas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_linearity():
    key = jax.random.PRNGKey(9)
    n, d = 8, 96
    q = jax.nn.softmax(jax.random.normal(key, (n, n)))
    a = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    b = jax.random.normal(jax.random.fold_in(key, 2), (n, d))
    lhs = gossip_mix(q, a + 2.0 * b, interpret=True)
    rhs = gossip_mix(q, a, interpret=True) + 2.0 * gossip_mix(q, b, interpret=True)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-4)


def test_row_stochastic_mass_distribution():
    """Each sender's delta is distributed with total weight 1 across
    receivers: column-summed output equals column-summed input."""
    key = jax.random.PRNGKey(11)
    n, d = 12, 64
    q = jax.random.uniform(key, (n, n))
    q = q - jnp.diag(jnp.diag(q))
    q = q / q.sum(1, keepdims=True)
    deltas = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    out = gossip_mix(q, deltas, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out.sum(0)), np.asarray(deltas.sum(0)), atol=1e-3)
