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
# 32,768 at 16, 16,384 at 25, 8,192 at 64), so that several grid steps run.
# Then client-stacked leaves of rank 3 and 4, mixed where they lie: a
# padded second-minor dim (2, 256), lanes that are not a multiple of 128,
# rows past one block with a ragged last block (1,024 rows of 256 lanes
# at N = 2 on the VPU, 256 at N = 8 on the MXU, one dot per row),
# and rows and lanes both ragged (one tile of rows, 2,560 f32 or 1,280
# bf16 lanes at N = 25).
SHAPES = [(4, 64), (16, 512), (25, 513), (32, 1000), (7, 129), (64, 2048),
          (2, 5000), (3, 1000), (2, 262_144 + 1000), (4, 131_072 + 77),
          (8, 65_536 + 129), (16, 32_768 + 513), (25, 16_384 + 77),
          (64, 2 * 8_192 + 300),
          (2, 2, 256), (5, 2, 256), (5, 3, 40, 130), (2, 7, 1),
          (25, 2, 3, 5, 300), (2, 2, 1_029, 256), (8, 2, 300, 256),
          (25, 9, 3_000)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_kernel_matches_oracle(shape, dtype):
    n = shape[0]
    key = jax.random.PRNGKey(int(np.prod(shape)))
    k1, k2 = jax.random.split(key)
    q = jax.nn.softmax(jax.random.normal(k1, (n, n)), axis=1)
    deltas = jax.random.normal(k2, shape).astype(dtype)
    out = gossip_mix(q, deltas, interpret=True)
    ref = gossip_mix_ref(q, deltas)
    assert out.dtype == deltas.dtype and out.shape == deltas.shape
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", [s for s in SHAPES if len(s) > 2])
@pytest.mark.parametrize("dtype", DTYPES)
def test_leaf_mix_bit_equals_the_flat_row(shape, dtype):
    """A leaf mixed in its own shape and dtype gives the bits of the same
    values raveled into an f32 (N, K) row, mixed, and rounded back: the
    same f32 sums, one rounding. Each of these leaves spans several
    blocks of its own or is one whole padded block."""
    n = shape[0]
    key = jax.random.PRNGKey(int(np.prod(shape)) + 1)
    k1, k2 = jax.random.split(key)
    q = jax.nn.softmax(jax.random.normal(k1, (n, n)), axis=1)
    leaf = jax.random.normal(k2, shape).astype(dtype)
    out = gossip_mix(q, leaf, interpret=True)
    row = gossip_mix(q, leaf.reshape(n, -1).astype(jnp.float32),
                     interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(row.astype(dtype).reshape(shape), np.float32))


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


@pytest.mark.parametrize("clients", [2, 5, 16, 25])
def test_mix_blocks_the_train_leaves_within_vmem(clients):
    """Each of the trainer's bf16 leaves (qwen2-1.5b at depth 2) is mixed
    in blocks that keep its minor two dims whole or in (16, 128) tiles,
    in at most 1,000 grid steps per client (1,500 in all at N = 2, 0.87
    MB read a step), and whose two input and two output buffers, with an
    f32 working copy of the block, fit the 16 MiB a v5e kernel may use."""
    from repro.configs.base import get_config
    from repro.launch.steps import depth_config, param_specs_abstract

    params = param_specs_abstract(depth_config(get_config("qwen2-1.5b"), 2))
    steps = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shape = (clients,) + leaf.shape
        block = gossip.mix_block(shape, jnp.bfloat16)
        assert len(block) == len(shape) and block[0] == clients
        if len(shape) == 2:
            assert block[1] == shape[1] or block[1] % 128 == 0
            tile = gossip._tile_rows(clients) * -(-block[1] // 128) * 128
        else:
            assert all(b is None for b in block[1:-2])
            rows, lanes = block[-2:]
            assert rows == shape[-2] or rows % 16 == 0
            assert lanes == shape[-1] or lanes % 128 == 0
            tile = clients * -(-rows // 16) * 16 * -(-lanes // 128) * 128
        assert (4 * 2 + 4) * tile <= 16 << 20
        steps += int(np.prod([-(-s // (b or 1))
                              for s, b in zip(shape[1:], block[1:])]))
    assert steps <= 1_000 * clients


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
