import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat
from repro.core.mixing import mix_dense, psi_cap_mask, receive_counts
from repro.core.topology import adjacency, row_stochastic
from repro.kernels.gossip import gossip
from repro.kernels.gossip.ops import gossip_mix, gossip_mix_reference


def test_mix_dense_matches_manual():
    key = jax.random.PRNGKey(0)
    n, d = 5, 7
    q = jax.nn.softmax(jax.random.normal(key, (n, n)))
    deltas = {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, d)),
              "b": jax.random.normal(jax.random.fold_in(key, 2), (n,))}
    out = mix_dense(q, deltas)
    np.testing.assert_allclose(
        np.asarray(out["w"]), np.asarray(q).T @ np.asarray(deltas["w"]), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out["b"]), np.asarray(q).T @ np.asarray(deltas["b"]), rtol=2e-5)


def test_mix_dense_kernel_path():
    key = jax.random.PRNGKey(1)
    n, d = 8, 33
    q = jax.nn.softmax(jax.random.normal(key, (n, n)))
    deltas = {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, d))}
    ref = mix_dense(q, deltas)
    out = mix_dense(q, deltas, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(ref["w"]),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,d", [(2, 1300), (25, 513)])
def test_mix_dense_kernel_path_unpadded(n, d):
    """The kernel's blocks span all N rows (N need not be a multiple of 8)
    and the last K tile is ragged; no padded copy is made."""
    key = jax.random.PRNGKey(n)
    q = jax.nn.softmax(jax.random.normal(key, (n, n)))
    deltas = {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, d))}
    out = mix_dense(q, deltas, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(q).T @ np.asarray(deltas["w"]),
                               atol=1e-5, rtol=1e-5)


def _plane_mix(q, deltas, compute_dtype, use_kernel):
    """The former formulation of `mix_dense`: every leaf raveled into one
    (N, Dflat) plane in compute_dtype, mixed at once, and unraveled."""
    plane = flat.ravel_clients(deltas, dtype=compute_dtype)
    if use_kernel:
        out = gossip_mix(q, plane, interpret=True)
    else:
        out = gossip_mix_reference(q.astype(compute_dtype), plane)
    return flat.unravel_clients(out, flat.spec_of(deltas))


def _ragged_leaves(n, dtype):
    """Leaves of rank 1 to 4 behind the client axis: an (N,) leaf, odd
    lanes, a padded second-minor dim (2, 256), and leaves whose rows, or
    lanes, run past one kernel block into a ragged last block."""
    rows = gossip.mix_block((n, 1 << 20, 256), dtype)[-2]
    lanes = gossip.mix_block((n, 1 << 30), dtype)[-1]
    wide = gossip.mix_block((n, 9, 1 << 16), dtype)[-1]
    return {"b": (), "v": (37,), "bias": (2, 256), "w": (3, 40, 130),
            "t": (2, 3, 5, 300), "rows": (2, rows + 5, 256),
            "lanes": (lanes + 77,), "wide": (9, wide + 129)}


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("compute_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [2, 5, 25])
def test_mix_dense_leaf_by_leaf_bit_equals_the_plane(n, dtype, compute_dtype,
                                                     use_kernel):
    """Each leaf mixed in its own shape and dtype gives the bits of the
    plane formulation, on both lowerings (the kernel in interpret mode:
    the VPU body at N = 2, the MXU at 5 and 25) and for each pair of leaf
    and compute dtypes: widening to f32 is exact, and the f32 sums and
    their one rounding are the same."""
    key = jax.random.PRNGKey(n)
    q = jax.nn.softmax(jax.random.normal(key, (n, n)), axis=1)
    q = q * (jax.random.uniform(jax.random.fold_in(key, 1), (n, 1)) > 0.3)
    deltas = {name: jax.random.normal(jax.random.fold_in(key, i + 2),
                                      (n,) + shape).astype(dtype)
              for i, (name, shape) in enumerate(_ragged_leaves(n, dtype).items())}
    out = mix_dense(q, deltas, use_kernel=use_kernel, interpret=True,
                    compute_dtype=compute_dtype)
    ref = _plane_mix(q, deltas, compute_dtype, use_kernel)
    for name, leaf in deltas.items():
        assert out[name].dtype == leaf.dtype and out[name].shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(out[name], np.float32),
                                      np.asarray(ref[name], np.float32),
                                      err_msg=name)


def test_psi_cap_column_budget():
    key = jax.random.PRNGKey(2)
    n, psi = 10, 3
    q = row_stochastic(adjacency("complete", n))
    capped = psi_cap_mask(key, q, psi)
    incoming = np.asarray((capped > 0).sum(0))
    assert (incoming <= psi).all()
    # kept weights unchanged where kept
    kept = np.asarray(capped)
    orig = np.asarray(q)
    mask = kept > 0
    np.testing.assert_allclose(kept[mask], orig[mask])


def test_psi_cap_noop_when_large():
    key = jax.random.PRNGKey(3)
    q = row_stochastic(adjacency("complete", 6))
    capped = psi_cap_mask(key, q, 100)
    np.testing.assert_array_equal(np.asarray(capped), np.asarray(q))


def test_receive_counts():
    q = jnp.array([[0.0, 1.0], [0.5, 0.0]])
    np.testing.assert_array_equal(np.asarray(receive_counts(q)), [1, 1])
