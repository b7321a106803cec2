import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mixing import mix_dense, psi_cap_mask, receive_counts
from repro.core.topology import adjacency, row_stochastic


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
