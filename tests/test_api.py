"""Unified `repro.api` interface: registry + parity with the legacy paths.

The acceptance bar for the API redesign: `simulate(...)` must reproduce
the legacy `run_windows` (DRACO) and `run_baseline` (all four baselines)
results **bit-for-bit** on a fixed seed, while compiling once per
(algorithm, config)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    Algorithm,
    get_algorithm,
    list_algorithms,
    make_context,
    simulate,
    steps_for_budget,
)
from repro.api.simulate import _run
from repro.core.baselines import (
    BASELINES,
    eval_params as legacy_eval_params,
    init_baseline_state,
    run_baseline,
)
from repro.core.channel import ChannelConfig
from repro.core.protocol import DracoConfig, build_graph, init_state, run_windows
from repro.data.synthetic import federated_classification, make_mlp

N = 5
ALL_METHODS = ("draco",) + tuple(BASELINES)


@pytest.fixture(scope="module")
def task():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    train, test = federated_classification(k1, N, input_dim=6, num_classes=3,
                                           per_client=64)
    params0, apply, loss, acc = make_mlp(k2, 6, (8,), 3)
    return train, test, params0, loss, acc


def _cfg(**kw):
    base = dict(num_clients=N, lr=0.1, local_batches=1, batch_size=8,
                lambda_grad=0.8, lambda_tx=0.8, unify_period=10, psi=2,
                topology="complete", max_delay_windows=3, channel=None)
    base.update(kw)
    return DracoConfig(**base)


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_registry_resolves_every_method():
    names = list_algorithms()
    for name in ALL_METHODS:
        algo = get_algorithm(name)
        assert name in names
        assert isinstance(algo, Algorithm)
        # singleton: jit-static identity is stable across lookups
        assert get_algorithm(name) is algo
    with pytest.raises(KeyError):
        get_algorithm("no-such-method")


@pytest.mark.slow
def test_draco_parity_bitwise(task):
    """simulate("draco", ...) == run_windows bit-for-bit, incl. wireless
    channel + Psi cap + unification, with in-jit eval enabled."""
    train, test, params0, loss, acc = task
    cfg = _cfg(channel=ChannelConfig(message_bytes=51_640, gamma_max=10.0))
    key = jax.random.PRNGKey(7)
    q, adj = build_graph(cfg)
    legacy = run_windows(init_state(key, cfg, params0), cfg, q, adj, loss,
                         train, 12)
    new, trace = simulate("draco", cfg, params0, loss, train, 12, key=key,
                          eval_every=4, eval_fn=acc, eval_data=test)
    _assert_trees_equal(legacy.params, new.params)
    _assert_trees_equal(legacy.pending, new.pending)
    _assert_trees_equal(legacy.buffer, new.buffer)
    np.testing.assert_array_equal(np.asarray(legacy.accept_count),
                                  np.asarray(new.accept_count))
    np.testing.assert_array_equal(np.asarray(legacy.total_accept),
                                  np.asarray(new.total_accept))
    # cumulative counter survives the periodic accept_count reset
    assert int(new.total_accept.sum()) >= int(new.accept_count.sum())
    assert int(legacy.window_idx) == int(new.window_idx) == 12
    assert list(trace.step) == [4, 8, 12]
    assert np.isfinite(trace.metrics["accuracy"]).all()
    assert (trace.metrics["consensus"] >= 0).all()


@pytest.mark.slow
@pytest.mark.parametrize("method", BASELINES)
def test_baseline_parity_bitwise(method, task):
    """simulate(method, ...) == run_baseline bit-for-bit for every
    registered baseline, and eval_params matches the legacy de-biasing."""
    train, _, params0, loss, _ = task
    cfg = _cfg(topology="cycle")
    key = jax.random.PRNGKey(11)
    legacy = run_baseline(method, init_baseline_state(key, cfg, params0),
                          cfg, loss, train, 10)
    new, _ = simulate(method, cfg, params0, loss, train, 10, key=key)
    _assert_trees_equal(legacy.params, new.params)
    np.testing.assert_array_equal(np.asarray(legacy.push_weight),
                                  np.asarray(new.push_weight))
    _assert_trees_equal(legacy_eval_params(method, legacy),
                        get_algorithm(method).eval_params(new))


def test_simulate_compiles_once_per_algo_cfg(task):
    """Re-running simulate with the same (algo, cfg, loss) hits the jit
    cache; a different cfg triggers exactly one new compile."""
    train, _, params0, loss, _ = task
    cfg = _cfg()
    key = jax.random.PRNGKey(3)
    simulate("draco", cfg, params0, loss, train, 3, key=key)
    n0 = _run._cache_size()
    simulate("draco", cfg, params0, loss, train, 3, key=key)
    assert _run._cache_size() == n0
    simulate("draco", cfg.replace(psi=3), params0, loss, train, 3, key=key)
    assert _run._cache_size() == n0 + 1


def test_simulate_traces_matmuls_at_highest(task):
    """Every dot of the simulation program (local update, drain, eval) is
    traced at `MATMUL_PRECISION`: f32 on TPU, where the default is bf16."""
    train, test, params0, loss, acc = task
    cfg = _cfg()
    algo = get_algorithm("draco")
    ctx = make_context(cfg, loss, train, params0=params0)
    state = algo.init(jax.random.PRNGKey(0), cfg, params0, task=ctx.task)
    text = _run.lower(algo, ctx, state, test, 4, 2, acc, "accuracy").as_text()
    dots = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots)


def test_ctx_use_kernel_is_static(task):
    """The drain's lowering choice rides the context's static aux data:
    it survives flatten/unflatten and keys a separate compile."""
    train, _, params0, loss, _ = task
    ctx = make_context(_cfg(), loss, train, params0=params0)
    xla = ctx.replace(use_kernel=False)
    leaves, treedef = jax.tree_util.tree_flatten(xla)
    assert jax.tree_util.tree_unflatten(treedef, leaves).use_kernel is False
    assert ctx.use_kernel is None
    assert treedef != jax.tree_util.tree_structure(ctx)


@pytest.mark.slow
def test_shared_context_reused_across_methods(task):
    """One SimContext drives every method (graph built once)."""
    train, _, params0, loss, _ = task
    cfg = _cfg(topology="cycle")
    ctx = make_context(cfg, loss, train)
    key = jax.random.PRNGKey(5)
    for name in ALL_METHODS:
        st, _ = simulate(name, cfg, params0, loss, train, 2, key=key, ctx=ctx)
        for leaf in jax.tree_util.tree_leaves(st.params):
            assert bool(jnp.isfinite(leaf).all()), name


def test_ctx_cfg_mismatch_guard(task):
    """A stale ctx.cfg raises; ctx.replace(cfg=...) shares the graph."""
    train, _, params0, loss, _ = task
    cfg = _cfg(topology="cycle")
    ctx = make_context(cfg, loss, train)
    key = jax.random.PRNGKey(9)
    cfg2 = cfg.replace(psi=1)
    with pytest.raises(ValueError, match="ctx.cfg"):
        simulate("draco", cfg2, params0, loss, train, 2, key=key, ctx=ctx)
    st, _ = simulate("draco", cfg2, params0, loss, train, 2, key=key,
                     ctx=ctx.replace(cfg=cfg2))
    assert int(st.window_idx) == 2


def test_steps_for_budget_compute_matching():
    cfg = _cfg(lambda_grad=0.1, window=1.0)
    p = 1.0 - np.exp(-0.1)
    budget = 100 * p  # DRACO's expected grads over 100 windows
    assert steps_for_budget("draco", cfg, budget) == 100
    assert steps_for_budget("sync-symm", cfg, budget) == max(1, round(budget))
    assert steps_for_budget("sync-push", cfg, budget) == max(1, round(budget))
    assert steps_for_budget("async-symm", cfg, budget) == max(1, round(budget / 0.5))
    assert steps_for_budget("async-push", cfg, budget) == max(1, round(budget / 0.5))


def test_eval_every_zero_skips_trace(task):
    train, _, params0, loss, _ = task
    cfg = _cfg()
    st, trace = simulate("draco", cfg, params0, loss, train, 4,
                         key=jax.random.PRNGKey(1))
    assert trace.step.shape == (0,)
    assert trace.metrics == {}
    assert int(st.window_idx) == 4


@pytest.mark.slow
def test_final_partial_chunk_eval_row(task):
    """`num_steps % eval_every` trailing steps end with a metrics row at
    step `num_steps`, so the trace reflects the end-of-run model (the
    pre-PR4 driver ran them metric-free and under-reported every run
    whose horizon wasn't a multiple of the cadence)."""
    train, test, params0, loss, acc = task
    cfg = _cfg()
    key = jax.random.PRNGKey(4)
    st, trace = simulate("draco", cfg, params0, loss, train, 10, key=key,
                         eval_every=4, eval_fn=acc, eval_data=test)
    assert list(trace.step) == [4, 8, 10]
    # the final row is measured on the returned final state
    final_acc = float(jax.vmap(lambda p: acc(p, test[0], test[1]))(
        st.params).mean())
    np.testing.assert_allclose(trace.metrics["accuracy"][-1], final_acc,
                               rtol=1e-6)
    # fewer steps than the cadence -> exactly one row, at num_steps
    st2, trace2 = simulate("draco", cfg, params0, loss, train, 3, key=key,
                           eval_every=4, eval_fn=acc, eval_data=test)
    assert list(trace2.step) == [3]


@pytest.mark.slow
def test_trace_step_dtype_unified(task):
    """SimTrace.step is int32 for empty, scanned, and appended rows."""
    train, test, params0, loss, acc = task
    cfg = _cfg()
    key = jax.random.PRNGKey(4)
    _, empty = simulate("draco", cfg, params0, loss, train, 2, key=key)
    assert empty.step.dtype == np.int32
    _, exact = simulate("draco", cfg, params0, loss, train, 8, key=key,
                        eval_every=4, eval_fn=acc, eval_data=test)
    assert exact.step.dtype == np.int32 and list(exact.step) == [4, 8]
    _, ragged = simulate("draco", cfg, params0, loss, train, 9, key=key,
                         eval_every=4, eval_fn=acc, eval_data=test)
    assert ragged.step.dtype == np.int32 and list(ragged.step) == [4, 8, 9]


def test_resume_from_state_without_key(task):
    """Resuming from an existing state needs no PRNGKey; two chained
    simulate calls equal one long run (scan is state-threaded)."""
    train, _, params0, loss, _ = task
    cfg = _cfg()
    key = jax.random.PRNGKey(2)
    full, _ = simulate("draco", cfg, params0, loss, train, 8, key=key)
    half, _ = simulate("draco", cfg, params0, loss, train, 4, key=key)
    resumed, _ = simulate("draco", cfg, params0, loss, train, 4, state=half)
    _assert_trees_equal(full.params, resumed.params)
    with pytest.raises(ValueError, match="key is required"):
        simulate("draco", cfg, params0, loss, train, 4)
