"""End-to-end behaviour tests for the full DRACO system."""

import jax
import numpy as np
import pytest

from repro.core.baselines import init_baseline_state, run_baseline, eval_params
from repro.core.channel import ChannelConfig
from repro.core.protocol import DracoConfig, build_graph, init_state, run_windows
from repro.data.synthetic import federated_classification, make_mlp

# tier-2: end-to-end system runs (ROADMAP tier-1 runs -m "not slow")
pytestmark = pytest.mark.slow

N = 8


@pytest.fixture(scope="module")
def task():
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    train, test = federated_classification(k1, N, input_dim=10, num_classes=5,
                                           per_client=200)
    params0, apply, loss, acc = make_mlp(k2, 10, (32, 32), 5)
    return train, test, params0, loss, acc


def _acc(params, acc, test):
    tx, ty = test
    return float(jax.vmap(lambda p: acc(p, tx, ty))(params).mean())


def test_draco_beats_or_matches_baselines_over_wireless(task):
    """Fig. 3 qualitative claim: DRACO is competitive with all four
    baselines under an unreliable wireless channel (cycle topology)."""
    train, test, params0, loss, acc = task
    chan = ChannelConfig(message_bytes=51_640, gamma_max=10.0)
    cfg = DracoConfig(num_clients=N, lr=0.1, local_batches=1, batch_size=32,
                      lambda_grad=0.5, lambda_tx=0.5, unify_period=25, psi=4,
                      topology="cycle", max_delay_windows=4, channel=chan)
    q, adj = build_graph(cfg)
    st = init_state(jax.random.PRNGKey(1), cfg, params0)
    st = run_windows(st, cfg, q, adj, loss, train, 400)
    draco_acc = _acc(st.params, acc, test)

    base_accs = {}
    for m in ("sync-symm", "async-push"):
        bst = init_baseline_state(jax.random.PRNGKey(1), cfg, params0)
        bst = run_baseline(m, bst, cfg, loss, train, 120)
        base_accs[m] = _acc(eval_params(m, bst), acc, test)

    assert draco_acc > 0.5, draco_acc
    # competitive: within 10 points of the best baseline
    assert draco_acc > max(base_accs.values()) - 0.10, (draco_acc, base_accs)


def test_trainer_client_mesh_shapes():
    """Too few devices for one group per client: a (1, 1) mesh on the
    first device holds every client replica."""
    from repro.launch.train import client_mesh

    one = client_mesh(4, jax.devices()[:1])
    assert dict(one.shape) == {"data": 1, "model": 1}
    assert one.devices.ravel().tolist() == jax.devices()[:1]


def test_trainer_cli_end_to_end(tmp_path, monkeypatch):
    """examples-grade driver: reduced arch trains and checkpoints resume."""
    from repro.launch import train as train_lib
    from repro.launch.train import main as train_main

    # the CLI's persistent compile cache stays out of the test session
    monkeypatch.setattr(train_lib, "enable_compile_cache", lambda: None)
    ckpt = str(tmp_path / "ck")
    losses = train_main([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "12", "--clients", "4",
        "--seq", "32", "--batch-per-client", "1", "--unify-every", "6",
        "--ckpt-dir", ckpt, "--ckpt-every", "6", "--log-every", "6",
    ])
    assert np.isfinite(losses).all()
    # resume from step 12 checkpoint
    losses2 = train_main([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "14", "--clients", "4",
        "--seq", "32", "--batch-per-client", "1", "--unify-every", "6",
        "--ckpt-dir", ckpt, "--log-every", "2",
    ])
    assert len(losses2) == 2  # only steps 12->14 ran
