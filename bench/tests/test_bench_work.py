"""Work counts against hand counts at small sizes."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common, work  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402


def test_bench_contraction_rows_by_hand():
    w = np.zeros((3, 4, 4))  # J=3 buckets, 4 senders x 4 receivers
    w[0, 1, 2] = w[0, 1, 3] = 0.5  # one payload row feeds two receivers
    w[2, 0, 2] = 0.5  # another bucket's row, same receiver 2
    # payload rows: (0, 1) and (2, 0); receiving rows: 2 and 3
    assert work.contraction_rows(w) == (2, 2)
    # a batch of two drains: counts add
    assert work.contraction_rows(np.stack([w, w])) == (4, 4)
    q = np.array([[0, 1.0], [0, 0]])  # a mix: client 0 sends to client 1
    assert work.contraction_rows(q) == (1, 1)
    assert work.contraction_bytes(2, 2, 10) == 4 * 10 * 4
    assert work.contraction_flops(3, 10) == 60


def test_bench_param_counts_by_hand():
    cfg, _ = common.config_files("qwen2-1.5b-d2")
    assert work.qwen2_params(cfg) == cfg["params_per_client"] == 326_970_880
    assert work.train_flops(10, 3) == 180 and work.eval_flops(10, 3) == 60


def test_bench_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9
    assert work.least_time_s(197e12, 819e9, p.bf16_flops,
                             p.hbm_bytes_per_s) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_bench_drain_counts_from_draws():
    """`Protocol.counts` against a loop over windows and ages."""
    import copy

    import jax

    common.add_program_path()
    cfg, model = common.config_files("emnist-n25")
    cfg = copy.deepcopy(cfg)
    cfg["num_clients"] = 5
    cfg["data"].update(samples_per_client=8, test_samples=4, pool_samples=50)
    traffic = dict(common.traffic_file("windowed-d8"), windows_per_job=30,
                   eval_every=10, max_delay_windows=4, lambda_tx=2.0,
                   lambda_grad=1.0)
    sim = common.entry_module("simulate")
    data, test = model.make_data(jax.random.PRNGKey(0), cfg)
    p0 = model.init_params(jax.random.PRNGKey(1), cfg)
    ref = sim.Protocol(cfg, traffic, model, data, test, p0)
    draws = ref.draws(jax.random.PRNGKey(2))
    got = ref.counts(draws)
    grad, _, _, accept, delay = (np.asarray(x) for x in draws)
    read = written = links = 0
    for w in range(30):
        rows, recv = set(), set()
        for a in range(1, 4):
            if w - a < 0:
                continue
            for i, j in zip(*np.nonzero(accept[w - a] & (delay[w - a] == a))):
                rows.add((a, i))
                recv.add(j)
                links += 1
        read, written = read + len(rows), written + len(recv)
    assert links > 0
    assert got["links"] == links
    assert (got["drain_rows_read"], got["drain_rows_written"]) == (read, written)
    assert got["grads"] == int(grad.sum())
    assert got["eval_samples"] == 3 * 5 * 4
