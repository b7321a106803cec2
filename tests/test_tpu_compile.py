"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a `v5e:2x2` topology that
is described, not attached, and refuses what the chip would refuse
(unsupported primitives, too much VMEM, unpartitionable kernels) — what
interpret-mode tests cannot see. Each test asserts the Mosaic kernel is
in the compiled program (`tpu_custom_call`).

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, so every worker collects the
same tests and only the one running this file loads it.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import trace as bench_trace  # noqa: E402
from repro.kernels.gossip.gossip import (  # noqa: E402
    gossip_drain_pallas, gossip_enqueue_pallas, gossip_mix_pallas)
from repro.kernels.gossip.ops import gossip_drain_sharded  # noqa: E402
from repro.kernels.ssd.ssd import ssd_chunk_pallas  # noqa: E402

# paper scale: EMNIST-like MLP (Dflat 146,447 padded to the 512 block),
# N=25 clients padded to 32, ring depth 8 -> J=7 stored broadcasts
J, N_PAD, K_PAD = 7, 32, 146_944


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


_DEF = re.compile(r"%([\w.\-]+) = (\S+) ")


def _traced_calls(text, kernel):
    """Every compiled instruction of the Pallas kernel named `kernel` as a
    device trace names the op: its operands printed with their shapes
    (`compiled.as_text()` prints their names alone)."""
    shapes = dict(_DEF.findall(text))
    for line in text.splitlines():
        if not re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", line):
            continue
        head, call = line.strip().removeprefix("ROOT ").split("custom-call(", 1)
        operands, rest = call.split(")", 1)
        typed = ", ".join(f"{shapes[o.strip()[1:]]} {o.strip()}"
                          for o in operands.split(","))
        yield f"{head}custom-call({typed}){rest}"


def _as_traced(text, kernel):
    """The first compiled instruction of the kernel, as `_traced_calls`."""
    return next(_traced_calls(text, kernel))


@pytest.mark.parametrize("ring_dtype", [jnp.float32, jnp.bfloat16])
def test_drain_compiles_at_paper_scale(one_chip, ring_dtype):
    text = _compiled_text(
        lambda w, p: gossip_drain_pallas(w, p),
        _sds((J, N_PAD, N_PAD), one_chip),
        _sds((J, N_PAD, K_PAD), one_chip, ring_dtype))
    assert "tpu_custom_call" in text


def test_drain_compiles_under_vmap(one_chip):
    """The sweep engine vmaps the drain over seeds."""
    seeds = 8
    text = _compiled_text(
        jax.vmap(lambda w, p: gossip_drain_pallas(w, p)),
        _sds((seeds, J, N_PAD, N_PAD), one_chip),
        _sds((seeds, J, N_PAD, K_PAD), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("clients", [2, 16])
def test_mix_compiles_at_train_width(one_chip, clients):
    """`mix_dense` over the trainer's own update: qwen2-1.5b at its
    published width cut to 2 layers, its 14 bf16 leaves stacked over 2
    clients (the one-chip cell, the VPU body) or 16 (the MXU body). Each
    leaf is mixed where it lies, in one `gossip_mix` call: no f32
    (clients, Dflat) plane, no concatenate, no convert and no copy, and
    `mix_roofline.train`'s pattern still finds every call by its f32
    (N, N) first operand."""
    from repro.configs.base import get_config
    from repro.core import mixing
    from repro.launch.steps import (depth_config, param_specs_abstract,
                                    stack_clients_abstract)

    cfg = depth_config(get_config("qwen2-1.5b"), 2)
    params = param_specs_abstract(cfg)
    dflat = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert dflat > 3e8
    deltas = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, one_chip, x.dtype),
        stack_clients_abstract(params, clients))
    leaves = jax.tree_util.tree_leaves(deltas)
    assert {x.dtype for x in leaves} == {jnp.dtype(jnp.bfloat16)}
    text = _compiled_text(
        lambda q, d: mixing.mix_dense(q, d, use_kernel=True, interpret=False),
        _sds((clients, clients), one_chip), deltas)
    assert f"f32[{clients},{dflat}]" not in text
    for op in ("concatenate", "convert", "copy", "slice"):
        assert not re.search(rf"= \S+ {op}(-start)?\(", text), op
    calls = list(_traced_calls(text, "gossip_mix"))
    assert len(calls) == len(leaves)
    for mix_op in calls:
        assert mix_op.startswith("%gossip_mix"), mix_op[:300]
        assert bench_trace.pallas_call(2).search(mix_op), mix_op[:300]
        assert not bench_trace.pallas_call(3).search(mix_op)
    shapes = {x.shape for x in leaves}
    assert {tuple(map(int, m.group(1).split(","))) for m in re.finditer(
        r"= bf16\[([\d,]+)\]\S* custom-call", text)} == shapes


@pytest.mark.parametrize("shape", [(5, 3, 40, 130), (2, 7, 1), (5, 4_096, 1),
                                   (25, 9, 3_000)])
def test_mix_compiles_odd_leaves(one_chip, shape):
    """bf16 leaves whose lanes are not a multiple of 128, or whose rows
    and lanes both end in a ragged block, compile for the chip on both
    bodies: the MXU's dot per row (N = 5 and 25; 816 rows a block for the
    one-lane leaf) and the VPU's (N = 2), with Q still the first operand
    by which `mix_roofline.train` finds the call."""
    n = shape[0]
    text = _compiled_text(
        lambda q, d: gossip_mix_pallas(q, d),
        _sds((n, n), one_chip), _sds(shape, one_chip, jnp.bfloat16))
    mix_op = _as_traced(text, "gossip_mix")
    assert bench_trace.pallas_call(2).search(mix_op), mix_op[:300]


def test_ssd_compiles(one_chip):
    bh, nc, q, n, p = 16, 8, 128, 128, 64
    text = _compiled_text(
        lambda *a: ssd_chunk_pallas(*a),
        _sds((bh, nc, q, n), one_chip), _sds((bh, nc, q, n), one_chip),
        _sds((bh, nc, q, p), one_chip), _sds((bh, nc, q), one_chip),
        _sds((bh, nc, q), one_chip))
    assert "tpu_custom_call" in text


def test_drain_sharded_compiles_on_four_chips(topo):
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    senders = NamedSharding(mesh, P(None, "data", None))
    text = _compiled_text(
        lambda w, r, s: gossip_drain_sharded(w, r, s, mesh, ("data",),
                                             use_kernel=True,
                                             interpret=False),
        _sds((J, N_PAD, N_PAD), senders),
        _sds((J + 1, N_PAD, K_PAD), senders),
        _sds((J,), NamedSharding(mesh, P()), jnp.int32))
    assert "tpu_custom_call" in text
    # the psum_scatter of the partials; at this size the TPU compiler
    # lowers it as an all-reduce plus a local slice
    assert "reduce-scatter" in text or "all-reduce" in text


def test_kernels_carry_their_names_and_the_readers_find_them(one_chip):
    """Each gossip kernel compiles under its own name, and the drain's and
    the mix's instructions still match the patterns by which
    `drain_roofline.sim` and `mix_roofline.train` find them in a trace."""
    drain = _compiled_text(
        lambda w, p: gossip_drain_pallas(w, p),
        _sds((J, N_PAD, N_PAD), one_chip), _sds((J, N_PAD, K_PAD), one_chip))
    mix = _compiled_text(
        lambda q, d: gossip_mix_pallas(q, d),
        _sds((2, 2), one_chip), _sds((2, 4096), one_chip))
    enqueue = _compiled_text(
        lambda w, p: gossip_enqueue_pallas(w, p),
        _sds((J, N_PAD, N_PAD), one_chip), _sds((N_PAD, 4096), one_chip))
    drain_op, mix_op = _as_traced(drain, "gossip_drain"), _as_traced(mix, "gossip_mix")
    assert _as_traced(enqueue, "gossip_enqueue")
    assert bench_trace.pallas_call(3).search(drain_op), drain_op[:300]
    assert not bench_trace.pallas_call(2).search(drain_op)
    assert bench_trace.pallas_call(2).search(mix_op), mix_op[:300]
    assert not bench_trace.pallas_call(3).search(mix_op)
