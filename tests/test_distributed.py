"""Sharding rules, fedopt bridge, HLO census calibration.

Mesh-dependent tests use AbstractMesh so they run on 1 CPU device without
forcing placeholder devices (the dry-run owns that)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.distributed import sharding as sh
from repro.models import lm
from repro.optim import adafactor, adamw


def fake_mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes)


@pytest.mark.parametrize("arch", ["nemotron-4-340b", "phi3.5-moe-42b-a6.6b",
                                  "mamba2-1.3b", "deepseek-v2-lite-16b"])
def test_param_specs_structure_and_divisibility(arch):
    cfg = get_config(arch)
    mesh = fake_mesh()
    rules = sh.make_rules(mesh, cfg)
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    pspecs = sh.param_specs(rules, pshapes)
    flat_s = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree_util.tree_leaves(pshapes)
    assert len(flat_s) == len(flat_p)
    for spec, leaf in zip(flat_s, flat_p):
        assert len(spec) <= leaf.ndim
        for dim, ax in zip(leaf.shape, list(spec) + [None] * leaf.ndim):
            if ax is None:
                continue
            size = np.prod([mesh.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))])
            assert dim % size == 0, (arch, spec, leaf.shape)


def test_fsdp_thresholds():
    mesh = fake_mesh()
    big = sh.make_rules(mesh, get_config("nemotron-4-340b"))
    small = sh.make_rules(mesh, get_config("smollm-360m"))
    assert big.fsdp and big.seq_parallel
    assert not small.fsdp and not small.seq_parallel


def test_nemotron_param_bytes_fit_hbm():
    """Per-device param+optimizer bytes for the 340B config must fit the
    16 GiB v5e budget under the published sharding rules."""
    cfg = get_config("nemotron-4-340b")
    mesh = fake_mesh()
    rules = sh.make_rules(mesh, cfg)
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    pspecs = sh.param_specs(rules, pshapes)
    total = 0
    for leaf, spec in zip(
            jax.tree_util.tree_leaves(pshapes),
            jax.tree_util.tree_leaves(pspecs,
                                      is_leaf=lambda x: isinstance(x, P))):
        shards = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shards *= mesh.shape[a]
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // shards
    assert total < 4 * 2**30, f"params/device {total/2**30:.2f} GiB"


def test_opt_specs_mirror_params():
    cfg = get_reduced("smollm-360m")
    mesh = fake_mesh()
    rules = sh.make_rules(mesh, cfg)
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    pspecs = sh.param_specs(rules, pshapes)
    for opt in (adamw(1e-3), adafactor(1e-3)):
        oshapes = jax.eval_shape(opt.init, pshapes)
        ospecs = sh.opt_specs(rules, oshapes, pspecs)
        flat_shapes = jax.tree_util.tree_leaves(oshapes)
        flat_specs = jax.tree_util.tree_leaves(
            ospecs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_shapes) == len(flat_specs)
        for leaf, spec in zip(flat_shapes, flat_specs):
            assert len(spec) <= leaf.ndim


def test_batch_and_cache_specs():
    from repro.configs.base import SHAPES
    cfg = get_config("nemotron-4-340b")
    mesh = fake_mesh(multi_pod=True)
    rules = sh.make_rules(mesh, cfg)
    bs = sh.batch_specs(rules, cfg, SHAPES["train_4k"])
    assert bs["tokens"] == P(("pod", "data"), None)
    # long_500k batch=1: never shard a size-1 dim
    bs1 = sh.batch_specs(rules, cfg, SHAPES["long_500k"])
    assert bs1["tokens"][0] is None
    cshapes = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 1024))
    cspecs = sh.cache_specs(rules, cfg, cshapes, 128)
    flat = jax.tree_util.tree_leaves(cspecs,
                                     is_leaf=lambda x: isinstance(x, P))
    assert flat  # exists and parses


# -- fedopt bridge ------------------------------------------------------------

def test_fedopt_round_and_delta_pruning():
    from repro.core.fedopt import FedOptConfig, FederatedLMTrainer
    from repro.data import synthetic_batches
    cfg = get_reduced("smollm-360m")
    fed = FedOptConfig(num_silos=2, local_steps=2, delta_topk_frac=0.2)
    tr = FederatedLMTrainer(cfg, adamw(1e-3), fed)
    gens = [synthetic_batches(cfg, batch=2, seq=16, seed=s)
            for s in range(2)]
    steps = [[next(g) for _ in range(2)] for g in gens]
    batches = jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                     *[jax.tree_util.tree_map(
                                         lambda *y: jnp.stack(y), *s)
                                       for s in steps])
    m = tr.round(batches)
    assert np.isfinite(m["loss"])
    assert tr.comm_bytes_per_round() < 0.25 * sum(
        p.size * p.dtype.itemsize
        for p in jax.tree_util.tree_leaves(tr.anchor))


def test_fedopt_stale_aggregation_defers_one_round():
    from repro.core.fedopt import FedOptConfig, FederatedLMTrainer
    from repro.data import synthetic_batches
    cfg = get_reduced("smollm-360m")
    fed = FedOptConfig(num_silos=2, local_steps=1, stale_aggregation=True)
    tr = FederatedLMTrainer(cfg, adamw(1e-3), fed)
    anchor0 = jax.tree_util.tree_map(jnp.copy, tr.anchor)
    gen = synthetic_batches(cfg, batch=2, seq=16, seed=0)
    b = next(gen)
    batches = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (2, 1) + x.shape), b)
    tr.round(batches)
    # first round: nothing applied yet (delta pending)
    d0 = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(anchor0),
        jax.tree_util.tree_leaves(tr.anchor)))
    assert d0 == 0.0
    tr.round(batches)
    d1 = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(anchor0),
        jax.tree_util.tree_leaves(tr.anchor)))
    assert d1 > 0.0


# -- HLO census calibration -----------------------------------------------------

def test_census_counts_scan_trips():
    from repro.launch.hlo_census import census
    x = jnp.ones((8, 16))
    w = jnp.ones((16, 16))

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    txt = jax.jit(f).lower(x, w).compile().as_text()
    cen = census(txt)
    expected = 2 * 8 * 16 * 16 * 5
    assert abs(cen["flops"] - expected) / expected < 0.05, cen["flops"]


def test_census_matches_cost_analysis_loop_free():
    from repro.launch.hlo_census import census
    x = jnp.ones((32, 64))
    w = jnp.ones((64, 128))
    c = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
    cen = census(c.as_text())
    ca = c.cost_analysis()["flops"]
    assert abs(cen["flops"] - ca) / ca < 0.05
