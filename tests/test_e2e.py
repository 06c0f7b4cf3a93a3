"""End-to-end behaviour tests for the paper's system."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_reduced, list_archs
from repro.core import (FederatedGNNTrainer, default_strategies,
                        peak_accuracy, time_to_accuracy)
from repro.graphs import make_graph
from repro.launch.steps import input_specs, shape_variant, cache_capacity
from repro.models import lm
from repro.optim import adamw


def test_full_federated_session_matches_paper_shape():
    """One complete FL session: pre-training bootstrap, pull/train/push
    rounds, FedAvg, validation — accuracy rises, phases are populated,
    OptimES reduces communication vs EmbC."""
    g = make_graph("reddit", scale=0.15, seed=5)
    peaks = {"E": [], "OPG": []}
    for seed in (0, 1, 2):
        runs = {}
        for name in ("E", "OPG"):
            tr = FederatedGNNTrainer(g, 3, default_strategies()[name],
                                     batch_size=64, seed=seed)
            stats = tr.train(6)
            runs[name] = (tr, stats)
            peaks[name].append(peak_accuracy(stats))
            accs = [s.accuracy for s in stats]
            assert max(accs[2:]) > accs[0]          # learning happens
        (tr_e, e), (tr_o, o) = runs["E"], runs["OPG"]
        # OPG holds fewer embeddings at the server and ships fewer bytes
        assert o[-1].embeddings_stored < e[-1].embeddings_stored
        assert tr_o.server.log.bytes < tr_e.server.log.bytes
    # peak accuracy stays comparable (within a few points), over trainer
    # seeds: one seed's peak on ~200 test vertices swings by several points
    assert np.mean(peaks["OPG"]) > np.mean(peaks["E"]) - 0.05, peaks


def test_transformer_training_loop_learns():
    from repro.data import synthetic_batches
    cfg = get_reduced("smollm-360m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(5e-3)
    state = opt.init(params)
    step = jax.jit(lm.make_train_step(cfg, opt))
    gen = synthetic_batches(cfg, batch=8, seq=64, seed=0)
    losses = []
    for _ in range(25):
        params, state, m = step(params, state, next(gen))
        losses.append(float(m["loss"]))
    # the Markov structure is learnable: loss must drop meaningfully
    assert min(losses[-3:]) < losses[0] - 0.5, losses


@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_cover_all_archs(shape_name):
    """input_specs (deliverable e.2): ShapeDtypeStruct stand-ins exist for
    every model input of every (arch × shape), no device allocation."""
    for arch in list_archs():
        cfg = get_reduced(arch)   # structure identical to full configs
        from repro.configs import get_config
        full = get_config(arch)
        specs = input_specs(full, SHAPES[shape_name])
        leaves = jax.tree_util.tree_leaves(specs)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        shp = SHAPES[shape_name]
        if shp.kind == "decode":
            assert specs["tokens"].shape == (shp.global_batch, 1)
            assert "cache" in specs
        else:
            assert specs["tokens"].shape == (shp.global_batch, shp.seq_len)
        if full.family == "vlm" and shp.kind != "decode":
            assert specs["vision"].shape[1] == full.vision_tokens
        if full.family == "audio" and shp.kind != "decode":
            assert specs["frames"].shape[1] == full.encoder_seq


def test_long_context_variant_rules():
    """DESIGN §4: long_500k forces SWA for attention archs, leaves SSM
    native, caps decode caches at the window."""
    from repro.configs import get_config
    long = SHAPES["long_500k"]
    dense = shape_variant(get_config("command-r-35b"), long)
    assert dense.sliding_window == 8192
    assert cache_capacity(dense, long) == 8192
    ssm = shape_variant(get_config("mamba2-1.3b"), long)
    assert ssm.sliding_window is None
    hymba = shape_variant(get_config("hymba-1.5b"), long)
    assert hymba.sliding_window == 8192       # its own design window
    d32 = shape_variant(get_config("command-r-35b"), SHAPES["decode_32k"])
    assert d32.sliding_window is None
    assert cache_capacity(d32, SHAPES["decode_32k"]) == 32768


def test_roofline_analytics():
    from benchmarks.roofline import analytic_hbm_bytes, model_flops_per_chip
    # train: 6·N·T/devices
    mf = model_flops_per_chip("smollm-360m", "train_4k", 256)
    from repro.configs import get_config
    n = get_config("smollm-360m").active_param_count()
    assert abs(mf - 6 * n * 4096 * 256 / 256) / mf < 1e-6
    # decode memory: MLA latent cache ≪ equivalent GQA cache
    mla = analytic_hbm_bytes("deepseek-v2-lite-16b", "decode_32k", 256)
    gqa = analytic_hbm_bytes("command-r-35b", "decode_32k", 256)
    assert mla < gqa
    # every (arch × shape) produces finite positive terms
    for arch in list_archs():
        for s in SHAPES:
            v = analytic_hbm_bytes(arch, s, 256)
            assert np.isfinite(v) and v > 0
