"""Ahead-of-time compiles for a described TPU v5e chip.

The TPU compiler is installed with jax, and compiles for a chip that is
described rather than attached, so these tests need no accelerator.
They catch what interpret-mode kernel tests cannot: a kernel the Pallas
TPU lowering refuses, or a program that does not fit the chip's 16 GiB.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import exchange_fused, ops, quantize

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("kernel", ["quantize_padded", "dequantize_padded"])
def test_quantize_kernels_compile_to_mosaic(one_chip, kernel):
    rows, hp = quantize.BUCKET_CAP, quantize.LANE
    if kernel == "quantize_padded":
        lowered = quantize.quantize_padded.lower(
            _spec(one_chip, (rows, hp), jnp.float32), interpret=False)
    else:
        lowered = quantize.dequantize_padded.lower(
            _spec(one_chip, (rows, hp), jnp.int8),
            _spec(one_chip, (rows, 1), jnp.float32), interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("op", ["gather", "scatter_set", "scatter_add"])
def test_exchange_xla_twins_fit_one_chip(one_chip, op):
    """The device-table exchange path on a 1M-row fp32 table (512 MiB),
    one full row bucket per call."""
    table = _spec(one_chip, (2**20, quantize.LANE), jnp.float32)
    idx = _spec(one_chip, (quantize.BUCKET_CAP, 1), jnp.int32)
    if op == "gather":
        lowered = exchange_fused._gather_quantize_padded_jnp.lower(table, idx)
    else:
        lowered = exchange_fused._dequant_scatter_padded_jnp.lower(
            table, idx,
            _spec(one_chip, (quantize.BUCKET_CAP, quantize.LANE), jnp.int8),
            _spec(one_chip, (quantize.BUCKET_CAP, 1), jnp.float32),
            accumulate=op == "scatter_add")
    assert _device_bytes(lowered.compile()) < V5E_HBM_BYTES


def test_gnn_train_step_compiles(one_chip):
    """The trainer's jitted step, at the shapes a CPU-built trainer
    feeds it (GraphConv, 3 layers, hidden 32, batch 64)."""
    from repro.core import FederatedGNNTrainer, default_strategies
    from repro.graphs import make_graph
    from repro.models import gnn

    tr = FederatedGNNTrainer(make_graph("reddit", scale=0.05, seed=0), 2,
                             default_strategies()["OP"], seed=0)
    batch = gnn.blocks_to_arrays(next(iter(tr.samplers[0].epoch())))
    args = (tr.params, tr.opt.init(tr.params), batch, tr.feats[0],
            tr._caches[0], tr.labels[0])
    specs = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, np.shape(x), x.dtype), args)
    compiled = tr._train_step.lower(*specs).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_epoch_split_compiles(one_chip):
    """The device split of one packed client epoch (batch 64, fanout 5,
    3 layers), the program that hands the step its inputs."""
    from repro.core import FederatedGNNTrainer, default_strategies
    from repro.graphs import make_graph
    from repro.models import gnn

    tr = FederatedGNNTrainer(make_graph("reddit", scale=0.05, seed=0), 2,
                             default_strategies()["OP"], seed=0)
    packed, layout = gnn.pack_epoch(list(tr.samplers[0].epoch()))
    compiled = gnn._split_epoch.lower(
        _spec(one_chip, packed.shape, jnp.int32), layout=layout).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_auto_dispatch_reaches_only_compiling_kernels(monkeypatch):
    """On a TPU backend, ``use_pallas="auto"`` picks the Pallas body only
    for the kernels compiled above; ``use_pallas=True`` still forces it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    kernels = ["quantize_int8", "dequantize_int8", "gather_quantize",
               "dequant_scatter", "gnn_aggregate", "dequant_aggregate",
               "topk_mask", "swa_attention_decode"]
    picked = {k for k in kernels if ops._resolve("auto", k)[0]}
    assert picked == {"quantize_int8", "dequantize_int8"}
    assert all(ops._resolve(True, k) == (True, False) for k in kernels)
