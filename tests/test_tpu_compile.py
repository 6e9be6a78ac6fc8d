"""Mosaic compile checks for the ACK kernels at serving widths.

Every test compiles for a described (not attached) TPU v5e and asserts
that the compiled text holds the Pallas kernel (``tpu_custom_call``).
Interpret mode accepts block shapes that Mosaic refuses — a (1, N) block
of a [C, N] array, a 64-wide lane block — so these tests guard what the
interpret-mode kernel tests cannot. Nothing runs: only shapes are given.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU compiler library, and the test workers
all import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.program import execute, lower_and_specialize
from repro.gnn.model import GNNConfig, init_gnn
from repro.kernels import ops
from repro.kernels.fused_gnn import BLOCK_F_CANDIDATES, fused_gnn_layer
from repro.kernels.gat_attention import gat_attention
from repro.kernels.scatter_gather import (BLOCK_E_CANDIDATES,
                                          scatter_gather_aggregate)

C, F_IN, F_OUT = 64, 512, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", (128, 256))
@pytest.mark.parametrize("form", ("gcn", "sage"))
def test_fused_gnn_layer(one_chip, form, n):
    s = functools.partial(_shape, one_chip)
    w_self = s((F_IN, F_OUT)) if form == "sage" else None
    text = _compiled_text(
        fused_gnn_layer, s((C, n, n)), s((C, n, F_IN)), s((F_IN, F_OUT)), w_self,
        s((F_OUT,)), s((C, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_f", BLOCK_F_CANDIDATES)
def test_fused_gnn_layer_every_block_f(one_chip, block_f):
    """The autotuner tries every candidate on the chip: each must be one
    Mosaic accepts."""
    s = functools.partial(_shape, one_chip)
    f_out = max(BLOCK_F_CANDIDATES)
    text = _compiled_text(
        functools.partial(fused_gnn_layer, block_f=block_f),
        s((C, 128, 128)), s((C, 128, F_IN)), s((F_IN, f_out)), None,
        s((f_out,)), s((C, 128)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_e", BLOCK_E_CANDIDATES)
def test_scatter_gather_aggregate(one_chip, block_e):
    s = functools.partial(_shape, one_chip)
    e = 1024
    text = _compiled_text(
        functools.partial(scatter_gather_aggregate, block_e=block_e),
        s((C, e), jnp.int32), s((C, e), jnp.int32), s((C, e)),
        s((C, 128, F_IN)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads", (4, 8))
def test_gat_attention(one_chip, heads):
    s = functools.partial(_shape, one_chip)
    n = 128
    text = _compiled_text(
        functools.partial(gat_attention, n_heads=heads),
        s((C, n, F_OUT)), s((C, n, heads)), s((C, n, heads)),
        s((C, n, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ("dense", "sg"))
def test_whole_pallas_gcn_program(one_chip, monkeypatch, mode):
    """One jitted impl="pallas" GCN program (L=3, N=128) as the engine
    builds it, from shapes only. The kernel entry points would pick
    interpret mode on this CPU host, so the test steers them to Mosaic."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, e = 128, 1024
    cfg = GNNConfig(kind="gcn", n_layers=3, receptive_field=n, f_in=F_IN,
                    f_hidden=F_OUT)
    prog, _ = lower_and_specialize(cfg, force=mode)
    s = functools.partial(_shape, one_chip)
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: init_gnn(cfg, jax.random.PRNGKey(0))))
    batch = {"feats": s((C, n, F_IN)), "mask": s((C, n)),
             "adj": s((C, n, n)), "adj_mean": s((C, n, n)),
             "edge_src": s((C, e), jnp.int32),
             "edge_dst": s((C, e), jnp.int32), "edge_w": s((C, e)),
             "edge_w_mean": s((C, e)), "self_w": s((C, n))}

    def forward(p, b):
        return execute(prog, p, b, impl="pallas")[0]

    assert "tpu_custom_call" in _compiled_text(forward, params, batch)
