"""Jit'd kernel entry points with automatic backend dispatch.

On TPU the Pallas kernels run compiled (Mosaic); on CPU they run in
``interpret=True`` mode for correctness, and callers that want production
CPU speed use the XLA reference path instead (``impl='xla'``). Any other
backend raises: a Pallas TPU kernel has no compiled form there, and
interpreting it silently would hide which device serves. The engine's
ACK dispatcher (core.ack) selects between dense/sg the way the paper's mode
mux does.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.fused_gnn import BLOCK_F_CANDIDATES  # noqa: F401
from repro.kernels.fused_gnn import fused_gnn_layer as _fused_pallas
from repro.kernels.gat_attention import gat_attention as _gat_pallas
from repro.kernels.scatter_gather import BLOCK_E_CANDIDATES  # noqa: F401
from repro.kernels.scatter_gather import \
    scatter_gather_aggregate as _sg_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas ACK kernels compile for TPU and interpret on CPU only; "
        f"the default backend is {backend!r}. Use impl='xla' there.")


def fused_gnn_layer(*args, impl: str = "pallas", **kw):
    if impl == "xla":
        return ref.fused_gnn_layer_ref(*args, **kw)
    return _fused_pallas(*args, interpret=_interpret(), **kw)


def scatter_gather_aggregate(*args, impl: str = "pallas", **kw):
    if impl == "xla":
        return ref.scatter_gather_aggregate_ref(*args, **kw)
    return _sg_pallas(*args, interpret=_interpret(), **kw)


def gat_attention(*args, impl: str = "pallas", **kw):
    if impl == "xla":
        return ref.gat_attention_ref(*args, **kw)
    return _gat_pallas(*args, interpret=_interpret(), **kw)
