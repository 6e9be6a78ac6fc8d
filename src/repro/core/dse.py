"""Design Space Exploration (paper §4.5) adapted to TPU.

The paper's DSE picks, from a DSP budget, (1) N_ALU per ALU, (2) the ACK
array size p_sys, (3) the PE count N_pe — one bitstream for a SET of GNN
models. The TPU analogue picks, from the device spec, the kernel tiling and
batching for ONE compiled kernel family serving every model in the set:

  Step 1 (N_ALU): verify the ALU op set — every aggregate()/update()/
          attention op of every model must map to MXU/VPU primitives.
  Step 2 (p_sys): maximize the fused-kernel feature block BF (multiple of
          the 128-lane MXU width) subject to the worst-case VMEM working
          set over all models, double-buffered.
  Step 3 (N_pe): choose the per-core subgraph tile C_core from the modeled
          per-target latency so a batch of C saturates the chip; across
          chips targets are data-parallel (mesh 'data'/'pod' axes).

Outputs one ``DSEPlan``; ``modeled_utilization`` reports the roofline-style
compute fraction per model under that single plan (Eq. 1's load-balance
argument: ACK gives every kernel the whole chip).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

from repro.core.program import lower, program_alu_ops
from repro.gnn.model import GNNConfig

MXU_LANE = 128

# scalar primitives the TPU's MXU (matmul) + VPU (elementwise) cover —
# the "N_ALU" feasibility vocabulary. The per-model REQUIRED set is no
# longer a hand-kept table: it is derived from the model's lowered
# AckProgram (core.program.program_alu_ops), so a kind registered at
# runtime is admissible with no DSE edit.
TPU_OPS = {"matmul", "add", "relu", "mul", "exp", "max", "leaky_relu",
           "min", "sub", "div"}


@dataclass(frozen=True)
class TPUSpec:
    """One chip's published limits (Google Cloud "TPU v5e" page). Every
    admission check plans against it, so a process that serves on some
    other chip must say so: ``check_device`` refuses a mismatch."""
    name: str = "tpu-v5e"
    device_kind: str = "TPU v5 lite"    # jax Device.device_kind of the chip
    peak_flops: float = 197e12          # bf16
    hbm_bw: float = 819e9               # bytes/s
    vmem_bytes: int = 16 * 2 ** 20      # per-core VMEM budget for the plan
    hbm_bytes: int = 16 * 2 ** 30
    ici_bw: float = 50e9                # per link
    mxu: int = MXU_LANE

    def check_device(self, device) -> None:
        """Raise unless ``device`` is the chip this spec describes."""
        if device.device_kind != self.device_kind:
            raise RuntimeError(
                f"{self.name} spec describes {self.device_kind!r}, but the "
                f"device is {device.device_kind!r} ({device.platform})")


@dataclass
class DSEPlan:
    block_f: int                        # p_sys analogue (MXU tile width)
    c_core: int                         # N_pe analogue (subgraphs/core)
    edge_block: int
    buffer_depth: int                   # double/triple buffering depth
    vmem_used: int
    ops_ok: bool
    per_model: Dict[str, dict] = field(default_factory=dict)


class PlanViolation(ValueError):
    """A model does not fit under the shared DSEPlan."""


def plan_covers(plan: DSEPlan, cfg: GNNConfig,
                spec: TPUSpec = TPUSpec()) -> List[str]:
    """Why ``cfg`` does NOT run under ``plan`` (empty list = covered).

    This is the serving-time admission check: a multi-model deployment
    keeps ONE plan (paper: one bitstream) and every registered model must
    (a) use only ops the plan's ALU set supports and (b) fit the plan's
    buffered VMEM working set at its own receptive field / feature dims.
    """
    reasons: List[str] = []
    try:
        ops = program_alu_ops(cfg)
    except KeyError as e:                 # no registered lowering: the
        reasons.append(str(e).strip('"'))  # message names the fix
    else:
        if not ops <= TPU_OPS:
            reasons.append(f"ops {sorted(ops - TPU_OPS)} unsupported")
    f = max(cfg.f_in, cfg.f_hidden)
    f_pad = f + (-f) % MXU_LANE
    vm = _vmem_layer(cfg.receptive_field, f_pad, plan.block_f,
                     plan.buffer_depth)
    if vm > spec.vmem_bytes:
        reasons.append(
            f"VMEM working set {vm} > budget {spec.vmem_bytes} "
            f"(N={cfg.receptive_field}, f_pad={f_pad}, BF={plan.block_f})")
    return reasons


def validate_models(plan: DSEPlan, models: Sequence[GNNConfig],
                    spec: TPUSpec = TPUSpec()) -> None:
    """Raise PlanViolation unless every model runs under the one plan."""
    if not plan.ops_ok:
        raise PlanViolation("plan was built over an unsupported op set")
    bad = {m.display: plan_covers(plan, m, spec) for m in models}
    bad = {k: v for k, v in bad.items() if v}
    if bad:
        raise PlanViolation(f"models outside the shared plan: {bad}")


def _vmem_layer(n: int, f_in: int, bf: int, depth: int = 2) -> int:
    """Working set of one fused-kernel grid step (fp32 bytes), times the
    pipeline buffering depth for the streamed operands."""
    a = n * n * 4
    h = n * f_in * 4
    w = f_in * bf * 4 * 2          # w_neigh + w_self
    acc = n * bf * 4 * 2           # accumulator + out block
    return depth * (a + h + w) + acc


def layer_costs(cfg: GNNConfig, n: int, f_in: int, f_out: int,
                spec: TPUSpec, *, section: str = "auto") -> dict:
    """Per-layer dense-mode compute/memory model for one subgraph, summed
    over the ops of the model's lowered layer template (per-op FLOP
    models live with the ops in core.program). The feature width is
    tracked through the op stream the same way specialize() does: each
    Transform re-widens to f_out, so later ops (a second GIN MLP, gat's
    attention) are costed at the width they actually see. ``section``
    picks the template explicitly ("layer0" | "inner"); "auto" infers it
    from the widths (layer0 iff f_in != f_out)."""
    from repro.core.program import Transform
    prog = lower(cfg)
    if section == "auto":
        section = "layer0" if f_in != f_out or cfg.n_layers == 1 \
            else "inner"
    ops_seq = prog.layer0 if section == "layer0" else prog.inner
    flops, f_cur = 0.0, f_in
    for op in ops_seq:
        flops += op.dense_flops(n, f_cur, f_out)
        if isinstance(op, Transform):
            f_cur = f_out
    # HBM traffic: H in/out + A once; weights amortized over C subgraphs
    bytes_hbm = 4.0 * (n * f_in + n * f_out + n * n)
    return {"flops": flops, "bytes": bytes_hbm,
            "t_compute": flops / spec.peak_flops,
            "t_memory": bytes_hbm / spec.hbm_bw}


def explore(models: Sequence[GNNConfig], spec: TPUSpec = TPUSpec(),
            buffer_depth: int = 2) -> DSEPlan:
    # Step 1 — op coverage, from each model's lowered instruction stream
    ops_ok = all(program_alu_ops(m) <= TPU_OPS for m in models)
    n_max = max(m.receptive_field for m in models)
    f_max = max(max(m.f_in, m.f_hidden) for m in models)
    f_pad = f_max + (-f_max) % MXU_LANE

    # Step 2 — maximize BF (power-of-two multiple of 128, paper: p_sys=2^k)
    bf = MXU_LANE
    while (_vmem_layer(n_max, f_pad, bf * 2, buffer_depth)
           <= spec.vmem_bytes and bf * 2 <= f_pad):
        bf *= 2

    # Step 3 — per-core subgraph tile: enough grid steps to amortize weight
    # streaming; modeled so device time per batch >= 2x weight-load time.
    per_model = {}
    c_core = 8
    for m in models:
        n = m.receptive_field
        costs = [layer_costs(m, n, m.f_in, m.f_hidden, spec,
                             section="layer0")] + \
            [layer_costs(m, n, m.f_hidden, m.f_hidden, spec,
                         section="inner")] * (m.n_layers - 1)
        t_comp = sum(c["t_compute"] for c in costs)
        t_mem = sum(c["t_memory"] for c in costs)
        w_bytes = 4.0 * (m.f_in * m.f_hidden
                         + (m.n_layers - 1) * m.f_hidden * m.f_hidden)
        t_weights = w_bytes / spec.hbm_bw
        # subgraphs per core so that compute hides one full weight sweep
        need = max(1, int(2 * t_weights / max(t_comp, 1e-12)))
        c_core = max(c_core, min(256, need))
        util = t_comp / max(t_comp, t_mem + t_weights / max(need, 1))
        per_model[m.display] = {
            "t_compute_per_target": t_comp, "t_memory_per_target": t_mem,
            "modeled_util": round(util, 3),
            "bound": "compute" if t_comp >= t_mem else "memory",
        }
    vm = _vmem_layer(n_max, f_pad, bf, buffer_depth)
    return DSEPlan(block_f=bf, c_core=c_core, edge_block=256,
                   buffer_depth=buffer_depth, vmem_used=vm, ops_ok=ops_ok,
                   per_model=per_model)
