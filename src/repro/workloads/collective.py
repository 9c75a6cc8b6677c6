"""Collective-derived workloads: LLM training traffic on chiplets.

`collective_workload` compiles a sharded model training step into a
phase schedule (DESIGN.md §9):

  1. `step_collective_ops` derives the step's ordered collectives (FSDP
     all-gather, per-layer TP all-reduces, MoE all-to-all, gradient
     reduce-scatter) and their bytes from an architecture's sizes
     (`ARCHS`, looked up by `arch_sizes`) and a logical mesh shape;
  2. `core.collectives.mesh_axis_groups` maps the mesh onto the chiplet
     placement (model groups physically contiguous) and
     `collective_flow` turns each collective into an [N, N] byte-flow
     matrix over those groups;
  3. ops sharing a phase are summed, phase durations are split
     proportionally to phase bytes (time ~ data over fixed wires), and
     intensities carry each phase's per-source demand *rate* so heavy
     concentrated phases drive the network harder than diffuse ones.

The headline question "how does FoldedHexaTorus hold up under
qwen3-style training traffic on glass vs organic?" becomes one batched
`run_workloads` call (benchmarks/workload_bench.py).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.collectives import collective_flow, mesh_axis_groups
from repro.core.topology import Topology

from .schedule import Phase, Schedule, Workload


# =====================================================================
# published architecture sizes
# =====================================================================

@dataclasses.dataclass(frozen=True)
class ArchSizes:
    """The size fields of a model architecture that its training step's
    collectives depend on.  head_dim 0 means d_model // n_heads;
    moe_every k puts experts in every k-th layer (with n_experts > 0)."""
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1


#: The published configurations, keyed by name with `-` and `.` as `_`.
ARCHS: dict[str, ArchSizes] = {
    # hf:Qwen/Qwen3-8B family
    "qwen3_1_7b": ArchSizes(
        name="qwen3-1.7b", n_layers=28, d_model=2048, n_heads=16,
        n_kv_heads=8, head_dim=128, d_ff=6144, vocab=151936),
    # hf:google/gemma-3-1b-pt
    "gemma3_1b": ArchSizes(
        name="gemma3-1b", n_layers=26, d_model=1152, n_heads=4,
        n_kv_heads=1, head_dim=256, d_ff=6912, vocab=262144),
    # arXiv:2402.19173
    "starcoder2_3b": ArchSizes(
        name="starcoder2-3b", n_layers=30, d_model=3072, n_heads=24,
        n_kv_heads=2, head_dim=128, d_ff=12288, vocab=49152),
    # hf:openbmb/MiniCPM3-4B
    "minicpm3_4b": ArchSizes(
        name="minicpm3-4b", n_layers=62, d_model=2560, n_heads=40,
        n_kv_heads=40, head_dim=64, d_ff=6400, vocab=73448),
    # arXiv:2308.11596 (12L encoder + 12L decoder; the decoder is sized)
    "seamless_m4t_medium": ArchSizes(
        name="seamless-m4t-medium", n_layers=12, d_model=1024, n_heads=16,
        n_kv_heads=16, head_dim=64, d_ff=4096, vocab=256206),
    # hf:Qwen/Qwen3-30B-A3B family
    "qwen3_moe_235b_a22b": ArchSizes(
        name="qwen3-moe-235b-a22b", n_layers=94, d_model=4096, n_heads=64,
        n_kv_heads=4, head_dim=128, d_ff=1536, vocab=151936,
        n_experts=128, top_k=8, moe_every=1),
    # hf:xai-org/grok-1
    "grok_1_314b": ArchSizes(
        name="grok-1-314b", n_layers=64, d_model=6144, n_heads=48,
        n_kv_heads=8, head_dim=128, d_ff=32768, vocab=131072,
        n_experts=8, top_k=2, moe_every=1),
    # arXiv:2405.09818
    "chameleon_34b": ArchSizes(
        name="chameleon-34b", n_layers=48, d_model=8192, n_heads=64,
        n_kv_heads=8, head_dim=128, d_ff=22016, vocab=65536),
    # arXiv:2403.19887
    "jamba_v0_1_52b": ArchSizes(
        name="jamba-v0.1-52b", n_layers=32, d_model=4096, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, vocab=65536,
        n_experts=16, top_k=2, moe_every=2),
    # arXiv:2405.21060
    "mamba2_1_3b": ArchSizes(
        name="mamba2-1.3b", n_layers=48, d_model=2048, n_heads=1,
        n_kv_heads=1, head_dim=64, d_ff=0, vocab=50280),
}


def arch_sizes(arch: str) -> ArchSizes:
    """The published sizes of `arch`, by name (`qwen3-1.7b`) or key
    (`qwen3_1_7b`)."""
    try:
        return ARCHS[arch.replace("-", "_").replace(".", "_")]
    except KeyError:
        raise KeyError(f"unknown architecture {arch!r}; "
                       f"known: {sorted(ARCHS)}") from None


# =====================================================================
# collective plan of a sharded training step
# =====================================================================

@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective a sharded train step issues, as traffic demand.

    phase groups ops that overlap in time (the workload engine turns
    each phase into one flow matrix); axis names the mesh axis whose
    groups communicate; bytes_per_chip is the payload each participant
    contributes.
    """
    phase: str                  # fsdp_gather | fwd_tp | moe_a2a | ...
    kind: str                   # all_reduce | all_gather | ...
    axis: str                   # mesh axis ("data" | "model")
    bytes_per_chip: float


def step_collective_ops(config, mesh_shape: dict, seq_len: int = 2048,
                        global_batch: int = 32, dtype_bytes: int = 2,
                        ) -> list[CollectiveOp]:
    """The ordered collectives of one training step with tensor axes
    sharded over "model" and ZeRO-3 weights (their "embed" dim) over
    "data", sized from the architecture alone: per step
      1. all-gather the data-sharded weights        (fsdp_gather, data)
      2. 2 activation all-reduces per layer forward (fwd_tp, model)
      3. MoE token all-to-all, if experts exist     (moe_a2a, model)
      4. 2 activation all-reduces per layer backward (bwd_tp, model)
      5. reduce-scatter the gradients               (grad_reduce, data)
    `config` is duck-typed (any object with `ArchSizes`' fields).
    """
    tm = int(mesh_shape.get("model", 1))
    dm = int(mesh_shape.get("data", 1))
    b_local = max(global_batch // max(dm, 1), 1)
    d = config.d_model
    hd = config.head_dim or d // config.n_heads
    attn = d * config.n_heads * hd + 2 * d * config.n_kv_heads * hd \
        + config.n_heads * hd * d
    dense_mlp = 3 * d * config.d_ff
    n_moe = config.n_layers // max(config.moe_every, 1) \
        if config.n_experts else 0
    mlp = (config.n_layers - n_moe) * dense_mlp \
        + n_moe * config.n_experts * dense_mlp
    params_tp = (config.n_layers * attn + mlp + 2 * config.vocab * d) / tm
    act = float(b_local) * seq_len * d * dtype_bytes

    # bytes_per_chip is always the FULL buffer size per participant;
    # ring-schedule (k-1)/k factors are applied downstream by
    # `collectives.collective_flow`, matching IciModel.collective_time_s
    ops: list[CollectiveOp] = []
    params_bytes = params_tp * dtype_bytes
    if dm > 1:
        ops.append(CollectiveOp("fsdp_gather", "all_gather", "data",
                                params_bytes))
    if tm > 1:
        ops.append(CollectiveOp("fwd_tp", "all_reduce", "model",
                                2 * config.n_layers * act))
        if n_moe:
            ops.append(CollectiveOp("moe_a2a", "all_to_all", "model",
                                    n_moe * act * max(config.top_k, 1)))
        ops.append(CollectiveOp("bwd_tp", "all_reduce", "model",
                                2 * config.n_layers * act))
    if dm > 1:
        ops.append(CollectiveOp("grad_reduce", "reduce_scatter", "data",
                                params_bytes))
    if not ops:   # unsharded mesh: the step still syncs grads pairwise
        ops.append(CollectiveOp("grad_reduce", "all_reduce", "data",
                                params_bytes))
    return ops


# =====================================================================
# phase schedules
# =====================================================================

def default_mesh_shape(n: int, model_parallel: int = 0) -> dict:
    """{"data": D, "model": T} with T*D == N; prefers TP degree 8/4/2."""
    if model_parallel:
        if n % model_parallel:
            raise ValueError(f"model_parallel {model_parallel} does not "
                             f"divide N={n}")
        return {"data": n // model_parallel, "model": model_parallel}
    for tm in (8, 4, 2):
        if n % tm == 0 and n // tm >= 2:
            return {"data": n // tm, "model": tm}
    return {"data": n, "model": 1}


def collective_workload(config, topo: Topology, *, mesh_shape: dict = None,
                        seq_len: int = 2048, global_batch: int = 0,
                        step_cycles: int = 1000, min_phase: int = 50,
                        dtype_bytes: int = 2) -> Schedule:
    """Phase schedule of one sharded training step of `config` on `topo`.

    config: an `ArchSizes` (or any object with its fields);
    mesh_shape defaults to TP-8/4/2 x FSDP over the remaining chiplets;
    global_batch defaults to 4 sequences per data shard; step_cycles is
    the replayed step's length, split across phases by bytes moved.
    """
    mesh_shape = mesh_shape or default_mesh_shape(topo.n)
    dm = int(mesh_shape.get("data", 1))
    global_batch = global_batch or 4 * dm
    ops = step_collective_ops(config, mesh_shape, seq_len=seq_len,
                              global_batch=global_batch,
                              dtype_bytes=dtype_bytes)
    # phase -> flow matrix + payload bytes, in op order
    flows: dict[str, np.ndarray] = {}
    payload: dict[str, float] = {}
    for op in ops:
        groups = mesh_axis_groups(topo, mesh_shape, op.axis)
        f = collective_flow(topo.n, op.kind, groups, op.bytes_per_chip)
        if f.sum() <= 0:        # degenerate axis (groups of 1): skip
            continue
        flows[op.phase] = flows.get(op.phase, 0) + f
        payload[op.phase] = payload.get(op.phase, 0.0) + op.bytes_per_chip
    if not flows:
        raise ValueError("sharded step issues no collectives on this mesh")

    total = sum(payload.values())
    durations = {p: max(min_phase, int(round(step_cycles * b / total)))
                 for p, b in payload.items()}
    # per-source demand rate: heaviest row of the phase's flow matrix,
    # spread over the phase's duration; normalized so the peak phase
    # drives intensity 1.0 (the rate sweep scales everything together)
    rates = {p: flows[p].sum(axis=1).max() / durations[p] for p in flows}
    peak = max(rates.values())
    phases = [Phase(traffic=flows[p], intensity=rates[p] / peak,
                    duration=durations[p], label=p) for p in flows]
    return Schedule(phases, name=f"collective:{config.name}")


def collective_workloads(configs, **kw) -> list[Workload]:
    """Wrap architecture sizes (`ArchSizes`) for the sweep engine."""
    return [Workload(name=f"collective:{c.name}",
                     build=lambda topo, c=c: collective_workload(
                         c, topo, **kw))
            for c in configs]
