"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k [--multi-pod] [--out results/dryrun]

The compiled module is one SPMD partition, so cost_analysis() FLOPs /
bytes and memory_analysis() are *per chip*; collective bytes are summed
from the post-partitioning HLO (output shapes of all-reduce / all-gather
/ reduce-scatter / all-to-all / collective-permute ops).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse   # noqa: E402
import dataclasses  # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, ARCHS, LONG_CONTEXT_OK, canon, \
    get_config, cells  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import steps as St  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.model import DecodeDims  # noqa: E402
from repro.optim import adamw_init  # noqa: E402

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"^\s*%?[\w.\-]+ = (\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*) "
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[\w.\-]*\(", re.M)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    out = {}
    for type_str, kind in _COLL_RE.findall(hlo_text):
        b = _shape_bytes(type_str)
        d = out.setdefault(kind, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    return out


def build_lowered(arch: str, shape_name: str, multi_pod: bool,
                  microbatches: int = 1, remat: str | None = None,
                  fsdp_pod: bool = False, extra_cfg: dict | None = None):
    cfg = get_config(arch)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = St.build_ctx(mesh)
    if fsdp_pod and multi_pod:
        ctx = dataclasses.replace(ctx, fsdp_axes=("pod", "data"))
    model = Model(cfg, ctx=ctx)

    mode = shape["mode"]
    p_shapes, p_shard = St.param_shardings(model, ctx, serving_mode=mode)
    b_shapes, b_shard = St.batch_specs(cfg, shape, ctx)
    if mode in ("prefill", "decode"):
        # serving holds bf16 weights (the fp32 masters live with the
        # trainer): halves weight-gather bytes and per-chip HBM
        p_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if s.dtype == jnp.float32 else s, p_shapes)

    with mesh:
        if mode == "train":
            tcfg = St.TrainConfig(microbatches=microbatches)
            step = St.make_train_step(model, tcfg)
            o_shapes = jax.eval_shape(adamw_init, p_shapes)
            o_shard = type(o_shapes)(
                step=NamedSharding(mesh, P()),
                m=p_shard, v=jax.tree.map(lambda s: s, p_shard))
            fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_shapes, o_shapes, b_shapes)
        elif mode == "prefill":
            step = St.make_prefill_step(model)
            fn = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = fn.lower(p_shapes, b_shapes)
        else:
            step = St.make_decode_step(model)
            dims = DecodeDims(batch=shape["global_batch"],
                              seq=shape["seq_len"])
            c_shapes, c_shard = St.cache_specs(model, dims, ctx)
            pos_shard = NamedSharding(mesh, P())
            fn = jax.jit(step, in_shardings=(
                p_shard, c_shard, b_shard["tokens"], pos_shard),
                donate_argnums=(1,))      # serving loop donates the cache
            lowered = fn.lower(p_shapes, c_shapes,
                               b_shapes["tokens"],
                               jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, mesh, cfg


def _extract(compiled):
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return dict(
        flops=float(cost.get("flops", -1)),
        bytes_accessed=float(cost.get("bytes accessed", -1)),
        peak_bytes=int(getattr(mem, "temp_size_in_bytes", -1)),
        argument_bytes=int(getattr(mem, "argument_size_in_bytes", -1)),
        output_bytes=int(getattr(mem, "output_size_in_bytes", -1)),
        collectives=coll,
        collective_bytes=sum(v["bytes"] for v in coll.values()),
    )


def _scan_reps(cfg) -> int:
    _, n_rep, _ = cfg.pattern()
    return n_rep


# per-arch microbatch tuning (see EXPERIMENTS.md §Perf): fewer
# microbatches -> fewer FSDP weight gathers per step, as long as the
# activation peak still fits 16 GB HBM.
MICROBATCH_DEFAULTS = {"starcoder2_3b": 1, "gemma3_1b": 2}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             microbatches: int = 1, **kw) -> dict:
    """Compile the production step (memory check) plus an unroll=2 variant
    whose static HLO-cost delta gives the per-layer cost, so scan-hidden
    FLOPs/bytes/collective-bytes extrapolate to true per-step totals:

        S(u) = const + u * per_layer   =>   total = k*(S1 + (R-1)*(S2-S1))
    """
    t0 = time.time()
    arch = canon(arch)
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    rec = dict(arch=arch, shape=shape_name,
               mesh="2x16x16" if multi_pod else "16x16", tag=tag)
    if SHAPES[shape_name]["mode"] == "train":
        k = MICROBATCH_DEFAULTS.get(arch, microbatches)
    else:
        k = 1
    try:
        lowered, mesh, cfg = build_lowered(
            arch, shape_name, multi_pod, microbatches=k, **kw)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        m1 = _extract(compiled)
        del compiled, lowered

        # unroll=2 variant for per-layer extrapolation
        kw2 = dict(kw)
        kw2.setdefault("extra_cfg", {})
        kw2["extra_cfg"] = dict(kw2["extra_cfg"] or {}, scan_unroll=2)
        lowered2, _, _ = build_lowered(
            arch, shape_name, multi_pod, microbatches=k, **kw2)
        m2 = _extract(lowered2.compile())
        t3 = time.time()

        n_rep = _scan_reps(get_config(arch))
        def extr(key):
            per_layer = max(m2[key] - m1[key], 0.0)
            return k * (m1[key] + (n_rep - 1) * per_layer)
        coll_total = {}
        for kind in set(m1["collectives"]) | set(m2["collectives"]):
            c1 = m1["collectives"].get(kind, {"count": 0, "bytes": 0})
            c2 = m2["collectives"].get(kind, {"count": 0, "bytes": 0})
            coll_total[kind] = {
                "count": int(k * (c1["count"] + (n_rep - 1) *
                                  max(c2["count"] - c1["count"], 0))),
                "bytes": int(k * (c1["bytes"] + (n_rep - 1) *
                                  max(c2["bytes"] - c1["bytes"], 0)))}
        rec.update(
            ok=True, microbatches=k, scan_reps=n_rep,
            lower_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1),
            unroll2_s=round(t3 - t2, 1),
            flops_per_chip=extr("flops"),
            bytes_accessed_per_chip=extr("bytes_accessed"),
            peak_bytes_per_chip=m1["peak_bytes"],
            argument_bytes_per_chip=m1["argument_bytes"],
            output_bytes_per_chip=m1["output_bytes"],
            collectives=coll_total,
            collective_bytes_per_chip=sum(v["bytes"]
                                          for v in coll_total.values()),
            raw_static=dict(u1=m1, u2={kk: m2[kk] for kk in
                                       ("flops", "bytes_accessed")}),
        )
        print(f"[dryrun] {tag}: OK  compile={rec['compile_s']}s "
              f"flops/chip={rec['flops_per_chip']:.3e} "
              f"peak={rec['peak_bytes_per_chip']/2**30:.2f}GiB "
              f"coll={rec['collective_bytes_per_chip']/2**20:.1f}MiB")
    except Exception as e:  # noqa: BLE001
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {tag}: FAIL {type(e).__name__}: {str(e)[:200]}")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--remat", default=None)
    args = ap.parse_args()

    if args.all:
        todo = [(a, s, mp) for (a, s) in cells()
                for mp in ((False, True) if args.both_meshes
                           else (args.multi_pod,))]
    else:
        assert args.arch and args.shape
        todo = [(args.arch, args.shape, mp)
                for mp in ((False, True) if args.both_meshes
                           else (args.multi_pod,))]

    n_ok = 0
    for arch, shape, mp in todo:
        tag = f"{canon(arch)}__{shape}__{'pod2' if mp else 'pod1'}"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"[dryrun] {tag}: cached OK")
                    n_ok += 1
                    continue
        rec = run_cell(arch, shape, mp, args.out,
                       microbatches=args.microbatches, remat=args.remat)
        n_ok += bool(rec.get("ok"))
    print(f"[dryrun] {n_ok}/{len(todo)} cells OK")


if __name__ == "__main__":
    main()
