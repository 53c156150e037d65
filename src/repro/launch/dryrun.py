import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the REAL jit root (train_step for train shapes,
prefill/decode serve steps for the others) against sharded
ShapeDtypeStructs — no arrays are ever allocated — then records:

  * ``compiled.memory_analysis()``  -> bytes/device (does it fit 16 GB?)
  * ``compiled.cost_analysis()``    -> per-device HLO FLOPs & bytes
  * the collective schedule parsed from the compiled HLO
    (all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute result bytes, per device)
  * the three roofline terms vs TPU v5e constants (197 TF bf16,
    819 GB/s HBM, ~50 GB/s/link ICI), MODEL_FLOPS, and the useful-compute
    ratio.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional, Tuple


import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_arch
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import params as pm
from repro.models.sharding import DEFAULT_RULES, ShardingCtx, use_ctx
from repro.models.transformer import init_cache, model_specs
from repro.train.data import specs_for_shape
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.steps import (make_decode_step, make_prefill_step,
                               make_train_step)

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link
HBM_BYTES = 16e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Sum result bytes per collective kind from (post-SPMD) HLO text."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo.splitlines():
        line = line.strip()
        for kind in COLLECTIVES:
            # match "= <shapes> kind(" but not "-start/-done" duplicates
            m = re.search(rf"= (.*?) {kind}(-start)?\(", line)
            if m:
                out[kind]["count"] += 1
                out[kind]["bytes"] += _shape_bytes(m.group(1))
                break
    return out


def input_specs(arch: ArchConfig, shape: ShapeConfig,
                ctx: Optional[ShardingCtx] = None) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (weak-type-correct, shardable, no device allocation)."""
    shapes = specs_for_shape(arch, shape)

    def sds(shp, dtype, logical):
        sh = ctx.sharding(logical) if ctx is not None else None
        return jax.ShapeDtypeStruct(shp, dtype, sharding=sh)

    out = {}
    for name, shp in shapes.items():
        if name == "embeds":
            out[name] = sds(shp, jnp.float32, ("batch", "seq", "embed"))
        else:
            out[name] = sds(shp, jnp.int32, ("batch", "seq")[:len(shp)])
    return out


def _flops_lower(arch: ArchConfig, shape: ShapeConfig, n_layers: int,
                 donate: bool = False, serve_dtype=None
                 ) -> Tuple[float, float]:
    """(flops, bytes) of one step at ``n_layers``, from an UNROLLED,
    unpartitioned lowering — XLA's cost model counts lax.scan bodies once,
    so the scanned production graph undercounts by ~L; the unrolled small-L
    lowering is exact and extrapolates linearly in L.

    Decode cells RETURN the updated cache (the copy/in-place distinction is
    the dominant byte term; ``donate`` aliases it like the real serving
    loop does)."""
    import dataclasses as dc

    from repro.models.transformer import forward as fwd
    cfg = dc.replace(arch, n_layers=n_layers)
    specs = model_specs(cfg)
    params = pm.shape_structs(specs, None)
    if serve_dtype is not None and shape.kind != "train":
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, serve_dtype), params)
    ins = input_specs(cfg, shape, None)

    if shape.kind == "train":
        opt = jax.eval_shape(init_opt_state, params)
        fn = make_train_step(cfg, OptConfig(), unroll=True)
        jk = {"donate_argnums": (0, 1)} if donate else {}
        lowered = jax.jit(fn, **jk).lower(params, opt, dict(ins))
    elif shape.kind == "prefill":
        def fn(p, t, e):
            logits, _ = fwd(cfg, p, t, embeds=e, remat=False,
                            return_cache=False, unroll=True)
            return logits[:, -1]
        lowered = jax.jit(fn).lower(params, ins["tokens"],
                                    ins.get("embeds"))
    else:
        cache = pm.shape_structs(
            init_cache(cfg, shape.global_batch, shape.seq_len), None)
        def fn(p, c, t, i):
            logits, nc = fwd(cfg, p, t, cache=c, cache_index=i,
                             remat=False, return_cache=True, unroll=True)
            return jnp.argmax(logits[:, -1], -1), nc
        jk = {"donate_argnums": (1,)} if donate else {}
        lowered = jax.jit(fn, **jk).lower(params, cache, ins["tokens"],
                                          jax.ShapeDtypeStruct((),
                                                               jnp.int32))
    # compile (single device, unpartitioned): post-fusion byte counts —
    # the unoptimized module would overcount HBM traffic 5-20x.
    #
    # KNOWN PROXY ARTIFACTS (EXPERIMENTS.md §Perf): (a) the CPU backend
    # upcasts bf16 compute to f32, inflating byte counts ~2x on
    # KV-cache-heavy graphs and inverting bf16-vs-f32 comparisons; (b) the
    # cost model charges dynamic-update-slice its FULL buffer, so
    # donation/in-place updates show no byte reduction.  Iterations on
    # those axes are therefore evaluated with clearly-labelled analytic
    # TPU projections alongside this proxy.
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)))


_EST_CACHE: Dict[Tuple, Dict[str, float]] = {}


def estimate_global_cost(arch: ArchConfig, shape: ShapeConfig,
                         donate: bool = False, serve_dtype=None
                         ) -> Dict[str, float]:
    """Extrapolated whole-step global FLOPs/bytes at full depth.
    Mesh-independent (global numbers) -> cached per (arch, shape, variant)."""
    key = (arch.name, shape.name, donate, str(serve_dtype),
           arch.moe.capacity_factor if arch.moe else None)
    if key in _EST_CACHE:
        return _EST_CACHE[key]
    k = arch.moe.first_k_dense if arch.moe else 0
    f2, b2 = _flops_lower(arch, shape, k + 2, donate, serve_dtype)
    f4, b4 = _flops_lower(arch, shape, k + 4, donate, serve_dtype)
    body_f, body_b = (f4 - f2) / 2.0, (b4 - b2) / 2.0
    n_body = arch.n_layers - k - 2
    out = {"flops": f2 + n_body * body_f,
           "bytes": b2 + n_body * body_b,
           "per_layer_flops": body_f}
    _EST_CACHE[key] = out
    return out


def _cell_abstract(arch: ArchConfig, shape: ShapeConfig, ctx: ShardingCtx,
                   serve_dtype=None, accum: int = 1) -> Tuple:
    """(jit-able fn, example args as sharded ShapeDtypeStructs)."""
    specs = model_specs(arch)
    params = pm.shape_structs(specs, ctx)
    if serve_dtype is not None and shape.kind != "train":
        # inference-weight quantization (perf variant): params streamed in
        # bf16 — halves the parameter-read term of serving cells
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, serve_dtype,
                                           sharding=s.sharding), params)
    ins = input_specs(arch, shape, ctx)

    if shape.kind == "train":
        opt_specs = jax.eval_shape(init_opt_state, params)

        def shard_like(opt_leaf, path_hint=None):
            return opt_leaf
        # moments share the param shardings; step is replicated
        po = pm.shardings(specs, ctx)
        opt = {"m": jax.tree.map(
                   lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                      sharding=sh),
                   opt_specs["m"], po),
               "v": jax.tree.map(
                   lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                      sharding=sh),
                   opt_specs["v"], po),
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
        fn = make_train_step(arch, OptConfig(), accum=accum)
        batch = dict(ins)
        return fn, (params, opt, batch)

    if shape.kind == "prefill":
        fn = make_prefill_step(arch, shape.seq_len)
        return fn, (params, ins["tokens"], ins.get("embeds"))

    # decode: serve_step over a full-length cache
    cache_specs = init_cache(arch, shape.global_batch, shape.seq_len)
    cache = pm.shape_structs(cache_specs, ctx)
    fn = make_decode_step(arch)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (params, cache, ins["tokens"], index)


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             donate: bool = False, serve_bf16: bool = False,
             capacity_factor: float = None, accum: int = 1) -> Dict:
    arch = get_arch(arch_name)
    if capacity_factor is not None and arch.moe is not None:
        import dataclasses as dc
        arch = dc.replace(arch, moe=dc.replace(
            arch.moe, capacity_factor=capacity_factor))
    shape = SHAPES[shape_name]
    rec: Dict = {"arch": arch_name, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": shape.kind,
                 "variant": dict(donate=donate, serve_bf16=serve_bf16,
                                 capacity_factor=capacity_factor,
                                 accum=accum)}
    if not arch.supports_shape(shape_name):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: 512K dense decode is "
                         "O(L^2) with no architectural mitigation "
                         "(DESIGN.md §3)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = dict(DEFAULT_RULES)
    dp = 32 if multi_pod else 16
    if shape.global_batch % dp != 0:
        # long_500k (batch=1): batch cannot split the data axis — replicate
        # it and spread the half-megatoken context over BOTH mesh axes
        rules["batch"] = None
        rules["kv_seq"] = ("pod", "data", "model") if multi_pod \
            else ("data", "model")
    ctx = ShardingCtx(mesh, rules)

    t0 = time.perf_counter()
    with use_ctx(mesh, rules):
        fn, args = _cell_abstract(
            arch, shape, ctx,
            serve_dtype=jnp.bfloat16 if serve_bf16 else None, accum=accum)
        jit_kwargs = {}
        if donate:
            if shape.kind == "train":
                jit_kwargs["donate_argnums"] = (0, 1)   # params, opt state
            elif shape.kind == "decode":
                jit_kwargs["donate_argnums"] = (1,)     # the KV/SSM cache
        with mesh:
            lowered = jax.jit(fn, **jit_kwargs).lower(*args)
            t_lower = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    print(mem)                                   # proves it fits
    cost = compiled.cost_analysis()
    print({k: cost.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    chips = 512 if multi_pod else 256
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_bytes_dev = float(sum(v["bytes"] for v in coll.values()))

    # whole-step global FLOPs/bytes from the unrolled estimator (the
    # compiled per-device numbers undercount lax.scan bodies)
    t0 = time.perf_counter()
    est = estimate_global_cost(
        arch, shape, donate=donate,
        serve_dtype=jnp.bfloat16 if serve_bf16 else None)
    t_est = time.perf_counter() - t0

    t_comp = est["flops"] / (chips * PEAK_FLOPS)
    t_mem = est["bytes"] / (chips * HBM_BW)
    t_coll = coll_bytes_dev / ICI_BW
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]

    n_act = arch.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_act * tokens
    else:
        model_flops = 2 * n_act * shape.global_batch

    bytes_per_device = (mem.argument_size_in_bytes
                        + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        estimate_s=round(t_est, 2),
        chips=chips,
        memory=dict(argument=mem.argument_size_in_bytes,
                    temp=mem.temp_size_in_bytes,
                    output=mem.output_size_in_bytes,
                    total=bytes_per_device,
                    fits_hbm=bool(bytes_per_device <= HBM_BYTES)),
        compiled_flops_per_device=flops_dev,
        compiled_bytes_per_device=bytes_dev,
        hlo_flops=est["flops"],          # global, scan-corrected
        hlo_bytes=est["bytes"],
        collectives=coll,
        collective_bytes_per_device=coll_bytes_dev,
        roofline=dict(compute_s=t_comp, memory_s=t_mem,
                      collective_s=t_coll, dominant=dominant),
        model_flops=model_flops,
        useful_compute_ratio=(model_flops / est["flops"]
                              if est["flops"] else None),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--donate", action="store_true",
                    help="donate params/opt (train) or cache (decode)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="stream params in bf16 for serve cells")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="override MoE capacity factor")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
        print(f"=== {tag}")
        try:
            rec = run_cell(a, s, mp, donate=args.donate,
                           serve_bf16=args.serve_bf16,
                           capacity_factor=args.capacity_factor,
                           accum=args.accum)
        except Exception as e:   # a failure here is a bug in our sharding
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()[-2000:]}
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        st = rec["status"]
        n_ok += st == "ok"
        n_skip += st == "skipped"
        n_fail += st == "error"
        if st == "ok":
            r = rec["roofline"]
            print(f"    ok: lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"mem/dev={rec['memory']['total']/1e9:.2f}GB "
                  f"terms(c/m/x)=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                  f"{r['collective_s']:.2e}) dom={r['dominant']}")
        else:
            print(f"    {st}: {rec.get('reason', rec.get('error'))}")
    print(f"SUMMARY ok={n_ok} skipped={n_skip} failed={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
