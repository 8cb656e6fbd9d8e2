import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod, or 2x16x16 multi-pod),
  2. resolves the arch's sharding rules and materializes ShapeDtypeStruct
     stand-ins for params / optimizer state / batch / caches (NO device
     allocation anywhere),
  3. ``jax.jit(step).lower(...)`` then ``.compile()`` — any sharding
     mismatch, non-divisible axis, or unsupported collective fails here,
  4. prints ``memory_analysis()`` (per-device bytes: proves what fits) and
     ``cost_analysis()``, walks the optimized HLO for trip-count-correct
     FLOPs / HBM bytes / collective bytes, and derives the three roofline
     terms against v5e constants,
  5. appends a JSON record to the results file (resumable across runs).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
  python -m repro.launch.dryrun --all            # every applicable cell
  python -m repro.launch.dryrun --all --multi-pod
"""

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config, list_archs            # noqa: E402
from repro.configs.shapes import (                          # noqa: E402
    SHAPES, applicable, serve_inputs, train_inputs,
)
from repro.distributed.context import (                     # noqa: E402
    ShardingRules, activate,
)
from repro.launch.hlo_analysis import analyze_hlo           # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.models.common import (                           # noqa: E402
    ModelConfig, abstract_params,
)
from repro.models.transformer import (                      # noqa: E402
    active_params, model_specs, num_params,
)
from repro.optim.adamw import AdamWConfig, opt_state_specs  # noqa: E402
from repro.serve.step import make_prefill_step, make_serve_step  # noqa: E402
from repro.train.step import make_train_step                # noqa: E402

# ------------------------------------------------- hardware constants (v5e)
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link
HBM_PER_CHIP = 16e9

DEFAULT_RESULTS = "results/dryrun.jsonl"


#: archs whose attention heads don't tile the 16-way model axis (40H, 20H,
#: or big replicated wk/wv) — their params take FSDP storage over 'data'
#: via the embed dim instead (gathered per layer by SPMD; overlappable).
_FSDP_ARCHS = ("qwen2.5-14b", "whisper-large-v3", "kimi-k2-1t-a32b")


def rules_for(cfg: ModelConfig, multi_pod: bool, fsdp_scope: str = "all",
              pp: bool = False):
    """(compute_rules, storage_rules) per arch.

    Compute rules steer ``constrain`` hints inside the model (intermediates
    may be padded by GSPMD, so non-divisible axes are fine there).  Storage
    rules resolve jit INPUT shardings, which must tile evenly — divisibility
    masking in ``ShardingCtx.spec`` drops what doesn't fit, and FSDP archs
    shard the d dims over the data axes instead.  ``fsdp_scope``:
    "all" (embed + attention + mlp d dims) or "attn" (attention weights
    only — the MLP keeps pure-TP storage; §Perf lever).
    """
    rules = ShardingRules()
    # with pipeline parallelism the pod axis holds STAGES, not data
    data_axes = ("data", "pod") if (multi_pod and not pp) else ("data",)
    if pp:
        rules = rules.override(layers="pod")
    if getattr(cfg, "seq_shard_norms", 0):
        rules = rules.override(seq_sp="model")
    if cfg.family == "moe":
        # expert weights: FSDP storage over data axes, gathered inside the
        # MoE shard_map (its AD transpose reduce-scatters the grads).
        rules = rules.override(expert_mlp=data_axes)
    if cfg.name.startswith("gemma3") or cfg.name.startswith("xlstm"):
        # 4 q-heads / <=4 kv-heads cannot shard 16-way; attention stays
        # replicated over 'model' and the MLP carries the TP.
        rules = rules.override(qheads=None, kv_heads=None)
    storage = rules
    if cfg.name in _FSDP_ARCHS:
        fsdp = dict(attn_in=data_axes, attn_out_d=data_axes)
        if fsdp_scope == "all":
            fsdp["embed"] = data_axes
        storage = rules.override(**fsdp)
    return rules, storage


def opt_rules_for(storage: ShardingRules, multi_pod: bool) -> ShardingRules:
    """ZeRO-1: moments additionally sharded over the data axes via the
    d dims (divisible by 32 for every assigned arch)."""
    data_axes = ("data", "pod") if multi_pod else ("data",)
    return storage.override(embed=data_axes, attn_in=data_axes,
                            attn_out_d=data_axes)


def decode_rules(cfg: ModelConfig, rules: ShardingRules,
                 batch: int, model_axis: int = 16) -> ShardingRules:
    """Decode-cache sharding strategy.

    * batch==1 (long_500k): seq-shard the cache over 'data' (batch can't
      shard; masking would otherwise leave the 500k cache replicated).
    * kv-heads divide the model axis: keep head-sharded caches.
    * otherwise (GQA kv=8 vs model=16): seq-shard the cache over 'model' —
      attention reduces over the sharded seq axis via GSPMD collectives.
    """
    if batch <= 8:
        if cfg.n_kv_heads % model_axis == 0:
            return rules.override(cache_seq="data")
        return rules.override(cache_seq=("data", "model"), kv_heads=None)
    if cfg.n_kv_heads % model_axis != 0:
        return rules.override(cache_seq="model", kv_heads=None)
    return rules


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: str | None = None, overrides: dict | None = None,
             fsdp_scope: str = "all", tag: str | None = None,
             pp: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}

    if pp and cfg.name in _FSDP_ARCHS:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped",
                "reason": "pp unsupported with FSDP storage (see "
                          "repro.distributed.pipeline docstring)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    rules, storage_rules = rules_for(cfg, multi_pod, fsdp_scope=fsdp_scope,
                                     pp=pp)
    if shape.kind == "decode":
        rules = decode_rules(cfg, rules, shape.batch,
                             model_axis=mesh.shape["model"])
    opt_cfg = AdamWConfig(
        moment_dtype="bfloat16" if cfg.name.startswith("kimi") else "float32")

    t0 = time.time()
    specs = model_specs(cfg)
    with activate(mesh, storage_rules):
        params = abstract_params(specs, dtype=jnp.bfloat16)
    with activate(mesh, rules):
        if shape.kind == "train":
            with activate(mesh, opt_rules_for(storage_rules, multi_pod)):
                opt_specs = opt_state_specs(specs, opt_cfg)
                m = abstract_params(opt_specs["m"],
                                    dtype=jnp.dtype(opt_cfg.moment_dtype))
                v = abstract_params(opt_specs["v"],
                                    dtype=jnp.dtype(opt_cfg.moment_dtype))
            state = {"params": params,
                     "opt": {"m": m, "v": v,
                             "step": jax.ShapeDtypeStruct((), jnp.float32)},
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
            batch = train_inputs(cfg, shape)
            if pp:
                from repro.distributed.pipeline import (
                    make_pp_forward, pp_lm_loss)
                from repro.optim.adamw import adamw_apply
                fwd = make_pp_forward(cfg, mesh,
                                      n_microbatches=max(cfg.microbatches, 4))

                def step_fn(st, b):
                    loss, grads = jax.value_and_grad(
                        lambda p: pp_lm_loss(p, cfg, b, fwd))(st["params"])
                    new_p, new_opt, om = adamw_apply(
                        grads, st["opt"], st["params"], opt_cfg)
                    return ({"params": new_p, "opt": new_opt,
                             "step": st["step"] + 1}, {"loss": loss, **om})
            else:
                step_fn = make_train_step(cfg, opt_cfg)
            lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(state, batch)
        elif shape.kind == "prefill":
            batch = train_inputs(cfg, shape)
            step_fn = make_prefill_step(cfg)
            lowered = jax.jit(step_fn).lower(params, batch)
        else:  # decode
            cache, token, pos = serve_inputs(cfg, shape)
            step_fn = make_serve_step(cfg)
            lowered = jax.jit(step_fn, donate_argnums=(1,)).lower(
                params, cache, token, pos)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    print(ma)
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})

    hlo = compiled.as_text()
    # always archive the optimized HLO (gzipped) so the roofline walker can
    # be refined without recompiling 66 cells on one CPU core
    import gzip
    hlo_dir = os.path.join(os.path.dirname(DEFAULT_RESULTS) or ".", "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    hlo_path = os.path.join(
        hlo_dir, f"{arch}_{shape_name}_{'2x16x16' if multi_pod else '16x16'}"
        f"{suffix}.hlo.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    cost = analyze_hlo(hlo)

    # roofline terms (per device; post-SPMD HLO shapes are per-device)
    t_compute = cost.flops / PEAK_FLOPS
    t_memory = cost.bytes_accessed / HBM_BW
    t_coll = cost.collective_bytes / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)

    n_total = num_params(cfg)
    n_active = active_params(cfg)
    tokens = shape.batch * (shape.seq if shape.kind != "decode" else 1)
    mult = 3 if shape.kind == "train" else 1  # fwd+bwd
    model_flops = 2.0 * n_active * tokens * mult          # global
    model_flops_per_chip = model_flops / n_chips
    useful_ratio = (model_flops_per_chip / cost.flops) if cost.flops else 0.0

    arg_bytes = int(ma.argument_size_in_bytes) if ma else None
    temp_bytes = int(ma.temp_size_in_bytes) if ma else None
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        **({"variant": tag} if tag else {}),
        **({"overrides": {k: str(v) for k, v in overrides.items()}}
           if overrides else {}),
        "status": "ok",
        "n_chips": n_chips,
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_dev": arg_bytes,
            "temp_bytes_per_dev": temp_bytes,
            "output_bytes_per_dev": int(ma.output_size_in_bytes) if ma else None,
            "fits_16gb": (arg_bytes + temp_bytes) < HBM_PER_CHIP
            if ma else None,
        },
        "xla_cost_analysis": {
            "flops_body_once": ca.get("flops"),
            "bytes_body_once": ca.get("bytes accessed"),
        },
        "hlo_walk": {
            "flops_per_dev": cost.flops,
            "hbm_bytes_per_dev": cost.bytes_accessed,
            "collective_bytes_per_dev": cost.collective_bytes,
            "collectives": {k: int(v) for k, v in cost.collectives.items()},
            "collective_count": cost.collective_count,
            "unparsed_while": cost.unparsed_while,
            "copy_bytes_per_dev": cost.copy_bytes,
            "elided_bytes_per_dev": cost.elided_bytes,
        },
        "roofline": {
            **{k: round(v, 6) for k, v in terms.items()},
            "bottleneck": bottleneck.replace("_s", ""),
            "model_flops_global": model_flops,
            "useful_flops_ratio": round(useful_ratio, 4),
            "params_total": n_total,
            "params_active": n_active,
        },
    }
    del compiled, lowered
    gc.collect()
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all applicable (arch x shape) cells for this mesh")
    ap.add_argument("--out", default=DEFAULT_RESULTS)
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already recorded in --out")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="config override key=value (perf variants), "
                         "e.g. --set remat=dots --set microbatches=2")
    ap.add_argument("--fsdp-scope", default="all", choices=("all", "attn"))
    ap.add_argument("--tag", default=None,
                    help="variant label recorded with the results")
    ap.add_argument("--pp", action="store_true",
                    help="pipeline the pod axis (multi-pod train cells): "
                         "stages over 'pod' via shard_map+ppermute")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.overrides:
        k, _, v = kv.partition("=")
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skipped"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    failures = 0
    for arch, shape in cells:
        if (arch, shape, mesh_name) in done:
            print(f"# skip (done): {arch} {shape} {mesh_name}", flush=True)
            continue
        print(f"# === {arch} x {shape} @ {mesh_name}"
              f"{' [' + args.tag + ']' if args.tag else ''} ===", flush=True)
        try:
            rec = run_cell(arch, shape, args.multi_pod,
                           save_hlo=args.save_hlo, overrides=overrides,
                           fsdp_scope=args.fsdp_scope, tag=args.tag,
                           pp=args.pp)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec.get("roofline", rec), indent=None), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
