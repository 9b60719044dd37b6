import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, record memory/cost/collective analysis (deliverable (e) + §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k [--multi-pod] [--all] [--out results/dryrun]

Each cell writes ``results/dryrun/<arch>__<shape>__<mesh>.json`` with:
    per-device bytes (memory_analysis), flat cost_analysis, loop-aware HLO
    cost (flops / bytes / collective bytes by type), roofline terms against
    TPU v5e constants, and MODEL_FLOPS utilization ratio.

The 512 placeholder host devices exist ONLY in this process (see XLA_FLAGS
above, set before any jax import); smoke tests and benches see 1 device.
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import SHAPES, cell_skips, get_config, list_archs
from ..distributed.sharding import (activation_specs, data_axes_of,
                                    serve_rules, train_rules, tree_shardings)
from ..models import build_model
from ..train.optimizer import AdamWConfig
from ..train.train_state import abstract_train_state, make_train_step
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link


def _axes_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh, axes, dim: int):
    """Use ``axes`` for a dim only when it divides evenly (batch=1 cells
    replicate over data and put all parallelism on the model axis)."""
    return axes if dim % _axes_size(mesh, axes) == 0 else None


def _batch_shardings(mesh, batch_specs):
    fsdp = data_axes_of(mesh)

    def spec_for(path_key, s):
        nd = len(s.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        entries = [_fit(mesh, fsdp, s.shape[0])] + [None] * (nd - 1)
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(
        lambda p, s: spec_for(p, s), batch_specs)


def _cache_shardings(mesh, cache_specs):
    """KV sequence shards over `model` (flash-decoding style); states shard
    batch over data axes and a wide inner dim over model when divisible."""
    fsdp = data_axes_of(mesh)
    n_model = mesh.shape["model"]

    def spec_for(path, s):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(s.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        b = _fit(mesh, fsdp, s.shape[0])
        if key in ("k", "v"):
            # [B, C, kv, hd]: sequence over model (flash-decoding split-K)
            seq = _fit(mesh, "model", s.shape[1])
            return NamedSharding(mesh, P(b, seq, None, None))
        if key in ("k_scale", "v_scale"):   # [B, C, kv]
            seq = _fit(mesh, "model", s.shape[1])
            return NamedSharding(mesh, P(b, seq, None))
        if key == "wkv":        # [B, H, K, V]
            h = _fit(mesh, "model", s.shape[1])
            return NamedSharding(mesh, P(b, h, None, None))
        if key == "ssd":        # [B, H, P, N]
            pdim = _fit(mesh, "model", s.shape[2])
            return NamedSharding(mesh, P(b, None, pdim, None))
        if key == "conv":       # [B, k-1, conv_dim]
            c = _fit(mesh, "model", s.shape[2])
            return NamedSharding(mesh, P(b, None, c))
        if key == "enc_out":    # [B, T, D]
            return NamedSharding(mesh, P(b, None, None))
        return NamedSharding(mesh, P(*([b] + [None] * (nd - 1))))

    return jax.tree_util.tree_map_with_path(spec_for, cache_specs)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, block_size: int = 1024,
             variant: str = "baseline",
             kernel_contract: bool = False,
             seq_parallel_acts: bool = False,
             donate_cache: bool = False,
             kv_int8: bool = False,
             serve_bf16: bool = False,
             moe_a2a: bool = False,
             flash_vjp: bool = True) -> dict:
    """Lower+compile one cell.  ``variant`` names the perf-iteration
    configuration (EXPERIMENTS.md §Perf); baseline is paper-faithful."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skips = cell_skips()
    if (arch, shape_name) in skips:
        res = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "status": "skipped", "reason": skips[(arch, shape_name)]}
        out_dir.mkdir(parents=True, exist_ok=True)
        tag = "multi" if multi_pod else "single"
        (out_dir / f"{arch}__{shape_name}__{tag}.json").write_text(
            json.dumps(res, indent=2))
        return res

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    fsdp = data_axes_of(mesh)
    mode = "train" if shape.kind == "train" else "serve"
    rules = train_rules(mesh) if mode == "train" else serve_rules(mesh)
    act_mode = "train" if (mode == "train" or seq_parallel_acts) else "serve"
    model = build_model(
        cfg, mesh=mesh, data_axes=fsdp,
        act_specs=activation_specs(mesh, act_mode),
        remat=(shape.kind == "train"),
        scan_impl="kernel_contract" if kernel_contract else "chunked",
        kv_cache_dtype=jnp.int8 if kv_int8 else jnp.bfloat16,
        param_dtype=jnp.bfloat16 if (serve_bf16 and mode == "serve")
        else jnp.float32,
        moe_impl="a2a" if moe_a2a else "psum",
        flash_vjp=flash_vjp)

    param_shardings = tree_shardings(mesh, model.param_logical_axes(), rules)
    batch_specs = model.input_specs(shape)
    t0 = time.time()

    if shape.kind == "train":
        opt_cfg = AdamWConfig(
            schedule="wsd" if arch == "minicpm-2b" else "cosine")
        step_fn = make_train_step(model, opt_cfg)
        state_abs = abstract_train_state(model)
        state_shardings = {
            "params": param_shardings,
            "opt": {"m": param_shardings, "v": param_shardings,
                    "step": NamedSharding(mesh, P())},
        }
        in_shardings = (state_shardings, _batch_shardings(mesh, batch_specs))
        lowered = jax.jit(step_fn, in_shardings=in_shardings).lower(
            state_abs, batch_specs)
    elif shape.kind == "prefill":
        params_abs = model.abstract_params()

        def prefill_fn(params, batch):
            logits, cache = model.prefill(params, batch,
                                          max_len=shape.seq_len)
            return logits, cache

        in_shardings = (param_shardings, _batch_shardings(mesh, batch_specs))
        lowered = jax.jit(prefill_fn, in_shardings=in_shardings).lower(
            params_abs, batch_specs)
    else:  # decode
        params_abs = model.abstract_params()
        cache_abs = batch_specs["cache"]
        tokens_abs = batch_specs["tokens"]
        in_shardings = (param_shardings,
                        _cache_shardings(mesh, cache_abs),
                        NamedSharding(
                            mesh, P(_fit(mesh, fsdp, tokens_abs.shape[0]),
                                    None)))
        donate = (1,) if donate_cache else ()
        lowered = jax.jit(model.decode_step,
                          in_shardings=in_shardings,
                          donate_argnums=donate).lower(
            params_abs, cache_abs, tokens_abs)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    flat_cost = dict(compiled.cost_analysis() or {})
    try:
        mem = compiled.memory_analysis()
        memory = {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
        }
    except Exception as e:                              # pragma: no cover
        memory = {"error": str(e)}

    hlo_text = compiled.as_text()
    cost = analyze_hlo(hlo_text)

    # roofline terms (seconds); per-device analyzer values are multiplied
    # back to whole-machine with n_chips cancelling out:
    compute_s = cost.flops / PEAK_FLOPS
    memory_s = cost.bytes / HBM_BW
    collective_s = cost.total_collective_bytes / ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)], key=lambda kv: kv[1])[0]

    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1)
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        model_flops = 6.0 * n_active * n_tokens
    else:
        model_flops = 2.0 * n_active * n_tokens
    hlo_flops_global = cost.flops * n_chips
    useful_ratio = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi(2x16x16)" if multi_pod else "single(16x16)",
        "variant": variant,
        "status": "ok",
        "n_chips": int(n_chips),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "param_count": int(n_params),
        "active_param_count": int(n_active),
        "memory": memory,
        "flat_cost_analysis": {k: float(v) for k, v in flat_cost.items()
                               if "flops" in k or k == "bytes accessed"},
        "hlo_cost_per_device": {
            "flops": cost.flops,
            "bytes": cost.bytes,
            "collective_bytes": cost.collective_bytes,
        },
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops": model_flops,
            "hlo_flops_global": hlo_flops_global,
            "useful_flop_ratio": useful_ratio,
        },
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    suffix = "" if variant == "baseline" else f"__{variant}"
    path = out_dir / f"{arch}__{shape_name}__{mesh_tag}{suffix}.json"
    path.write_text(json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    # perf-iteration variants (EXPERIMENTS.md §Perf)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--kernel-contract", action="store_true",
                    help="lower WKV/SSD as the Pallas kernel's IO contract")
    ap.add_argument("--seq-parallel-acts", action="store_true",
                    help="sequence-parallel activation constraints in serve")
    ap.add_argument("--donate-cache", action="store_true",
                    help="alias decode cache in/out (in-place KV update)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache with per-token scales")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="bf16 inference weights (vs fp32 master copies)")
    ap.add_argument("--moe-a2a", action="store_true",
                    help="all-to-all expert dispatch (vs psum EP)")
    ap.add_argument("--no-flash-vjp", action="store_true",
                    help="reproduce the autodiff-attention baseline")
    args = ap.parse_args()
    out_dir = Path(args.out)

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        archs = [args.arch] if args.arch else list_archs()
        shapes = [args.shape] if args.shape else list(SHAPES)
        for a in archs:
            for s in shapes:
                cells.append((a, s))
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch} x {shape} x {'multi' if multi else 'single'}"
            mesh_tag = "multi" if multi else "single"
            path = out_dir / f"{arch}__{shape}__{mesh_tag}.json"
            if args.skip_existing and path.exists():
                prev = json.loads(path.read_text())
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[skip-existing] {tag}")
                    continue
            t0 = time.time()
            try:
                res = run_cell(arch, shape, multi, out_dir,
                               variant=args.variant,
                               kernel_contract=args.kernel_contract,
                               seq_parallel_acts=args.seq_parallel_acts,
                               donate_cache=args.donate_cache,
                               kv_int8=args.kv_int8,
                               serve_bf16=args.serve_bf16,
                               moe_a2a=args.moe_a2a,
                               flash_vjp=not args.no_flash_vjp)
                if res["status"] == "skipped":
                    print(f"[SKIP] {tag}: {res['reason'][:60]}")
                else:
                    r = res["roofline"]
                    print(f"[OK]   {tag}: compile={res['compile_s']}s "
                          f"dominant={r['dominant']} "
                          f"compute={r['compute_s']*1e3:.2f}ms "
                          f"mem={r['memory_s']*1e3:.2f}ms "
                          f"coll={r['collective_s']*1e3:.2f}ms")
            except Exception as e:
                failures += 1
                print(f"[FAIL] {tag}: {e}")
                traceback.print_exc()
                path.write_text(json.dumps({
                    "arch": arch, "shape": shape, "mesh": mesh_tag,
                    "status": "failed", "error": str(e)[-2000:]}, indent=2))
            finally:
                print(f"       ({time.time()-t0:.1f}s)", flush=True)
    print(f"done; {failures} failures")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
