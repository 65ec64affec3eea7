import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract roofline inputs from the compiled artifact.

The two lines above MUST stay the first statements in this file — jax locks
the device count at first backend init (this is why smoke tests and benches
import repro.* normally and see 1 device, while only this entry point sees
512 placeholder devices).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]

Outputs one JSON per cell under experiments/dryrun/.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import models
from ..configs import ARCHS, get_arch, SHAPES_BY_NAME, SHAPES, cell_is_runnable
from ..train.step import TrainConfig, make_train_step, abstract_train_state, train_state_specs
from .mesh import make_production_mesh, batch_spec, data_axes

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — never allocated)
# ---------------------------------------------------------------------------

def dp_for(shape, mesh):
    """DP axes for the batch dim, or None when the batch doesn't divide the
    DP extent (long_500k has global_batch=1 — batch stays unsharded and
    parallelism comes from model/sequence sharding)."""
    dp = data_axes(mesh)
    extent = 1
    for a in dp:
        extent *= mesh.shape[a]
    return dp if shape.global_batch % extent == 0 else None


def input_specs(cfg, shape, mesh):
    """Model inputs for one cell as ShapeDtypeStructs + their PartitionSpecs."""
    dp = dp_for(shape, mesh)
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        batch = {"tokens": tok, "labels": tok}
        specs = {"tokens": P(dp), "labels": P(dp)}
    elif shape.kind == "prefill":
        batch = {"tokens": tok}
        specs = {"tokens": P(dp)}
    else:  # decode: one new token, cache of length s
        batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        specs = {"tokens": P(dp)}
    if cfg.family == "audio":
        batch["audio_embed"] = jax.ShapeDtypeStruct(
            (b, cfg.n_audio_frames, cfg.d_model), jnp.float32)
        specs["audio_embed"] = P(dp)
    if cfg.family == "vlm" and shape.kind != "decode":
        batch["patch_embed"] = jax.ShapeDtypeStruct(
            (b, cfg.n_patches, cfg.d_model), jnp.float32)
        specs["patch_embed"] = P(dp)
    return batch, specs


def _shardings(mesh, spec_tree):
    from .mesh import filter_spec
    return jax.tree.map(lambda sp: NamedSharding(mesh, filter_spec(sp, mesh)),
                        spec_tree, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# collective-byte extraction from HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(r"=\s*(?:\([^)]*\)|\S+)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(?:-start)?\(")

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shapes_bytes(segment: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(segment):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op, by kind. HLO lines look like
    ``%x = bf16[16,128]{...} all-reduce(...)`` — we take the result shape(s)
    on the lhs of the op name as the wire-bytes proxy per device."""
    out = {k: 0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLL_KINDS:
            # result-def lines: "<name> = <shape(s)> <kind>(" or fusion-wrapped
            idx = stripped.find(f" {kind}(")
            if idx < 0:
                idx = stripped.find(f" {kind}-start(")
            if idx < 0:
                continue
            eq = stripped.find("=")
            if eq < 0 or eq > idx:
                continue
            seg = stripped[eq + 1: idx]
            out[kind] += _shapes_bytes(seg)
            counts[kind] += 1
            break
    out["total"] = sum(out[k] for k in _COLL_KINDS)
    out["counts"] = counts
    return out


# ---------------------------------------------------------------------------
# lowering one cell
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               tcfg: TrainConfig | None = None, extra_tag: str = ""):
    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    # 4 microbatches: keeps one microbatch's remat residuals live at a time
    # (peak activation memory / 4) and lets XLA overlap the grad reduce-
    # scatter of microbatch i with compute of i+1.
    tcfg = tcfg or TrainConfig(n_microbatches=4)
    dp = dp_for(shape, mesh)

    batch, bspecs = input_specs(cfg, shape, mesh)
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            state = abstract_train_state(cfg, tcfg)
            sspecs = train_state_specs(cfg, tcfg)
            step = make_train_step(cfg, tcfg)
            fn = jax.jit(step,
                         in_shardings=(_shardings(mesh, sspecs),
                                       _shardings(mesh, bspecs)),
                         out_shardings=(_shardings(mesh, sspecs), None),
                         donate_argnums=(0,))
            lowered = fn.lower(state, batch)
        elif shape.kind == "prefill":
            pvals, pspecs = models.abstract_params(cfg)
            pf = models.prefill_step(cfg)
            fn = jax.jit(pf, in_shardings=(_shardings(mesh, pspecs),
                                           _shardings(mesh, bspecs)),
                         out_shardings=(NamedSharding(mesh, P(dp, "model")),
                                        cspecs_sh(mesh, cfg, dp)))
            lowered = fn.lower(pvals, batch)
        else:  # decode
            pvals, pspecs = models.abstract_params(cfg)
            caches = jax.eval_shape(
                lambda: models.init_caches(cfg, shape.global_batch, shape.seq_len))
            dstep = models.decode_step(cfg)
            args = [pvals, caches, batch["tokens"]]
            csh = cspecs_sh(mesh, cfg, dp)
            in_sh = [_shardings(mesh, pspecs), csh, NamedSharding(mesh, P(dp))]
            if cfg.enc_dec:
                enc_kv = jax.eval_shape(
                    lambda: _abstract_enc_kv(cfg, shape.global_batch))
                kv_spec = jax.tree.map(
                    lambda s: NamedSharding(mesh, P(None, dp, None, "model", None)),
                    enc_kv)
                args.append(enc_kv)
                in_sh.append(kv_spec)
            fn = jax.jit(dstep, in_shardings=tuple(in_sh),
                         out_shardings=(NamedSharding(mesh, P(dp)), csh))
            lowered = fn.lower(*args)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    from .hlo_analysis import analyse_hlo
    loop_aware = analyse_hlo(hlo_text)

    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "multi_pod": multi_pod, "mesh": list(mesh.devices.shape),
        "n_devices": mesh.devices.size,
        "tag": extra_tag,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "collectives": coll,
        "loop_aware": loop_aware,
    }
    return result


def cspecs_sh(mesh, cfg, dp):
    """NamedShardings for the stacked cache tree (leading unit dim)."""
    from .mesh import filter_spec
    unit_specs = models.cache_specs(cfg, dp)
    return jax.tree.map(lambda spec: NamedSharding(mesh, filter_spec(spec, mesh)),
                        unit_specs, is_leaf=lambda x: isinstance(x, P))


def _abstract_enc_kv(cfg, b):
    nu = models.n_units(cfg)
    f = cfg.n_audio_frames
    hd = cfg.hd
    sds = jax.ShapeDtypeStruct((nu, b, f, cfg.n_kv_heads, hd), jnp.dtype(cfg.dtype))
    return {str(j): (sds, sds) for j in range(len(models.unit_layout(cfg)))}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cell(arch, shape_name, multi_pod, force=False, tcfg=None, tag=""):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    mesh_tag = "pod2" if multi_pod else "pod1"
    suffix = f"_{tag}" if tag else ""
    out = RESULTS_DIR / f"{arch}__{shape_name}__{mesh_tag}{suffix}.json"
    if out.exists() and not force:
        print(f"[skip cached] {out.name}")
        return json.loads(out.read_text())
    print(f"[lowering] {arch} × {shape_name} × {mesh_tag} ...", flush=True)
    try:
        res = lower_cell(arch, shape_name, multi_pod, tcfg=tcfg, extra_tag=tag)
    except Exception as e:
        res = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    out.write_text(json.dumps(res, indent=1))
    if "error" in res:
        print(f"  ERROR: {res['error'][:300]}")
    elif "skipped" in res:
        print(f"  skipped: {res['skipped']}")
    else:
        print(f"  ok: flops={res['flops']:.3e} compile={res['compile_s']}s "
              f"coll={res['collectives']['total']:.3e}B")
    return res


VARIANTS = {
    "": None,
    "gather_once": TrainConfig(n_microbatches=4, gather_weights_once=True),
    "bf16_opt": TrainConfig(n_microbatches=4, moments_bf16=True,
                            grad_accum_bf16=True),
    "micro2": TrainConfig(n_microbatches=2),
    "micro8": TrainConfig(n_microbatches=8),
    "micro8_bf16": TrainConfig(n_microbatches=8, moments_bf16=True,
                               grad_accum_bf16=True),
    "gather_once_bf16": TrainConfig(n_microbatches=4, gather_weights_once=True,
                                    moments_bf16=True, grad_accum_bf16=True),
    "save_tp": TrainConfig(n_microbatches=4, remat_policy="save_tp"),
    "save_tp_micro8": TrainConfig(n_microbatches=8, remat_policy="save_tp"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="", choices=sorted(VARIANTS),
                    help="train-step perf variant (EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        res = run_cell(a, s, mp, force=args.force,
                       tcfg=VARIANTS[args.variant], tag=args.variant)
        if "error" in res:
            failures += 1
    print(f"\n{len(cells)} cells, {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
