import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step, in_shardings=...).lower(**input_specs).compile()``
must succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh
for every assigned cell. Results (memory_analysis, cost_analysis,
per-collective bytes) are written to JSON for EXPERIMENTS.md and the
roofline module.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --sweep [--multi-pod] [--variants]
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .. import sharding as shlib              # noqa: E402
from ..analysis.hlo import collective_bytes   # noqa: E402
from ..configs import (SHAPES, applicable, cache_specs, get_config,  # noqa: E402
                       input_specs)
from ..configs.archs import ARCHS             # noqa: E402
from ..models import decode_step, forward     # noqa: E402
from ..training import OptimizerConfig, init_state, make_train_step  # noqa: E402
from . import specs as speclib                # noqa: E402
from .mesh import make_production_mesh        # noqa: E402

# HBM-driven overrides for the >=100B archs: bf16 optimizer moments
# (memory_analysis reports the result either way).
_OPT_OVERRIDES = {
    "command-r-plus-104b": {"state_dtype": "bfloat16"},
    "qwen3-moe-235b-a22b": {"state_dtype": "bfloat16"},
}

# Microbatching (gradient accumulation) for cells whose activations exceed
# HBM at one shot — the standard production knob; HLO cost scales exactly.
_ACCUM_OVERRIDES = {
    ("command-r-plus-104b", "train_4k"): 8,
    ("qwen3-moe-235b-a22b", "train_4k"): 8,
    ("whisper-large-v3", "train_4k"): 2,
    ("recurrentgemma-9b", "train_4k"): 4,
}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _opt_cfg(arch: str) -> OptimizerConfig:
    return OptimizerConfig(**_OPT_OVERRIDES.get(arch, {}))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               cfg=None, mesh=None, want_hlo: bool = False,
               cast_once: bool = False) -> dict:
    """Lower + compile one cell; return its dry-run record."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    ctx = shlib.make_ctx(mesh)
    ocfg = _opt_cfg(arch)
    t0 = time.monotonic()

    with shlib.use(ctx):
        if shape.step == "train":
            state_shapes = jax.eval_shape(
                lambda k: init_state(cfg, ocfg, k), jax.random.PRNGKey(0))
            batch_shapes = input_specs(cfg, shape)
            st_sh = speclib.state_shardings(state_shapes, ctx)
            bt_sh = speclib.batch_shardings(cfg, batch_shapes, ctx)
            accum = _ACCUM_OVERRIDES.get((arch, shape_name), 1)
            step_fn = make_train_step(cfg, ocfg, grad_accum=accum,
                                      param_shardings=st_sh["params"],
                                      cast_params_once=cast_once)
            lowered = jax.jit(
                step_fn, in_shardings=(st_sh, bt_sh), donate_argnums=(0,)
            ).lower(state_shapes, batch_shapes)
        elif shape.step == "prefill":
            params_shapes = jax.eval_shape(
                lambda k: _init_params(cfg, k), jax.random.PRNGKey(0))
            batch_shapes = input_specs(cfg, shape)
            p_sh = shlib.param_sharding_tree(params_shapes, ctx)
            bt_sh = speclib.batch_shardings(cfg, batch_shapes, ctx)

            def prefill_fn(params, batch):
                kw = {}
                if cfg.kind == "vlm":
                    kw["embeds"] = batch["embeds"]
                if cfg.kind == "audio":
                    kw["enc_embeds"] = batch["enc_embeds"]
                return forward(params, cfg, tokens=batch["tokens"], **kw)

            lowered = jax.jit(prefill_fn, in_shardings=(p_sh, bt_sh)).lower(
                params_shapes, batch_shapes)
        else:  # decode
            params_shapes = jax.eval_shape(
                lambda k: _init_params(cfg, k), jax.random.PRNGKey(0))
            cache_shapes = cache_specs(cfg, shape)
            token_shapes = input_specs(cfg, shape)
            p_sh = shlib.param_sharding_tree(params_shapes, ctx)
            c_sh = speclib.cache_shardings(cache_shapes, ctx)
            t_sh = speclib.batch_shardings(cfg, token_shapes, ctx)

            def serve_fn(params, cache, batch):
                return decode_step(params, cache, cfg, batch["token"])

            lowered = jax.jit(
                serve_fn, in_shardings=(p_sh, c_sh, t_sh),
                donate_argnums=(1,)
            ).lower(params_shapes, cache_shapes, token_shapes)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "step": shape.step,
        "compile_s": round(time.monotonic() - t0, 2),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_per_device_gb": (
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 1e9,
        },
        "cost": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_per_device": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        "collectives_per_device": coll,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "grad_accum": _ACCUM_OVERRIDES.get((arch, shape_name), 1),
        "cast_once": cast_once,
    }
    if want_hlo:
        rec["hlo"] = hlo
    return rec


def _init_params(cfg, key):
    from ..models import init_model
    return init_model(cfg, key)


# -------------------------------------------------------------- variants
def variant_configs(cfg):
    """Configs isolating each scan body for trip-count cost correction:
    'nonloop' (0 layers) + one single-cycle variant per stage (+ encoder).
    Returns [(tag, cfg, repetitions_in_full_model)]."""
    out = [("nonloop", dataclasses.replace(
        cfg, n_layers=0, n_enc_layers=0), 0)]
    for i, (pat, rep) in enumerate(cfg.stages()):
        out.append((f"stage{i}", dataclasses.replace(
            cfg, n_layers=len(pat), block_pattern=pat, n_enc_layers=0), rep))
    if cfg.n_enc_layers:
        out.append(("enc", dataclasses.replace(
            cfg, n_layers=0, n_enc_layers=1), cfg.n_enc_layers))
    return out


def lower_cell_with_variants(arch, shape_name, *, multi_pod=False,
                             cfg=None, cast_once=False):
    """Full compile (memory truth, scanned chunk loops) + cost-mode variant
    compiles (unrolled chunk loops, exact HLO cost). The roofline derives
    costs from the variants alone: nonloop + sum_s rep_s * body_s."""
    from ..models import layers as _layers

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg or get_config(arch)
    rec = lower_cell(arch, shape_name, multi_pod=multi_pod, cfg=cfg,
                     mesh=mesh, cast_once=cast_once)
    rec["variants"] = {}
    _layers.set_cost_mode(True)
    try:
        for tag, vcfg, rep in variant_configs(cfg):
            vrec = lower_cell(arch, shape_name, multi_pod=multi_pod,
                              cfg=vcfg, mesh=mesh, cast_once=cast_once)
            rec["variants"][tag] = {
                "rep": rep,
                "params": vcfg.param_count(),
                "flops_per_device": vrec["cost"]["flops_per_device"],
                "bytes_per_device": vrec["cost"]["bytes_per_device"],
                "collectives_per_device": vrec["collectives_per_device"],
            }
    finally:
        _layers.set_cost_mode(False)
    return rec


# ------------------------------------------------------------------ main
def run_sweep(multi_pod: bool, variants: bool, archs=None, shapes=None,
              out_dir=OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for arch in (archs or list(ARCHS)):
        for shape_name in (shapes or list(SHAPES)):
            if not applicable(arch, shape_name):
                print(f"SKIP  {arch} x {shape_name} (documented: "
                      f"full-attention arch, 500k decode)")
                continue
            tag = f"{arch}__{shape_name}__" + (
                "pod2x16x16" if multi_pod else "16x16")
            path = os.path.join(out_dir, tag + ".json")
            if os.path.exists(path):
                print(f"CACHED {tag}")
                results.append(json.load(open(path)))
                continue
            try:
                fn = (lower_cell_with_variants if variants else lower_cell)
                rec = fn(arch, shape_name, multi_pod=multi_pod)
                rec["ok"] = True
                print(f"OK    {tag}: peak/dev "
                      f"{rec['memory']['peak_per_device_gb']:.2f} GB, "
                      f"{rec['compile_s']}s compile")
            except Exception as e:  # a failure here is a bug in the system
                rec = {"arch": arch, "shape": shape_name, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()}
                print(f"FAIL  {tag}: {rec['error']}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variants", action="store_true",
                    help="also lower 0-layer/1-cycle variants for roofline")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    if args.sweep:
        res = run_sweep(args.multi_pod, args.variants,
                        archs=[args.arch] if args.arch else None,
                        shapes=[args.shape] if args.shape else None,
                        out_dir=args.out)
        bad = [r for r in res if not r.get("ok")]
        print(f"\n{len(res) - len(bad)}/{len(res)} cells OK")
        raise SystemExit(1 if bad else 0)
    assert args.arch and args.shape, "--arch and --shape (or --sweep)"
    fn = lower_cell_with_variants if args.variants else lower_cell
    rec = fn(args.arch, args.shape, multi_pod=args.multi_pod)
    print(json.dumps({k: v for k, v in rec.items() if k != "hlo"}, indent=2))


if __name__ == "__main__":
    main()
