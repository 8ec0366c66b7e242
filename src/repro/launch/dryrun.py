import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything else follows.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.hlo import parse_hlo_module               # noqa: E402
from repro.analysis import roofline as rl                     # noqa: E402
from repro.configs import (                                   # noqa: E402
    ARCH_IDS, SHAPES, cell_supported, get_config, input_specs,
)
from repro.launch.mesh import (                               # noqa: E402
    default_rules, make_production_mesh, mesh_device_count,
)
from repro.models import api as mapi                          # noqa: E402
from repro.models.module import (                             # noqa: E402
    abstract_params, partition_specs,
)
from repro.optim.adamw import AdamW                           # noqa: E402
from repro.sharding.ctx import use_sharding                   # noqa: E402
from repro.sharding.specs import (                            # noqa: E402
    cache_partition_specs, input_partition_specs, to_shardings,
)
from repro.train.step import TrainState, make_train_step      # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves: the sharding annotations are coherent at 256/512
chips, the program fits (memory_analysis), and produces the cost/collective
numbers §Roofline consumes. No arrays are ever allocated — everything lowers
from ShapeDtypeStruct stand-ins.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

# The production meshes are TPU v5e pods; the host devices only stand in
# for them, so the roofline is priced at that chip's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _abstract_opt_state(aparams):
    sds = lambda: jax.ShapeDtypeStruct((), jnp.int32)
    from repro.optim.adamw import AdamWState
    return AdamWState(sds(), jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), aparams),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     aparams))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    """Lower + compile one cell; returns a JSON-able result dict."""
    cfg = get_config(arch)
    if variant != "baseline":
        from repro.configs.base import apply_variant
        cfg = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "variant": variant}
    if not ok:
        return dict(base, status="skipped", reason=reason)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh_device_count(mesh)
    rules = default_rules(mesh)
    spec = mapi.spec(cfg)
    aparams = abstract_params(spec)
    pspecs = partition_specs(spec, mesh, rules)
    pshard = to_shardings(mesh, pspecs)
    ins = input_specs(cfg, shape)
    in_sh = to_shardings(mesh, input_partition_specs(mesh, rules, ins))

    with mesh, use_sharding(mesh, rules):
        if shape.kind == "train":
            opt = AdamW()
            step_fn = make_train_step(cfg, opt)
            astate = TrainState(aparams, _abstract_opt_state(aparams), None,
                                jax.ShapeDtypeStruct((), jnp.int32))
            state_sh = TrainState(
                pshard, type(astate.opt)(
                    NamedSharding(mesh, P()),
                    pshard, jax.tree.map(lambda s: s, pshard)),
                None, NamedSharding(mesh, P()))
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, in_sh),
                donate_argnums=(0,),
            ).lower(astate, ins)
        elif shape.kind == "prefill":
            def prefill_fn(params, batch):
                logits, caches = mapi.prefill(params, cfg, batch,
                                              shape.seq_len)
                return logits[:, -1:], caches

            lowered = jax.jit(
                prefill_fn, in_shardings=(pshard, in_sh),
            ).lower(aparams, ins)
        else:  # decode
            acaches = mapi.cache_abstract(cfg, shape.global_batch,
                                          shape.seq_len,
                                          enc_len=shape.seq_len)
            cache_sh = to_shardings(
                mesh, cache_partition_specs(cfg, mesh, rules, acaches))

            def decode_fn(params, caches, token, pos):
                return mapi.decode_step(params, cfg, caches, token, pos)

            lowered = jax.jit(
                decode_fn,
                in_shardings=(pshard, cache_sh, in_sh["token"],
                              in_sh["pos"]),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(aparams, acaches, ins["token"], ins["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- analyses -----------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}
    try:
        ca = dict(compiled.cost_analysis())
        cost_d = {k: float(v) for k, v in ca.items()
                  if isinstance(v, (int, float)) and k in
                  ("flops", "bytes accessed", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        cost_d = {"error": str(e)}

    hlo_text = compiled.as_text()
    stats = parse_hlo_module(hlo_text)
    hlo_path = None
    if os.environ.get("REPRO_SAVE_HLO", "1") != "0":
        out_dir = os.environ.get("REPRO_HLO_DIR", "results/hlo")
        os.makedirs(out_dir, exist_ok=True)
        import zstandard
        tag = f"{arch}__{shape_name}__{mesh_name}"
        if variant != "baseline":
            tag += f"__{variant}"
        hlo_path = os.path.join(out_dir, tag + ".hlo.zst")
        with open(hlo_path, "wb") as f:
            f.write(zstandard.ZstdCompressor(level=6).compress(
                hlo_text.encode()))

    sp = mapi.spec(cfg)
    n_params = rl.active_param_count(sp)
    moe = cfg.moe
    n_active = rl.active_param_count(
        sp, moe.top_k if moe else None, moe.n_experts if moe else None)
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    mf = rl.model_flops(n_params, n_active, tokens, shape.kind)
    roof = rl.analyze(stats, mf, n_chips, device_kind=TARGET_DEVICE_KIND)

    return dict(
        base,
        status="ok",
        hlo_path=hlo_path,
        n_chips=n_chips,
        device_kind=TARGET_DEVICE_KIND,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        params=n_params,
        active_params=n_active,
        tokens_per_step=tokens,
        memory_analysis=mem_d,
        cost_analysis=cost_d,
        hlo=dict(
            flops=stats.flops,
            dot_flops=stats.dot_flops,
            bytes_accessed=stats.bytes_accessed,
            collective_bytes=stats.collective_bytes,
            collective_breakdown=stats.collective_breakdown,
            while_trip_counts=stats.while_trip_counts,
            warnings=stats.warnings[:5],
        ),
        roofline=roof.as_dict(),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["all"],
                    default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"],
                    default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.variant != "baseline":
                    tag += f"__{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    res = lower_cell(arch, shape, mp, args.variant)
                except Exception:
                    failures += 1
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error",
                           "traceback": traceback.format_exc()}
                    print(f"[FAIL] {tag}", file=sys.stderr)
                    traceback.print_exc()
                    if args.fail_fast:
                        with open(path, "w") as f:
                            json.dump(res, f, indent=2)
                        return 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
                status = res["status"]
                extra = ""
                if status == "ok":
                    r = res["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" comp={r['compute_s']:.3e}s"
                             f" mem={r['memory_s']:.3e}s"
                             f" coll={r['collective_s']:.3e}s"
                             f" mfu={r['mfu']:.3f}"
                             f" compile={res['compile_s']:.0f}s")
                elif status == "skipped":
                    extra = " " + res["reason"]
                print(f"[{status:7s}] {tag}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
