import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: JAX locks the device
# count at first initialization.  Dry-runs keep bf16 dots un-upcast (they
# never execute, so the CPU DotThunk limitation is irrelevant).
os.environ.setdefault("REPRO_SAFE_DOT", "0")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the full production step function — train_step
(train shapes), serve_prefill (prefill shapes) or serve_decode (decode
shapes) — resolves in/out shardings on the production mesh, lowers with
ShapeDtypeStruct inputs (no allocation), compiles, and records:

  * memory_analysis()  — proves the per-device footprint fits HBM,
  * cost_analysis()    — HLO FLOPs / bytes for §Roofline,
  * parsed collective bytes (roofline/hlo.py),
  * lowering/compile wall time and HLO op counts.

Results are cached as JSON under reports/dryrun/; EXPERIMENTS.md §Dry-run
and §Roofline are generated from these files.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--quick]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.configs.registry import ALL_SHAPES, ARCH_IDS, get_config, get_shape
from repro.data.pipeline import make_batch_specs
from repro.launch import shardings as sh
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.registry import serve_decode, serve_prefill
from repro.models.sharding import use_mesh
from repro.roofline.analysis import derive_terms, model_flops
from repro.roofline.hlo import collective_bytes, count_ops
from repro.train.step import TrainConfig, make_train_step

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def _n_micro(cfg: ArchConfig, shape: ShapeConfig, mesh) -> int:
    """Microbatch so ~2 batch rows are live per device per microstep —
    bounds activation memory for every arch at every mesh size.

    Perf note: FSDP weight gathers and wgrad reductions repeat per
    microbatch, so collective volume scales with n_micro — the hillclimb
    halves it for the collective-bound 300B configs (4 rows live instead
    of 2; REPRO_NMICRO overrides for experiments)."""
    if os.environ.get("REPRO_NMICRO"):
        return int(os.environ["REPRO_NMICRO"])
    from repro.models.sharding import data_axes
    dp = 1
    for a in data_axes(mesh):
        dp *= mesh.shape[a]
    rows_per_dev = max(1, shape.global_batch // dp)
    divisor = 4 if cfg.param_count() > 100e9 else 2
    return int(min(16, max(1, rows_per_dev // divisor)))


def build_train(cfg: ArchConfig, shape: ShapeConfig, mesh):
    tc = TrainConfig(remat="dots", n_micro=_n_micro(cfg, shape, mesh),
                     moment_dtype=cfg.moment_dtype,
                     loss_chunk=512)
    init_state, train_step = make_train_step(cfg, tc)
    state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_sh = sh.train_state_shardings(state_shapes, cfg, mesh)
    batch_shapes = make_batch_specs(cfg, shape)
    batch_sh = sh.batch_shardings(batch_shapes, mesh)
    metrics_sh = None
    fn = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, metrics_sh))
    return fn, (state_shapes, batch_shapes)


def build_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh):
    S = cfg.effective_seq(shape)
    B = shape.global_batch

    def prefill_fn(params, batch):
        return serve_prefill(params, cfg, batch, max_len=S)

    from repro.models.registry import init_model
    params_shapes = jax.eval_shape(lambda k: init_model(cfg, k),
                                   jax.random.PRNGKey(0))
    p_sh = sh.params_shardings(params_shapes, cfg, mesh)
    batch_shapes = make_batch_specs(cfg, shape)
    batch_shapes.pop("labels", None)
    batch_sh = sh.batch_shardings(batch_shapes, mesh)
    # outputs: (logits [B,V], caches)
    cache_shapes = jax.eval_shape(
        lambda: transformer.init_caches(cfg, B, S))
    out_sh = (sh.logits_sharding(mesh, cfg.vocab_size, B),
              sh.cache_shardings(cache_shapes, cfg, mesh, B))
    fn = jax.jit(prefill_fn, in_shardings=(p_sh, batch_sh),
                 out_shardings=out_sh)
    return fn, (params_shapes, batch_shapes)


def build_decode(cfg: ArchConfig, shape: ShapeConfig, mesh):
    from repro.models.registry import init_model
    S = cfg.effective_seq(shape)
    B = shape.global_batch

    def decode_fn(params, token, pos, caches):
        return serve_decode(params, cfg, token, pos, caches)

    params_shapes = jax.eval_shape(lambda k: init_model(cfg, k),
                                   jax.random.PRNGKey(0))
    p_sh = sh.params_shardings(params_shapes, cfg, mesh)
    cache_shapes = jax.eval_shape(lambda: transformer.init_caches(cfg, B, S))
    cache_sh = sh.cache_shardings(cache_shapes, cfg, mesh, B)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    tok_sh = sh.batch_shardings({"t": tok}, mesh)["t"]
    out_sh = (sh.logits_sharding(mesh, cfg.vocab_size, B), cache_sh)
    # donate the KV caches: the decode step updates one token in place —
    # without donation XLA materializes a full second cache every step
    fn = jax.jit(decode_fn,
                 in_shardings=(p_sh, tok_sh, sh.replicated(mesh), cache_sh),
                 out_shardings=out_sh, donate_argnums=(3,))
    return fn, (params_shapes, tok, pos, cache_shapes)


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             hlo_snippet: bool = False) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, reason = cfg.shape_applicable(shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    with mesh, use_mesh(mesh):
        fn, arg_shapes = BUILDERS[shape.kind](cfg, shape, mesh)
        lowered = fn.lower(*arg_shapes)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    ops = count_ops(hlo)

    # Trip-count-aware analysis (XLA's cost_analysis counts while bodies
    # once, under-reporting every lax.scan — see roofline/hlo_cost.py).
    from repro.roofline.hlo_cost import analyze
    acc = analyze(hlo)
    flops_per_dev = acc.flops
    bytes_per_dev = acc.bytes
    coll = dict(acc.coll_by_kind)
    coll["total"] = acc.coll_bytes
    terms = derive_terms(cfg, shape, mesh_name, chips,
                         hlo_flops=flops_per_dev * chips,
                         hlo_bytes=bytes_per_dev * chips,
                         collective_bytes_per_chip=coll.get("total", 0.0))

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips, "kind": shape.kind,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {"flops_per_device": flops_per_dev,
                 "bytes_per_device": bytes_per_dev,
                 "xla_flops_per_device": float(cost.get("flops", 0.0)),
                 "xla_bytes_per_device": float(cost.get("bytes accessed",
                                                        0.0))},
        "collectives": coll,
        "hlo_ops": ops,
        "roofline": terms.row(),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if hlo_snippet:
        result["hlo_head"] = hlo[:4000]
    return result


def cell_path(arch: str, shape_name: str, multi_pod: bool) -> str:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(REPORT_DIR, mesh_name), exist_ok=True)
    return os.path.join(REPORT_DIR, mesh_name, f"{arch}__{shape_name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s.name) for a in ARCH_IDS for s in ALL_SHAPES])
    failures = 0
    for arch, shape_name in cells:
        path = cell_path(arch, shape_name, args.multi_pod)
        if os.path.exists(path) and not args.force:
            with open(path) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                print(f"[cached] {arch} x {shape_name}")
                continue
        print(f"[dryrun] {arch} x {shape_name} "
              f"({'multi' if args.multi_pod else 'single'}-pod) ...",
              flush=True)
        try:
            res = run_cell(arch, shape_name, args.multi_pod)
        except Exception as e:                         # noqa: BLE001
            res = {"arch": arch, "shape": shape_name, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-3000:]}
            failures += 1
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = res["status"]
        extra = ""
        if status == "ok":
            r = res["roofline"]
            extra = (f" dom={r['dominant']}"
                     f" frac={r['roofline_fraction']:.3f}"
                     f" lower={res['t_lower_s']}s comp={res['t_compile_s']}s")
        elif status == "error":
            extra = " " + res["error"][:120]
        print(f"  -> {status}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
