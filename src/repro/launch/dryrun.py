"""Multi-pod dry-run: prove that every (architecture x input-shape x mesh)
combination lowers AND compiles on the production mesh, and extract the
roofline terms from the compiled artifact. The target is TPU v5e
(``TARGET_KIND``): roofline terms are reckoned against its published
peaks (``repro.roofline.analyze.PEAKS``).

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

Results are cached as JSON per combination so interrupted sweeps resume.
"""
# The VERY FIRST lines, before ANY other import: 512 placeholder devices
# so jax.make_mesh can build the production mesh (jax locks the device
# count on first init). Do NOT replicate this in tests/benches.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, SHAPES, get_arch, shape_applicable)
from repro.configs.base import FLConfig, ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models.frontends import batch_spec
from repro.models.model import Model
from repro.optim import adamw
from repro.roofline.analyze import analyze
from repro.serve.decode import make_prefill_step, make_serve_step
from repro.train.steps import MeshTopology, make_fl_train_step

PARAM_DTYPE = jnp.bfloat16
TARGET_KIND = "TPU v5 lite"      # jax device_kind of a TPU v5e chip
REF_BATCH_PER_CLOUD = 2


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def train_model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    tokens = shape.global_batch * shape.seq_len
    return 6.0 * cfg.active_param_count() * tokens


def decode_model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    return 2.0 * cfg.active_param_count() * shape.global_batch


def lower_pair(arch: str, shape_name: str, mesh, flcfg: FLConfig
               ) -> Tuple[Any, Any, float]:
    """Returns (lowered, compiled, model_flops)."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    model = Model(cfg)
    params_sds = jax.eval_shape(lambda k: model.init(k, PARAM_DTYPE),
                                jax.random.PRNGKey(0))

    jax.set_mesh(mesh)  # ambient mesh: enables intermediate constraints
    if shape.kind == "train":
        topo = MeshTopology.from_mesh(mesh, flcfg.n_clouds)
        opt = adamw(3e-4)
        opt_sds = jax.eval_shape(opt[0], params_sds)
        step, _ = make_fl_train_step(model, mesh, flcfg, opt)
        batch_sds = batch_spec(cfg, shape)
        ref_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (topo.n_clouds, REF_BATCH_PER_CLOUD) + s.shape[1:], s.dtype),
            batch_sds)
        rep_sds = jax.ShapeDtypeStruct((topo.n_clients,), jnp.float32)
        args = [params_sds, opt_sds, rep_sds, batch_sds, ref_sds]
        if cfg.fl_strategy == "fused":
            args.append(jax.ShapeDtypeStruct((2,), jnp.uint32))
        lowered = step.lower(*args)
        mf = train_model_flops(cfg, shape)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, mesh, batch=shape.global_batch)
        b_sds = batch_spec(cfg, shape)
        b_sds.pop("labels", None), b_sds.pop("mask", None)
        lowered = step.lower(params_sds, b_sds)
        mf = 2.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len
    else:  # decode
        step, _ = make_serve_step(model, mesh, batch=shape.global_batch,
                                  max_len=shape.seq_len,
                                  cache_dtype=PARAM_DTYPE)
        from repro.models import transformer as tfm
        cache_sds = jax.eval_shape(
            lambda p: tfm.init_cache(p, cfg, shape.global_batch,
                                     shape.seq_len, PARAM_DTYPE),
            params_sds)
        tok_sds = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = step.lower(params_sds, cache_sds, tok_sds, idx_sds)
        mf = decode_model_flops(cfg, shape)
    compiled = lowered.compile()
    return lowered, compiled, mf


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            flcfg: FLConfig, force: bool = False) -> Dict[str, Any]:
    mesh_tag = "pod2x16x16" if multi_pod else "16x16"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    if not shape_applicable(arch, shape_name):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "skipped", "reason": "see DESIGN.md §4.1"}
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        _, compiled, mf = lower_pair(arch, shape_name, mesh, flcfg)
        report = analyze(compiled, mesh, kind=TARGET_KIND, arch=arch,
                         shape=shape_name, model_flops=mf)
        rec = {"status": "ok", "compile_s": round(time.time() - t0, 1),
               **report.to_json()}
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:],
               "compile_s": round(time.time() - t0, 1)}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--n-clouds", type=int, default=4)
    args = ap.parse_args()

    flcfg = FLConfig(n_clouds=args.n_clouds, clients_per_round=12)
    pairs = []
    arches = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in arches:
            for s in shapes:
                pairs.append((a, s, mp))

    for a, s, mp in pairs:
        rec = run_one(a, s, mp, args.out, flcfg, force=args.force)
        status = rec.get("status")
        msg = (f"dominant={rec.get('dominant')} "
               f"compute={rec.get('compute_s', 0):.2e}s "
               f"mem={rec.get('memory_s', 0):.2e}s "
               f"coll={rec.get('collective_s', 0):.2e}s"
               if status == "ok" else rec.get("error", rec.get("reason", "")))
        print(f"[{'2x16x16' if mp else '16x16'}] {a:28s} {s:12s} "
              f"{status:8s} {msg}", flush=True)


if __name__ == "__main__":
    main()
