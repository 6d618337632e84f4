import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding rules are coherent (GSPMD partitions the whole step),
  * the program fits (memory_analysis bytes/device),
  * and it yields the roofline terms (cost_analysis + HLO collective bytes)
    recorded into EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
Results append incrementally to experiments/dryrun.json (idempotent per key).
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import numpy as np

from repro.analysis.roofline import analytic_traffic, build_report
from repro.configs import (ASSIGNED, SHAPE_BY_NAME, SHAPES, cell_supported,
                           get_config)
from repro.core.perf_model import model_flops
from repro.distributed import ctx as shard_ctx
from repro.distributed.sharding import (batch_spec, cache_spec, param_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, input_specs
from repro.obs.log import get_logger
from repro.train.optimizer import OptConfig
from repro.train.train_loop import (TrainConfig, make_train_step,
                                    train_state_shape)

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun.json")

# level-filtered and capturable in tests (repro.obs.log.capture); emits
# the same "[dryrun] ..." lines the bare prints used to
_log = get_logger("dryrun")


def _tree_bytes(tree) -> float:
    return float(sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                     for l in jax.tree_util.tree_leaves(tree)))


def _bf16_params(shape_tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), shape_tree)


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def train_tcfg(arch: str) -> TrainConfig:
    # bf16 moments for the two largest configs (HBM fit — DESIGN.md §7)
    big = arch in ("deepseek-v3-671b", "deepseek-67b")
    return TrainConfig(
        opt=OptConfig(state_dtype="bfloat16" if big else "float32"),
        accum=8, remat="full", grad_dtype="bfloat16" if big else "float32")


# ------------------------------------------------------------------ #
# §Perf hillclimb tunings — applied with --tuned; baselines stay frozen
# under their original keys. Each field is one hypothesis->change from
# EXPERIMENTS.md §Perf.
# ------------------------------------------------------------------ #
class CellTuning:
    def __init__(self, accum=None, cast_bf16=False, no_fsdp=False,
                 embed_tp=False, opt_dtype=None, attn_impl=None,
                 moe_shard_cap=False,
                 grad_dtype=None, dp_all=False, remat="keep",
                 moe_shardmap=False):
        self.accum, self.cast_bf16, self.no_fsdp = accum, cast_bf16, no_fsdp
        self.embed_tp, self.opt_dtype = embed_tp, opt_dtype
        self.attn_impl = attn_impl
        self.moe_shard_cap, self.grad_dtype = moe_shard_cap, grad_dtype
        self.remat = remat            # "keep" | None | "full" | "dots"
        self.moe_shardmap = moe_shardmap
        # dp_all: batch over EVERY mesh axis, replicated params, TP off —
        # the right layout for models far too small for 256-way TP
        self.dp_all = dp_all


TUNINGS = {
    # worst roofline fraction: tiny model over-sharded -> pure DP over all
    # 256 chips, one microbatch, bf16 grads
    ("whisper-base", "train_4k"): CellTuning(
        accum=1, cast_bf16=True, no_fsdp=True, grad_dtype="bfloat16",
        dp_all=True, remat="dots"),
    # most collective-bound + paper-representative: bf16 gathers, fewer
    # microbatches, data-sharded MoE capacity buffers, int8 moments, banded
    # attention. (embed_tp — d-sharded embedding — was tried and REFUTED: it
    # trips an XLA SPMD dynamic-slice bug on the token gather; see §Perf.)
    # (moe_shard_cap — capacity dim over data axes — was also REFUTED: the
    # dispatch scatter onto a 2-axis-sharded buffer replicates; see §Perf.)
    # (opt_dtype="int8" REFUTED at this scale: the dequant reshape between
    # block layout and the 4D expert layout forces 917 GB whole-tensor
    # re-gathers; a per-shard shard_map quantizer would be needed. §Perf.)
    ("deepseek-v3-671b", "train_4k"): CellTuning(
        accum=4, cast_bf16=True, moe_shardmap=True, grad_dtype="bfloat16"),
    # serving: TP-only weights (no per-token FSDP gather)
    ("deepseek-67b", "decode_32k"): CellTuning(no_fsdp=True),
}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               tuning: Optional[CellTuning] = None):
    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return None, why
    mesh = make_production_mesh(multi_pod=multi_pod)
    api = build_model(cfg)
    specs = input_specs(cfg, shape)
    t = tuning or CellTuning()
    os.environ["REPRO_MOE_SHARD_CAP"] = "1" if t.moe_shard_cap else "0"
    os.environ["REPRO_MOE_SHARDMAP"] = "1" if t.moe_shardmap else "0"
    spec_kw = dict(no_fsdp=t.no_fsdp, embed_tp=t.embed_tp)

    loss_fn = api.loss
    prefill_fn_base = api.prefill
    if t.attn_impl and cfg.family not in ("ssm", "cnn") and cfg.rwkv is None:
        import functools
        loss_fn = functools.partial(api.loss, attn_impl=t.attn_impl)
        prefill_fn_base = functools.partial(api.prefill, attn_impl=t.attn_impl)

    rules = None
    dp_axes = None
    if t.dp_all:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_axes = tuple(mesh.axis_names)
        while dp_axes and shape.global_batch % \
                int(np.prod([sizes[a] for a in dp_axes])):
            dp_axes = dp_axes[:-1]       # drop trailing axes until divisible
        rules = {"batch": dp_axes, "heads": None, "kv_heads": None,
                 "ff": None, "vocab": None, "experts": None}

    with shard_ctx.use_sharding(mesh, rules=rules):
        if shape.kind == "train":
            tcfg = train_tcfg(arch)
            import dataclasses as _dc
            if t.accum is not None:
                tcfg = _dc.replace(tcfg, accum=t.accum)
            if t.cast_bf16:
                tcfg = _dc.replace(tcfg, cast_params_bf16=True)
            if t.grad_dtype:
                tcfg = _dc.replace(tcfg, grad_dtype=t.grad_dtype)
            if t.opt_dtype:
                tcfg = _dc.replace(tcfg, opt=_dc.replace(
                    tcfg.opt, state_dtype=t.opt_dtype))
            if t.remat != "keep":
                tcfg = _dc.replace(tcfg, remat=t.remat)
            state_shape = train_state_shape(api.init, tcfg)
            if t.dp_all:
                spec_kw2 = dict(spec_kw)
                spec_kw2["no_fsdp"] = True
                state_specs = jax.tree_util.tree_map(
                    lambda _: jax.sharding.PartitionSpec(),
                    param_specs(mesh, state_shape, **spec_kw2),
                    is_leaf=lambda x: isinstance(x, P))
            else:
                state_specs = param_specs(mesh, state_shape, **spec_kw)
            b_specs = batch_spec(mesh, specs["batch"],
                                 dp_axes=dp_axes if t.dp_all else None)
            step = make_train_step(loss_fn, tcfg)
            fn = jax.jit(step,
                         in_shardings=(_ns(mesh, state_specs),
                                       _ns(mesh, b_specs)),
                         out_shardings=(_ns(mesh, state_specs), None),
                         donate_argnums=0)
            lowered = fn.lower(state_shape, specs["batch"])
            traffic = analytic_traffic(
                cfg, shape,
                params_bytes=_tree_bytes(state_shape["params"]),
                opt_bytes=_tree_bytes(state_shape["opt"]["m"]) +
                _tree_bytes(state_shape["opt"]["v"]),
                accum=tcfg.accum, remat=tcfg.remat is not None)
        elif shape.kind == "prefill":
            params_shape = _bf16_params(jax.eval_shape(
                lambda: api.init(jax.random.PRNGKey(0))))
            p_specs = param_specs(mesh, params_shape, **spec_kw)
            b_specs = batch_spec(mesh, specs["batch"])

            def prefill_fn(params, batch):
                kw = {}
                if "frames" in batch:
                    kw["frames"] = batch["frames"]
                return prefill_fn_base(params, batch["tokens"],
                                       shape.seq_len, **kw)

            fn = jax.jit(prefill_fn,
                         in_shardings=(_ns(mesh, p_specs),
                                       _ns(mesh, b_specs)))
            lowered = fn.lower(params_shape, specs["batch"])
            cache_shape = jax.eval_shape(
                lambda: api.init_cache(shape.global_batch, shape.seq_len))
            traffic = analytic_traffic(
                cfg, shape, params_bytes=_tree_bytes(params_shape),
                cache_bytes=_tree_bytes(cache_shape))
        else:  # decode
            params_shape = _bf16_params(jax.eval_shape(
                lambda: api.init(jax.random.PRNGKey(0))))
            p_specs = param_specs(mesh, params_shape, **spec_kw)
            c_specs = cache_spec(mesh, specs["cache"])
            t_spec = batch_spec(mesh, {"t": specs["token"]})["t"]

            def decode_fn(params, cache, token):
                return api.decode_step(params, cache, token)

            fn = jax.jit(decode_fn,
                         in_shardings=(_ns(mesh, p_specs),
                                       _ns(mesh, c_specs),
                                       NamedSharding(mesh, t_spec)),
                         out_shardings=(None, _ns(mesh, c_specs)),
                         donate_argnums=1)
            lowered = fn.lower(params_shape, specs["cache"], specs["token"])
            traffic = analytic_traffic(
                cfg, shape, params_bytes=_tree_bytes(params_shape),
                cache_bytes=_tree_bytes(specs["cache"]))
    return lowered, "", traffic


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, tuned: bool = False) -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = 512 if multi_pod else 256
    key = f"{arch}|{shape_name}|{mesh_name}" + ("|tuned" if tuned else "")
    tuning = TUNINGS.get((arch, shape_name)) if tuned else None
    if tuned and tuning is None:
        return {"key": key, "status": "skipped", "note": "no tuning defined"}
    t0 = time.time()
    try:
        out = lower_cell(arch, shape_name, multi_pod, tuning=tuning)
        if out[0] is None:
            rec = {"key": key, "status": "skipped", "note": out[1]}
            if verbose:
                _log.info(f"SKIP {key}: {out[1]}")
            return rec
        lowered, note, traffic = out
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        from repro.analysis.hlo_costs import cost_analysis_dict
        cost = cost_analysis_dict(compiled)
        _log.info(f"{key} memory_analysis: {mem}")
        _log.info(f"{key} cost_analysis: "
                  f"flops={cost.get('flops')} bytes={cost.get('bytes accessed')}")
        hlo = compiled.as_text()
        cfg = get_config(arch)
        shape = SHAPE_BY_NAME[shape_name]
        rep = build_report(arch=arch, shape=shape_name, mesh_name=mesh_name,
                           chips=chips, cost=cost, mem=mem, hlo_text=hlo,
                           model_flops=model_flops(cfg, shape),
                           traffic=traffic, note=note)
        rec = {"key": key, "status": "ok", "lower_s": round(t_lower, 1),
               "compile_s": round(t_compile, 1), **rep.to_json()}
        if verbose:
            _log.info(f"OK {key} compute={rep.compute_s:.3e}s "
                      f"mem={rep.memory_s:.3e}s coll={rep.collective_s:.3e}s "
                      f"dominant={rep.dominant} hbm={rep.hbm_total_gib:.1f}GiB "
                      f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        return rec
    except Exception as e:                                     # noqa: BLE001
        traceback.print_exc()
        return {"key": key, "status": "error", "error": f"{type(e).__name__}: {e}"}


def load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, res: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(res, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="apply the §Perf hillclimb tunings (separate keys)")
    ap.add_argument("--out", default=os.path.abspath(RESULTS))
    args = ap.parse_args()

    meshes = []
    if args.multi_pod or not args.single_pod:
        meshes.append(True)
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    meshes = sorted(set(meshes))        # False (single) first

    archs = [args.arch] if args.arch else sorted(ASSIGNED)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]

    res = load_results(args.out)
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                key = f"{arch}|{shape_name}|" + \
                    ("pod2x16x16" if mp else "pod16x16") + \
                    ("|tuned" if args.tuned else "")
                if args.tuned and (arch, shape_name) not in TUNINGS:
                    continue
                if not args.force and res.get(key, {}).get("status") == "ok":
                    _log.info(f"cached {key}")
                    continue
                rec = run_cell(arch, shape_name, mp, tuned=args.tuned)
                res[key] = rec
                save_results(args.out, res)
    n_ok = sum(1 for r in res.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in res.values() if r.get("status") == "skipped")
    n_err = sum(1 for r in res.values() if r.get("status") == "error")
    _log.info(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
