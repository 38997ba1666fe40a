import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the *full-size* architecture abstractly
(ShapeDtypeStruct, no allocation), jits the appropriate step
(train_step / prefill / serve decode_step) with production shardings,
``.lower().compile()``s it for the single-pod 16×16 mesh and the 2-pod
2×16×16 mesh, prints ``memory_analysis()`` / ``cost_analysis()``, derives
the three roofline terms (core/roofline.py), and writes one JSON per cell
to ``--out`` (default experiments/dryrun/).

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the CI gate is tests/test_dryrun_smoke.py plus the
full sweep recorded in EXPERIMENTS.md §Dry-run.

The first two lines of this file (XLA device-count flag) must run before
any jax import — jax locks the device count on first init.  (No
``from __future__`` here: the flag lines must be the first statements.)
"""
import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ShapeConfig
from repro.core import lanes, roofline
from repro.launch.mesh import make_production_mesh, chips
from repro.models import partition, registry
from repro.optim import adamw_init
from repro.runtime.trainer import TrainConfig, make_train_step


# ---------------------------------------------------------------------------
# MODEL_FLOPS (the "useful math" numerator of the roofline fraction)
# ---------------------------------------------------------------------------

def model_flops(cfg, shape: ShapeConfig) -> float:
    """6·N·D for training, 2·N·D (+attention) for serving, per step."""
    n = cfg.n_active_params()
    b, s = shape.global_batch, shape.seq_len
    nh, hd = cfg.n_heads, cfg.hd
    if shape.kind == "train":
        flops = 6.0 * n * b * s
        if cfg.family != "ssm":
            # causal attention math (QK^T + PV, fwd+bwd = 3x fwd, half mask)
            flops += 3.0 * cfg.n_layers * 2.0 * nh * hd * b * s * s
        return flops
    if shape.kind == "prefill":
        flops = 2.0 * n * b * s
        if cfg.family != "ssm":
            flops += cfg.n_layers * 2.0 * nh * hd * b * s * s
        return flops
    # decode: one token against a KV of length s
    flops = 2.0 * n * b
    if cfg.family == "ssm":
        ss = cfg.ssm
        di = ss.d_inner(cfg.d_model)
        flops += cfg.n_layers * 4.0 * di * ss.d_state * b
    else:
        window = cfg.attn_window or s
        kv = []
        for i in range(cfg.n_layers):
            if cfg.family == "hybrid":
                glob = {0, cfg.n_layers // 2, cfg.n_layers - 1}
                kv.append(s if i in glob else min(window, s))
            else:
                kv.append(s)
        flops += sum(4.0 * nh * hd * k * b for k in kv)
    return flops


# ---------------------------------------------------------------------------
# step builders (one per shape.kind)
# ---------------------------------------------------------------------------

def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               tcfg: TrainConfig, rules: lanes.LogicalRules):
    """Returns (lowered, compiled, meta) for one grid cell."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules.for_mesh(mesh)
    bundle = registry.build(arch, rules=rules)
    cfg = bundle.cfg
    shape = SHAPES[shape_name]
    specs = bundle.input_specs(shape_name)
    aparams = registry.abstract_params(cfg)
    pshard = _named(mesh, partition.param_specs(aparams, rules, mesh=mesh))

    t0 = time.time()
    if shape.kind == "train":
        step, shardings = make_train_step(bundle.model, mesh, tcfg,
                                          rules=rules)
        aopt = jax.eval_shape(adamw_init, aparams)
        args = (aparams, aopt, specs)
        if tcfg.reduction == "hier_ef8":
            from repro.runtime.trainer import ef_state_template
            aef = jax.eval_shape(
                lambda p: ef_state_template(p, mesh), aparams)
            args = (aparams, aopt, aef, specs)
        with jax.set_mesh(mesh):
            lowered = step.lower(*args)
    elif shape.kind == "prefill":
        cshard = _named(mesh, partition.cache_specs(specs["cache"], rules, mesh=mesh))
        tokshard = NamedSharding(mesh, partition.fit_spec(
            rules.spec("batch", None),
            (shape.global_batch, shape.seq_len), mesh))
        extras = {k: v for k, v in specs.items()
                  if k not in ("tokens", "cache")}
        extra_shard = {k: NamedSharding(mesh, rules.spec("batch", None))
                       for k in extras}

        def prefill(params, tokens, cache, extras):
            return bundle.model.prefill(params, tokens, cache,
                                        remat=tcfg.remat, **extras)

        logits_shard = NamedSharding(mesh, partition.fit_spec(
            rules.spec("batch", "vocab_tp"),
            (shape.global_batch, cfg.vocab), mesh))
        jfn = jax.jit(
            prefill,
            in_shardings=(pshard, tokshard, cshard, extra_shard),
            out_shardings=(logits_shard, cshard))
        with jax.set_mesh(mesh):
            lowered = jfn.lower(aparams, specs["tokens"], specs["cache"],
                                extras)
    else:   # decode
        cshard = _named(mesh, partition.cache_specs(specs["cache"], rules, mesh=mesh))
        bshard = NamedSharding(mesh, partition.fit_spec(
            rules.spec("batch"), (shape.global_batch,), mesh))

        def serve_step(params, token_t, cache, pos):
            return bundle.model.decode_step(params, token_t, cache, pos)

        logits_shard = NamedSharding(mesh, partition.fit_spec(
            rules.spec("batch", "vocab_tp"),
            (shape.global_batch, cfg.vocab), mesh))
        jfn = jax.jit(
            serve_step,
            in_shardings=(pshard, bshard, cshard, bshard),
            out_shardings=(logits_shard, cshard),
            donate_argnums=(2,))
        with jax.set_mesh(mesh):
            lowered = jfn.lower(aparams, specs["token_t"], specs["cache"],
                                specs["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips(mesh), "kind": shape.kind,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
    }
    return lowered, compiled, meta


def analyse(compiled, meta, cfg, shape) -> dict:
    from repro.core import hlo_analysis
    mem = compiled.memory_analysis()
    mf = model_flops(cfg, shape)
    cost = hlo_analysis.analyze(compiled.as_text())   # parse once
    terms = roofline.RooflineTerms(
        flops_per_chip=cost.flops,
        hbm_bytes_per_chip=cost.bytes,
        wire_bytes_per_chip=cost.wire_bytes,
        collective_counts=dict(cost.collective_counts),
        model_flops_per_chip=mf / meta["chips"])
    ca = compiled.cost_analysis()
    legacy = roofline.RooflineTerms(
        flops_per_chip=float(ca.get("flops", 0.0)),
        hbm_bytes_per_chip=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_chip=0.0, collective_counts={},
        model_flops_per_chip=mf / meta["chips"])
    rec = dict(meta)
    rec["roofline"] = terms.as_dict()
    rec["roofline"]["dot_flops_per_chip"] = cost.dot_flops
    rec["roofline"]["collective_wire"] = {
        k: float(v) for k, v in cost.collective_wire.items()}
    rec["xla_costanalysis"] = {
        "flops_per_chip": legacy.flops_per_chip,
        "hbm_bytes_per_chip": legacy.hbm_bytes_per_chip,
        "note": "while bodies counted once (undercounts scans)",
    }
    if cost.warnings:
        rec["analyzer_warnings"] = cost.warnings[:10]
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            rec.setdefault("memory", {})[attr] = int(v)
    if "memory" in rec:
        per_chip = (rec["memory"].get("argument_size_in_bytes", 0)
                    + rec["memory"].get("temp_size_in_bytes", 0))
        rec["memory"]["per_chip_gib"] = round(per_chip / 2**30, 3)
    return rec


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str,
             tcfg: TrainConfig, rules: lanes.LogicalRules,
             tag: str = "baseline", verbose: bool = True) -> dict:
    cfg = registry.config(arch)
    shape = SHAPES[shape_name]
    ok, why = registry.cell_supported(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": True, "reason": why}
    else:
        try:
            lowered, compiled, meta = lower_cell(
                arch, shape_name, multi_pod=multi_pod, tcfg=tcfg,
                rules=rules)
            rec = analyse(compiled, meta, cfg, shape)
            if verbose:
                print(f"[{cell_id}] memory_analysis:",
                      compiled.memory_analysis())
                print(f"[{cell_id}] cost_analysis keys:",
                      sorted(compiled.cost_analysis().keys())[:12])
        except Exception as e:
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "failed": True, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
    rec["tag"] = tag
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{cell_id}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        r = rec.get("roofline", {})
        status = ("SKIP: " + rec["reason"] if rec.get("skipped")
                  else "FAIL: " + rec.get("error", "")
                  if rec.get("failed") else
                  f"dom={r['dominant']} frac={r['roofline_fraction']:.3f} "
                  f"c/m/w(ms)={1e3*r['compute_s']:.2f}/"
                  f"{1e3*r['memory_s']:.2f}/{1e3*r['collective_s']:.2f}")
        print(f"[{cell_id}] {status}", flush=True)
    return rec


def parse_rules(overrides: list[str]) -> lanes.LogicalRules:
    kw = {}
    for item in overrides or []:
        k, _, v = item.partition("=")
        kw[k] = tuple(v.split(",")) if v else None
    return lanes.with_rules(**kw) if kw else lanes.LogicalRules()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", action="append", default=None,
                   choices=list(registry.ARCH_NAMES), help="repeatable")
    p.add_argument("--shape", action="append", default=None,
                   choices=list(SHAPES))
    p.add_argument("--mesh", default="both",
                   choices=["single", "multi", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--tag", default="baseline")
    # hillclimb knobs
    p.add_argument("--reduction", default="gspmd",
                   choices=["gspmd", "hier", "hier_tree", "hier_ef8"])
    p.add_argument("--remat", default="full",
                   choices=["none", "full", "dots", "save_tp"])
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--no-zero1", action="store_true")
    p.add_argument("--moe-dispatch", default="global",
                   choices=["global", "local"],
                   help="MoE dispatch lowering (§Perf cell-2)")
    p.add_argument("--tp-reduce", default="auto",
                   choices=["auto", "bf16_dot", "bf16_scatter"],
                   help="TP-boundary reduction lowering (§Perf it4)")
    p.add_argument("--attn-impl", default="flash",
                   choices=["flash", "naive"],
                   help="ref attention lowering (naive = pre-§Perf baseline)")
    p.add_argument("--rule", action="append", default=None,
                   metavar="logical=mesh1,mesh2",
                   help="override a logical->mesh sharding rule")
    args = p.parse_args(argv)

    from repro.kernels import ops as _ops
    from repro.models import layers as _layers
    from repro.models import moe as _moe
    _ops.set_attn_impl(args.attn_impl)
    _layers.set_tp_reduce(args.tp_reduce)
    _moe.set_moe_dispatch(args.moe_dispatch)
    tcfg = TrainConfig(reduction=args.reduction, remat=args.remat,
                       microbatches=args.microbatches,
                       zero1=not args.no_zero1)
    rules = parse_rules(args.rule)
    archs = args.arch or list(registry.ARCH_NAMES)
    shapes = args.shape or list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_cell(
                    arch, shape, multi_pod=mp, out_dir=args.out,
                    tcfg=tcfg, rules=rules, tag=args.tag))
    n_ok = sum(1 for r in results
               if not r.get("failed") and not r.get("skipped"))
    n_skip = sum(1 for r in results if r.get("skipped"))
    n_fail = sum(1 for r in results if r.get("failed"))
    print(f"\ndry-run: {n_ok} compiled, {n_skip} skipped (documented), "
          f"{n_fail} FAILED")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
