import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell on
the production mesh with ShapeDtypeStruct inputs (no device allocation), and
record memory_analysis / cost_analysis / collective schedule for §Roofline.

The lines above MUST precede any other import (jax locks the platform and
the device count at first init): the 512 devices are host CPUs, so the
dry-run never takes an accelerator, even on a host that has one. Do not set
that flag globally — smoke tests and benches see the real host. The roofline
terms are computed against the peaks of :data:`TARGET_DEVICE_KIND`, the
chip the production mesh is made of.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
Results accumulate incrementally into --out (default results/dryrun.json).
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCHS, SHAPES, get_arch, get_shape, input_specs,
                           cell_applicable)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_train_step, make_serve_step,
                                init_train_state, TrainState)
from repro.dist.sharding import make_rules, param_shardings, cache_shardings
from repro.models import init_params, init_cache
from repro.models.transformer import forward
from repro.optim import adamw_init, OptState
from repro.roofline.analysis import analyze_compiled, model_flops

#: the chip of the production mesh (a TPU v5e pod), as jax names it
TARGET_DEVICE_KIND = "TPU v5 lite"


def _active_params(cfg, params_sds) -> int:
    """Params touched per token: everything except the embedding gather;
    for MoE, routed experts scaled by top_k/E."""
    import jax.tree_util as jtu
    total = 0
    for path, leaf in jtu.tree_leaves_with_path(params_sds):
        keys = [e.key for e in path if isinstance(e, jtu.DictKey)]
        if keys and keys[-1] == "embed":
            continue
        n = 1
        for s in leaf.shape:
            n *= s
        if "moe" in keys and "shared" not in keys and keys[-1] != "router":
            n = int(n * cfg.top_k / max(cfg.n_experts, 1))
        total += n
    if cfg.tie_embeddings:
        total += cfg.d_model * cfg.vocab
    return total


def _batch_shardings(specs: dict, rules):
    from repro.dist.sharding import fit_spec
    shardings = {}
    for name, sds in specs.items():
        spec = P(*((rules.dp if rules.dp else None,) +
                   (None,) * (sds.ndim - 1)))
        shardings[name] = NamedSharding(rules.mesh,
                                        fit_spec(spec, sds.shape, rules.mesh))
    return shardings


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               ca_k: int = 8):
    """Lower + compile one cell. Returns (lowered, compiled, meta)."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh)
    specs = input_specs(cfg, shape)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    params_sds = jax.eval_shape(
        lambda k: init_params(cfg, k), key_sds)
    p_sh = param_shardings(params_sds, rules)
    n_active = _active_params(cfg, params_sds)
    meta = dict(arch=arch_name, shape=shape_name,
                mesh="2x16x16" if multi_pod else "16x16",
                kind=shape.kind, n_active_params=n_active)

    if shape.kind == "train":
        state_sds = jax.eval_shape(
            lambda k: TrainState(params=init_params(cfg, k),
                                 opt=adamw_init(init_params(cfg, k))),
            key_sds)
        opt_sh = OptState(step=rules.replicated(),
                          m=p_sh, v=p_sh)
        state_sh = TrainState(params=p_sh, opt=opt_sh)
        step = make_train_step(cfg, rules, ca_k=ca_k, remat=True)
        jitted = jax.jit(step,
                         in_shardings=(state_sh, _batch_shardings(specs, rules)),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_sds, specs)

    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = forward(params, cfg, batch,
                                constrain=rules.constrain, last_only=True)
            return logits
        jitted = jax.jit(prefill_step,
                         in_shardings=(p_sh, _batch_shardings(specs, rules)))
        lowered = jitted.lower(params_sds, specs)

    else:  # decode
        B = shape.global_batch
        cache_sds = jax.eval_shape(
            lambda: init_cache(cfg, B, shape.seq_len,
                               enc_len=shape.seq_len
                               if cfg.family == "audio" else None))
        from repro.dist.sharding import fit_spec
        c_sh = cache_shardings(cache_sds, rules)
        tok_sh = NamedSharding(mesh, fit_spec(
            P(rules.dp if rules.dp else None, None), (B, 1), mesh))
        step = make_serve_step(cfg, rules)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, c_sh, tok_sh),
                         out_shardings=(tok_sh, None, c_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_sds, cache_sds, specs["tokens"])

    return lowered, meta, shape, cfg


def run_cell(arch_name, shape_name, multi_pod, ca_k=8):
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, reason = cell_applicable(cfg, shape)
    if not ok:
        return dict(arch=arch_name, shape=shape_name,
                    mesh="2x16x16" if multi_pod else "16x16",
                    status="skipped", reason=reason)
    t0 = time.time()
    try:
        lowered, meta, shape, cfg = lower_cell(arch_name, shape_name,
                                               multi_pod, ca_k)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        roof = analyze_compiled(compiled, TARGET_DEVICE_KIND)
        mf = model_flops(cfg, shape, meta["n_active_params"], shape.kind)
        chips = 512 if multi_pod else 256
        rec = dict(meta, status="ok", t_lower_s=round(t_lower, 1),
                   t_compile_s=round(t_compile, 1),
                   roofline=roof.as_dict(),
                   model_flops_total=mf,
                   model_flops_per_chip=mf / chips,
                   useful_flop_ratio=(mf / chips) / max(roof.flops, 1.0))
        print(f"OK   {arch_name:24s} {shape_name:12s} "
              f"{'2x16x16' if multi_pod else '16x16':8s} "
              f"lower {t_lower:6.1f}s compile {t_compile:6.1f}s "
              f"bottleneck={roof.bottleneck}", flush=True)
        return rec
    except Exception as e:
        traceback.print_exc()
        print(f"FAIL {arch_name} {shape_name} multi_pod={multi_pod}: {e}",
              flush=True)
        return dict(arch=arch_name, shape=shape_name,
                    mesh="2x16x16" if multi_pod else "16x16",
                    status="error", error=f"{type(e).__name__}: {e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="both")
    ap.add_argument("--ca-k", type=int, default=8)
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out.exists():
        results = json.loads(out.read_text())

    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    for arch in archs:
        for shape in shapes:
            for mp in pods:
                cell = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
                if cell in results and not args.force \
                        and results[cell].get("status") in ("ok", "skipped"):
                    continue
                results[cell] = run_cell(arch, shape, mp, args.ca_k)
                out.write_text(json.dumps(results, indent=1))

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
