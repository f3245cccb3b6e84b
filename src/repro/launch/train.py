"""Training driver: host-mesh training with the CA gradient-sync schedule,
fault-tolerant runner, async checkpointing, restartable data pipeline.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --preset tiny --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.autotune_cli import (add_autotune_args, plan_shapes,
                                       run_autotune)
from repro.launch.mesh import make_host_mesh
from repro.launch.obs_cli import add_obs_args, obs_begin, obs_end
from repro.launch.steps import make_train_step, init_train_state, TrainState
from repro.dist.sharding import make_rules, param_shardings
from repro.dist.fault_tolerance import TrainingRunner, FailureSource
from repro.optim import OptState
from repro.data.synthetic import TokenStream


def build(args):
    arch = get_arch(args.arch)
    if args.preset == "tiny":
        cfg = smoke_config(arch)
        batch, seq = 8, 64
    elif args.preset == "100m":
        cfg = arch.scaled(n_layers=6, d_model=1024,
                          n_heads=8, n_kv_heads=max(arch.n_kv_heads // 4, 1),
                          head_dim=128, d_ff=4096, vocab=32000)
        batch, seq = max(args.ca_k, 8), 512
    else:
        cfg = arch
        batch, seq = 8 * args.ca_k, 1024
    return cfg, batch, seq


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--preset", choices=["tiny", "100m", "full"],
                    default="tiny")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ca-k", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject node failures at these steps (FT demo)")
    ap.add_argument("--log-every", type=int, default=10)
    add_autotune_args(ap)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    use_compile_cache()
    observing = obs_begin(args)

    cfg, batch, seq = build(args)
    if args.autotune:
        # training geometry, forward AND backward tunables (bwd winners are
        # cached under separate <op>+bwd keys; pallas-only, so an XLA host
        # reports the forward entries and skips the rest)
        run_autotune(plan_shapes(cfg, batch=batch, seq_q=seq, seq_kv=seq),
                     grad=True)
    mesh = make_host_mesh()
    rules = make_rules(mesh)

    def step_builder(mesh_):
        rules_ = make_rules(mesh_)
        step = make_train_step(cfg, rules_, ca_k=args.ca_k,
                               peak_lr=args.lr, warmup=10,
                               total_steps=args.steps, remat=True)
        params_sds = jax.eval_shape(
            lambda k: init_train_state(cfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_sh = param_shardings(params_sds.params, rules_)
        state_sh = TrainState(params=p_sh, opt=OptState(
            step=rules_.replicated(), m=p_sh, v=p_sh))
        return jax.jit(step, in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,)), state_sh

    def data_factory(start_step):
        stream = TokenStream(batch=batch, seq=seq, vocab=cfg.vocab, seed=0,
                             start_step=start_step)
        def gen():
            for item in stream:
                yield dict(tokens=jnp.asarray(item["tokens"]),
                           labels=jnp.asarray(item["labels"]))
        return iter(gen())

    runner = TrainingRunner(
        step_builder, mesh, data_factory,
        lambda: init_train_state(cfg, jax.random.PRNGKey(0)),
        args.ckpt_dir, ckpt_every=args.ckpt_every,
        failure_source=FailureSource(args.fail_at))

    t0 = time.time()
    try:
        runner.run(args.steps)
    finally:
        obs_end(args, observing)
    dt = time.time() - t0
    for m in runner.metrics_log[::args.log_every]:
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}")
    if runner.metrics_log:
        last = runner.metrics_log[-1]
        print(f"step {last['step']:5d}  loss {last['loss']:.4f}  (final)")
    else:
        print(f"checkpoint in {args.ckpt_dir} already at step "
              f"{args.steps}; nothing to do")
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s), restarts={runner.restarts}")
    return runner


if __name__ == "__main__":
    main()
