"""Training smoke: one ``loss_fn`` + ``jax.grad`` step per model family
under the ambient kernel-backend policy.

CI runs this inside the ``REPRO_BACKEND`` tier-1 matrix: the ``=pallas`` leg
differentiates straight through the Pallas kernels (custom VJPs), so a
kernel landing without a working backward — or a registration that silently
reroutes training to XLA — fails fast here rather than deep inside a TPU
run. Under ``=pallas`` the script also asserts, via ``registry.select``,
that ``flash_attention`` and ``ssd`` really select their pallas impls inside
``grad_safe`` (no silent fallback).

    PYTHONPATH=src REPRO_BACKEND=pallas python -m repro.launch.grad_smoke
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.kernels import registry
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params, loss_fn


def _smoke_batch(cfg, key, batch: int, seq: int):
    tok = lambda n: jax.random.randint(key, (batch, n), 0, cfg.vocab)
    if cfg.family == "audio":
        return dict(enc_embeds=jax.random.normal(
                        key, (batch, seq, cfg.d_model), jnp.bfloat16),
                    tokens=tok(cfg.dec_len), labels=tok(cfg.dec_len))
    if cfg.family == "vlm":
        txt = seq - cfg.vision_patches
        return dict(vision_embeds=jax.random.normal(
                        key, (batch, cfg.vision_patches, cfg.d_model),
                        jnp.bfloat16),
                    tokens=tok(txt), labels=tok(txt))
    return dict(tokens=tok(seq), labels=tok(seq))


def _family_archs():
    """One (smallest-by-name) arch per family, deterministic order."""
    picked = {}
    for name in sorted(ARCHS):
        picked.setdefault(ARCHS[name].family, name)
    return [picked[f] for f in sorted(picked)]


def _assert_pallas_backward_selected():
    fa_args, fa_kw = registry.get_op("flash_attention").make_inputs(
        (1, 32, 4, 16, 32, 2))
    ssd_args, ssd_kw = registry.get_op("ssd").make_inputs((1, 32, 2, 8, 4))
    with registry.grad_safe():
        for op, args, kw in (("flash_attention", fa_args, fa_kw),
                             ("ssd", ssd_args, ssd_kw)):
            impl = registry.select(op, *args, **kw)
            if impl.backend != "pallas" or impl.vjp is None:
                raise SystemExit(
                    f"{op}: training would not trace the pallas backward "
                    f"(selected {impl.backend}, vjp={impl.vjp is not None})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    args = ap.parse_args(argv)
    use_compile_cache()

    backend = registry.resolved_backend()
    print(f"# grad smoke: backend={backend} "
          f"(policy={registry.policy()!r})")
    if backend == "pallas":
        _assert_pallas_backward_selected()

    key = jax.random.PRNGKey(0)
    failed = []
    for name in _family_archs():
        cfg = smoke_config(ARCHS[name])
        params = init_params(cfg, key)
        batch = _smoke_batch(cfg, key, args.batch, args.seq)
        t0 = time.time()
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch)))(params)
        gnorm = float(jnp.sqrt(sum(
            jnp.vdot(g, g).real for g in jax.tree.leaves(grads))))
        ok = np.isfinite(float(loss)) and np.isfinite(gnorm) and gnorm > 0
        print(f"{name:<18} family={cfg.family:<7} loss={float(loss):.4f} "
              f"gnorm={gnorm:.3e} dt={time.time() - t0:.1f}s "
              f"{'OK' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        return 1
    print("# all families differentiate under this backend")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
