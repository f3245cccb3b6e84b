"""Serving driver: thin CLI over the continuous-batching engine
(``repro.serve``), with the classic whole-batch single-shot loop kept as
``--engine off`` for parity testing.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
      --preset tiny --batch 4 --new-tokens 32 --k 4

Engine mode drains a synthetic request stream through ``repro.serve.Engine``
(k decode steps per host sync). Classic mode decodes one fixed batch with a
host round-trip per token. Both report compile time and steady-state
throughput separately — jit compile used to leak into the classic path's
per-step number.

Sampling: ``--temperature/--top-p/--top-k/--sample-seed`` attach a
``SamplingParams`` to every synthetic request (default: greedy argmax).
``--stream`` switches the drain to ``Engine.stream`` and prints each
request's token deltas as k-blocks retire — tokens surface with one block
of latency, at the same one-sync-per-k-tokens schedule.

Paged extras: ``--kv-dtype int8`` stores pageable K/V as int8 codes with
f32 row/head scales (about double the resident capacity at the same pool
bytes); ``--n`` fans every synthetic request into n sampled streams that
share its prompt pages, each stream seeded with ``fold_in_seed(seed, i)``.
:func:`check_paged_logits` compares cached decode through a page pool with
a full forward over the same tokens.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.autotune_cli import (add_autotune_args, plan_shapes,
                                       run_autotune)
from repro.launch.mesh import make_host_mesh
from repro.launch.obs_cli import add_obs_args, obs_begin, obs_end
from repro.launch.steps import make_serve_step
from repro.dist.sharding import make_rules
from repro.models import init_params, init_cache
from repro.models.transformer import decode_step, forward, prefill_audio_cache
from repro.serve import Engine, PagedCachePool, Request, SamplingParams

#: bound on the relative L2 error, per position, of cached paged-decode
#: logits against a full forward over the same tokens. Both paths carry bf16
#: activations (unit roundoff 2^-9) through every layer, in different orders,
#: and on a TPU f32 matmuls run in bf16 passes unless the precision says
#: otherwise: on a v5e, internlm2-1.8b's full forward at default precision is
#: 2.0e-2 from the same forward at "highest", and paged decode 1.96e-2. A
#: wrong page or head mapping moves logits by O(1).
LOGIT_RTOL = 3e-2


def _synthetic_requests(cfg, n: int, max_prompt: int, new_tokens: int,
                        enc_len: int, seed: int = 0, sampling=None,
                        fanout: int = 1):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, max_prompt + 1))
        prompt = rng.randint(0, cfg.vocab, size=plen).tolist()
        enc = rng.randn(enc_len, cfg.d_model).astype(np.float32) \
            if cfg.family == "audio" else None
        sp = None
        if sampling is not None:
            # distinct per-request seeds derived from the CLI seed
            sp = dataclasses.replace(sampling, seed=(sampling.seed or 0) + i)
        reqs.append(Request(id=f"req-{i}", prompt=prompt,
                            max_new_tokens=new_tokens, enc_embeds=enc,
                            sampling=sp, n=fanout))
    return reqs


def _cli_sampling(args):
    if args.temperature <= 0.0:
        return None
    return SamplingParams(temperature=args.temperature, top_p=args.top_p,
                          top_k=args.top_k, seed=args.sample_seed)


def serve_stream(cfg, engine, reqs, args):
    """Streamed drain: print token deltas as each k-block retires."""
    t0 = time.perf_counter()
    n_deltas = 0
    for d in engine.stream(reqs):
        n_deltas += 1
        if d.done:
            r = d.response
            print(f"  {r.id} += {d.tokens} [finish={r.finish_reason} "
                  f"total={len(r.tokens)}]", flush=True)
        else:
            print(f"  {d.id} += {d.tokens}", flush=True)
    dt = time.perf_counter() - t0
    s = engine.stats
    print(f"streamed {s.tokens_out} tokens across {n_deltas} deltas in "
          f"{dt:.2f} s (incl. compile); syncs={s.syncs} "
          f"(k={args.k}: {s.tokens_out / max(s.syncs, 1):.1f} tok/sync)")
    print(f"stats: syncs={s.syncs} steps={s.steps} tokens_out={s.tokens_out} "
          f"retired={s.retired} shed={s.shed} defrags={s.defrags} "
          f"occupancy={s.occupancy:.2f}")
    print(s.summary())
    return engine


def serve_engine(cfg, rules, args):
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(params, cfg, rules=rules, num_slots=args.batch,
                    max_len=args.max_len, k=args.k,
                    max_prompt=min(16, args.max_len // 2),
                    enc_len=args.max_len if cfg.family == "audio" else None,
                    page_size=args.page_size or None,
                    kv_dtype=args.kv_dtype,
                    prefix_cache=args.prefix_cache,
                    overlap=args.overlap)
    reqs = _synthetic_requests(cfg, args.requests or 2 * args.batch,
                               min(16, args.max_len // 2), args.new_tokens,
                               args.max_len, sampling=_cli_sampling(args),
                               fanout=args.n)
    if args.stream:
        print(f"arch={cfg.name} engine=on stream=on slots={args.batch} "
              f"k={args.k} requests={len(reqs)} "
              f"temperature={args.temperature}")
        return serve_stream(cfg, engine, reqs, args)
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    responses = engine.step()            # first block: jit compile dominates
    compile_s = time.perf_counter() - t0
    warm_toks = engine.stats.tokens_out
    t0 = time.perf_counter()
    responses += engine.run()
    dt = time.perf_counter() - t0
    s = engine.stats
    steady_toks = s.tokens_out - warm_toks
    steady_steps = (s.syncs - 1) * args.k
    print(f"arch={cfg.name} engine=on slots={args.batch} k={args.k} "
          f"requests={len(reqs)} new_tokens={args.new_tokens}")
    print(f"compile+first-block: {compile_s:.2f} s")
    if steady_steps and dt > 0:
        print(f"steady-state: {steady_toks / dt:.1f} tok/s "
              f"({dt / steady_steps * 1e3:.2f} ms/step, "
              f"{dt / (s.syncs - 1) * 1e3:.2f} ms/sync at k={args.k})")
    print(f"stats: syncs={s.syncs} steps={s.steps} tokens_out={s.tokens_out} "
          f"prefill_tokens={s.prefill_tokens} retired={s.retired} "
          f"shed={s.shed} defrags={s.defrags} occupancy={s.occupancy:.2f}")
    print(s.summary())
    if engine.paged:
        print(f"paged: page_size={engine.pool.page_size} "
              f"pages={engine.pool.num_pages} "
              f"kv_dtype={'int8' if engine.pool.quantized else 'f32'} "
              f"page_bytes={engine.pool.page_bytes()} "
              f"prefix_hits={s.prefix_hits} prefix_tokens={s.prefix_tokens} "
              f"cow_copies={s.cow_copies} page_defrags={s.page_defrags}")
    for r in sorted(responses, key=lambda r: r.id)[:2]:
        print(f"  {r.id}: finish={r.finish_reason} tokens={r.tokens[:16]}")
    return engine


def check_paged_logits(params, cfg, *, page_size: int, kv_dtype: str = "f32",
                       n_tokens: int = 32, seed: int = 0) -> float:
    """Worst per-position relative L2 error of the logits of ``n_tokens``
    seeded tokens decoded one at a time through a fresh page pool, against
    one full forward over the same tokens at the highest matmul precision."""
    pool = PagedCachePool(cfg, 1, n_tokens, page_size=page_size,
                          kv_dtype=kv_dtype)
    slot = pool.allocate("logit-check")
    pool.reserve(slot, n_tokens)
    table = jnp.asarray(pool.tables)
    toks = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab, (1, n_tokens)), jnp.int32)
    step = jax.jit(lambda p, c, t, pos: decode_step(
        p, cfg, c, t, positions=pos, page_table=table))
    cache, got = pool.make_cache(), []
    for i in range(n_tokens):
        logits, cache = step(params, cache, toks[:, i:i + 1],
                             jnp.full((1,), i, jnp.int32))
        got.append(logits[0, 0].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: forward(p, cfg, dict(tokens=t))[0])(
            params, toks)[0].astype(jnp.float32)
    err = (jnp.linalg.norm(jnp.stack(got) - want, axis=-1)
           / jnp.linalg.norm(want, axis=-1))
    return float(err.max())


def serve_classic(cfg, rules, args):
    """Whole-batch greedy decode, one host round trip per token."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, args.batch, args.max_len, enc_len=args.max_len)
    if cfg.family == "audio":
        enc = jax.random.normal(jax.random.PRNGKey(1),
                                (args.batch, args.max_len, cfg.d_model),
                                jnp.bfloat16)
        cache = jax.jit(lambda p, c, e: prefill_audio_cache(p, cfg, c, e))(
            params, cache, enc)

    serve = jax.jit(make_serve_step(cfg, rules))
    tok = jnp.zeros((args.batch, 1), jnp.int32)
    # first step pays jit compile: time it separately so the steady-state
    # numbers aren't diluted (and the step count matches the token count)
    t0 = time.perf_counter()
    tok, _, cache = serve(params, cache, tok)
    jax.block_until_ready(tok)
    compile_s = time.perf_counter() - t0

    seqs = [tok]
    steps = args.new_tokens - 1
    t0 = time.perf_counter()
    for _ in range(steps):
        tok, _, cache = serve(params, cache, tok)
        seqs.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    out = jnp.concatenate(seqs, axis=1)
    print(f"arch={cfg.name} engine=off batch={args.batch} "
          f"new_tokens={args.new_tokens}")
    print(f"compile+first-step: {compile_s:.2f} s")
    if steps and dt > 0:
        print(f"steady-state: {args.batch * steps / dt:.1f} tok/s "
              f"({dt / steps * 1e3:.2f} ms/step over {steps} timed steps)")
    for b in range(min(args.batch, 2)):
        print(f"  seq[{b}]: {out[b, :16].tolist()} ...")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--preset", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots / classic batch size")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--k", type=int, default=4,
                    help="decode steps per host sync (engine mode)")
    ap.add_argument("--requests", type=int, default=0,
                    help="synthetic request count (default 2*batch)")
    ap.add_argument("--engine", choices=["on", "off"], default="on",
                    help="off: classic per-token whole-batch loop")
    ap.add_argument("--stream", action="store_true",
                    help="engine mode: print per-request token deltas as "
                         "k-blocks retire (Engine.stream)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass (1.0 disables)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 disables)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base seed for per-request sampling streams")
    ap.add_argument("--page-size", type=int, default=0,
                    help="engine mode: tokens per KV page (0 = whole-row "
                         "slot cache; token streams identical either way)")
    ap.add_argument("--kv-dtype", choices=["f32", "int8"], default="f32",
                    help="engine mode, with --page-size: int8 stores "
                         "pageable K/V as int8 codes + f32 row/head scales "
                         "(~2x resident capacity at matched pool bytes)")
    ap.add_argument("--n", type=int, default=1,
                    help="engine mode: fan each synthetic request into n "
                         "sampled streams sharing its prompt pages (stream "
                         "i seeds with fold_in_seed(seed, i))")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="engine mode, with --page-size: reuse radix-trie "
                         "shared prompt-prefix pages across requests and "
                         "skip their prefill steps")
    ap.add_argument("--overlap", action="store_true",
                    help="engine mode: double-buffer the host loop — "
                         "dispatch each k-block before blocking on the "
                         "previous one (tokens identical; hidden_syncs / "
                         "host_blocked stats report the effect)")
    add_autotune_args(ap)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch)
    cfg = smoke_config(arch) if args.preset == "tiny" else arch
    if args.autotune:
        # decode geometry: q length 1 against the full KV horizon
        run_autotune(plan_shapes(cfg, batch=args.batch, seq_q=1,
                                 seq_kv=args.max_len,
                                 page_size=args.page_size or None,
                                 max_len=args.max_len))
    rules = make_rules(make_host_mesh())
    observing = obs_begin(args)
    try:
        if args.engine == "on":
            return serve_engine(cfg, rules, args)
        return serve_classic(cfg, rules, args)
    finally:
        obs_end(args, observing)


if __name__ == "__main__":
    main()
