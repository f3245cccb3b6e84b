"""Serve driver for a mixture-of-experts LM at one chip's share of the
experts: the closed loop of ``drivers/serve.py`` (its ``Traffic``,
``Loop`` and ``warm``), with the MoE architecture and the reference of
``references/moe_lm.py``.

Besides ``serve.py``'s counts, the traced window's engine counts
``moe_assignments`` ((token, held expert) pairs computed) and
``moe_experts_hit`` ((step, layer, held expert) triples with a token) go
to the per-layer metrics.

The check compares the first ``check_requests`` requests submitted after
the window began (their prompts and lengths are the seed's first draws),
each drained to its end: which requests are checked, and how many, depends
on the seed alone, not on how many steps the window held.
"""
from __future__ import annotations

import math
import sys
import time
from typing import List

import numpy as np

from perfbench.drivers.serve import Loop, Rec, Traffic, warm
from perfbench.harness import percentile
from perfbench.references import moe_lm as ref


def arch_config(cfg: dict):
    from repro.configs.base import ArchConfig
    z = ref.dims(cfg)
    return ArchConfig(name=cfg["name"], family="moe",
                      n_layers=z["n_layers"], d_model=z["d"],
                      n_heads=z["n_heads"], n_kv_heads=z["n_kv_heads"],
                      d_ff=z["d_ff"], vocab=z["vocab"],
                      head_dim=z["head_dim"], rope_theta=z["rope_theta"],
                      norm_eps=z["eps"],
                      tie_embeddings=bool(cfg["tie_word_embeddings"]),
                      n_experts=z["n_experts"], top_k=z["top_k"],
                      n_shared_experts=z["n_shared"], moe_d_ff=z["moe_ff"],
                      norm_topk_prob=z["norm_topk_prob"],
                      first_layer_dense=z["n_dense"] == 1,
                      dense_d_ff=z["d_ff"],
                      first_expert=z["first_expert"],
                      n_experts_held=z["n_held"])


def run(b) -> None:
    from repro.dist.sharding import make_rules
    from repro.launch.mesh import make_host_mesh
    from repro.serve import Engine
    cfg, tr = b.config, b.traffic
    arch = arch_config(cfg)              # before the weights: fails fast
    params = ref.make_params(b.seed, cfg)
    engine = Engine(params, arch, rules=make_rules(make_host_mesh()),
                    **cfg["serve"])
    warm_loop = warm(b, engine, tr)
    warm_defrags = engine.stats.defrags + engine.stats.page_defrags
    b.setup_done()

    loop = Loop(b, engine, Traffic(tr, b.seed, arch.vocab))
    # the window starts in steady state, every slot busy
    loop.recs, loop.order = warm_loop.recs, warm_loop.order
    loop.seq = first = warm_loop.seq
    counts = dict(syncs=0, tokens_processed=0, kv_token_reads=0)
    st = engine.stats
    t0 = time.perf_counter()
    tok_traced = 0
    if b.tracing:
        moe0 = st.moe_assignments, st.moe_experts_hit
        with b.traced():
            tok_traced, _ = loop.run_for(float(tr["trace_seconds"]), True,
                                         counts)
        counts.update(moe_assignments=st.moe_assignments - moe0[0],
                      moe_experts_hit=st.moe_experts_hit - moe0[1])
    left = b.seconds - (time.perf_counter() - t0)
    tokens, t1 = loop.run_for(max(left, 0.0), True)
    tokens += tok_traced
    b.window_done(t0, t1)
    window = t1 - t0
    due = [r for r in loop.recs.values() if r.in_window]
    checked = [f"r{i}" for i in range(first, first
                                      + int(tr["check_requests"]))]
    limit = t1 + float(tr["drain_seconds"])
    loop.run_for(float(tr["drain_seconds"]), False, until=lambda: all(
        r.done is not None for r in due + [loop.recs.get(i) for i in checked
                                           if i in loop.recs])
        and all(i in loop.recs for i in checked)
        or time.perf_counter() > limit)
    b.read_memory()
    b.counts.update(counts, k=engine.k, slots=engine.pool.num_slots,
                    warm_defrags=warm_defrags,
                    defrags=engine.stats.defrags + engine.stats.page_defrags
                    - warm_defrags)

    done = [r for r in due if r.done is not None]
    b.attempted, b.failed = len(due), len(due) - len(done)
    # a request that never finished misses every limit
    tpot = [(r.done - r.first) / (len(r.toks) - 1)
            if r.done is not None else math.inf
            for r in due if r.done is None or len(r.toks) > 1]
    b.e2e.update(out_tok_s=tokens / window,
                 tpot_p95_ms=percentile(tpot, .95) * 1e3)
    print(f"window {window:.3f} s: {len(due)} requests due, {len(done)} "
          f"finished, {tokens} tokens; defrags {warm_defrags} in set-up, "
          f"{b.counts['defrags']} after; {engine.stats.summary()}",
          file=sys.stderr)

    # ---- the check: the program's state freed, the reference over the
    # first requests submitted in the window; one that never finished is
    # left out, and counts as failed if it was due
    pick = [loop.recs[i] for i in checked
            if i in loop.recs and loop.recs[i].done is not None]
    del engine, loop, warm_loop
    import gc
    gc.collect()
    check(b, params, cfg, pick)


def check(b, params, cfg, pick: List[Rec]) -> None:
    g = read_gaps(params, cfg, pick)
    b.counts.update(checked_requests=len(pick), checked_tokens=g["served"])
    print(f"reference: {len(pick)} requests, {g['served']} served tokens; "
          f"widest gap {g['widest']:.6f}, mean gap {g['mean']:.3e}, "
          f"tokens not the reference's first {g['flips']}", file=sys.stderr)
    if b.control:
        # the control in the program's place: its first choices are judged
        # as the program's served tokens are
        c = read_gaps(params, cfg, pick, True)
        print(f"control: widest gap {c['widest']:.6f}, mean gap "
              f"{c['mean']:.3e}", file=sys.stderr)
        b.counts.update(program_logit_gap_mean=g["mean"],
                        program_widest_gap=g["widest"],
                        program_flips=g["flips"],
                        control_widest_gap=c["widest"],
                        control_flips=c["flips"])
        g = c
    b.check("logit_gap_mean", g["mean"], b.limit("logit_gap_mean"))


def read_gaps(params, cfg, recs: List[Rec], control: bool = False) -> dict:
    """Gaps by which each served token's reference logit (or, with
    ``control``, that of the control's first choice) lies below the
    reference's best at its position: the widest, the mean over every
    served token, and how many are not zero."""
    import jax.numpy as jnp
    shape = ref.shape_key(cfg)
    bucket = int(cfg.get("reference_bucket", 256))
    gaps = []
    for r in recs:
        seq = list(r.prompt) + list(r.toks)
        S = -(-len(seq) // bucket) * bucket
        toks = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
        best, at_next, at_ctrl = (np.asarray(a, np.float64) for a in
                                  ref.readings(params, toks, shape=shape,
                                               control=control))
        lo, hi = len(r.prompt) - 1, len(seq) - 1
        gaps.append(best[lo:hi] - (at_ctrl if control else at_next)[lo:hi])
    # no request to compare misses the limit
    g = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    return dict(widest=float(g.max()), mean=float(g.mean()),
                flips=int((g > 0).sum()),
                served=sum(int(x.size) for x in gaps))
