"""Serve driver: requests through ``repro.serve.Engine`` in a closed loop.

Every slot is kept busy: a new request is submitted as each retires.
Traffic keys:

- ``prompt``, ``output``: length distributions, ``{"dist": "uniform",
  "lo", "hi"}``.
- ``block``: lengths are drawn in blocks of this many requests at fixed
  quantiles, each block in an order drawn from the seed, so every seed
  offers the same work.
- ``warm_seconds``: the mix run in set-up, after every admission size has
  been warmed; ``drain_seconds``: how long past the window the requests due
  in it may take; ``check_requests``: finished requests compared with the
  reference (the longest and others drawn from the seed);
  ``trace_seconds``: the profiled part of the window with ``--trace 1``.

Every request is greedy. A request's time per output token is (finish -
first token) / (tokens - 1), each the end of the engine step that delivered
it. The window ends with the first step that returns ``--seconds`` after it
began. Tails are over every request due in the window: requests still in
flight then are waited for, with the load kept on, for up to
``drain_seconds``, and one that never finishes counts as failed and as
missing every limit.
``out_tok_s`` is the tokens delivered by the window's steps over the
window's length.
"""
from __future__ import annotations

import math
import sys
import time
from typing import Dict, List

import numpy as np

from perfbench.harness import percentile
from perfbench.references import dense_lm as ref


def _inv_cdf(spec: dict, q: np.ndarray) -> np.ndarray:
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["lo"] + q * (spec["hi"] - spec["lo"] + 1)
    return np.clip(np.floor(x), spec["lo"], spec["hi"]).astype(int)


class Traffic:
    """Requests of one mix, from the seed: (prompt, max_new)."""

    def __init__(self, tr: dict, seed: int, vocab: int):
        self.tr, self.vocab = tr, vocab
        self.rng = np.random.RandomState(seed % 2 ** 32)
        self.buf: List[tuple] = []

    def _fill(self):
        B = int(self.tr["block"])
        q = (np.arange(B) + 0.5) / B
        plen = _inv_cdf(self.tr["prompt"], q)[self.rng.permutation(B)]
        out = _inv_cdf(self.tr["output"], q)[self.rng.permutation(B)]
        for p, o in zip(plen, out):
            prompt = self.rng.randint(0, self.vocab, size=int(p)).tolist()
            self.buf.append((prompt, int(o)))

    def next(self):
        if not self.buf:
            self._fill()
        return self.buf.pop(0)


class Rec:
    __slots__ = ("prompt", "max_new", "first", "done", "toks", "in_window",
                 "blocks", "L")

    def __init__(self, prompt, max_new, in_window):
        self.prompt, self.max_new = prompt, max_new
        self.in_window = in_window
        self.first = self.done = None
        self.toks: List[int] = []
        self.blocks = 0
        self.L = 0               # context length the driver's bookkeeping
                                 # gives: tokens written to the cache


def arch_config(cfg: dict):
    from repro.configs.base import ArchConfig
    z = ref.dims(cfg)
    return ArchConfig(name=cfg["name"], family="dense",
                      n_layers=z["n_layers"], d_model=z["d"],
                      n_heads=z["n_heads"], n_kv_heads=z["n_kv_heads"],
                      d_ff=z["d_ff"], vocab=z["vocab"],
                      head_dim=z["head_dim"], rope_theta=z["rope_theta"],
                      norm_eps=z["eps"],
                      tie_embeddings=bool(cfg["tie_word_embeddings"]))


class Loop:
    """The load generator and the driver's bookkeeping around the engine."""

    def __init__(self, b, engine, traffic: Traffic):
        from repro.serve import Request
        self.b, self.engine, self.traffic = b, engine, traffic
        self.Request = Request
        self.recs: Dict[str, Rec] = {}
        self.order: List[str] = []      # unfinished ids, in submit order
        self.slots = engine.pool.num_slots
        self.k = engine.k
        self.seq = 0

    def offer(self, in_window):
        """Fill every free slot with a new request."""
        while len(self.order) < self.slots:
            prompt, max_new = self.traffic.next()
            rid = f"r{self.seq}"
            self.seq += 1
            self.recs[rid] = Rec(prompt, max_new, in_window)
            self.order.append(rid)
            with self.b.annotate("bench.submit"):
                self.engine.submit(self.Request(id=rid, prompt=prompt,
                                                max_new_tokens=max_new))

    def step(self, counts=None):
        """One engine round; returns (tokens delivered, end time)."""
        syncs = self.engine.stats.syncs
        with self.b.annotate("bench.step"):
            deltas, _ = self.engine.stream_step()
        now = time.perf_counter()
        queued = len(self.engine.scheduler)
        in_slot = self.order[:len(self.order) - queued]
        got = 0
        for d in deltas:
            r = self.recs[d.id]
            if d.tokens:
                if r.first is None:
                    r.first = now
                r.toks.extend(d.tokens)
                got += len(d.tokens)
            if d.done:
                r.done = now
        kv_reads = processed = 0
        for rid in in_slot:
            r = self.recs[rid]
            r.blocks += 1
            L = len(r.prompt) - 1 + len(r.toks) if r.toks else \
                min(r.blocks * self.k, len(r.prompt) - 1)
            processed += L - r.L
            kv_reads += (L * (L + 1) - r.L * (r.L + 1)) // 2
            r.L = L
        self.order = [rid for rid in self.order if self.recs[rid].done is None]
        if counts is not None:
            counts["syncs"] += self.engine.stats.syncs - syncs
            counts["tokens_processed"] += processed
            counts["kv_token_reads"] += kv_reads
        return got, now

    def run_for(self, seconds, in_window, counts=None, until=None):
        t_end = time.perf_counter() + seconds
        tokens = 0
        while True:
            self.offer(in_window)
            got, now = self.step(counts)
            tokens += got
            if now >= t_end or (until is not None and until()):
                return tokens, now


def warm(b, engine, tr):
    """Every admission size once, both of the engine's defrags, then the mix
    itself, untimed."""
    from repro.serve import Request
    n_slots = engine.pool.num_slots
    for n in range(1, n_slots + 1):
        engine.run([Request(id=f"w{n}.{i}", prompt=[1 + i], max_new_tokens=1)
                    for i in range(n)])
    # every other request retires after its first token: the holes left in
    # the slot span and in the page span make the engine compact both, as it
    # does when the mix fragments the pool inside the window
    engine.run([Request(id=f"d{i}", prompt=[1 + i],
                        max_new_tokens=1 if i % 2 == 0 else 3 * engine.k)
                for i in range(n_slots)])
    loop = Loop(b, engine, Traffic(tr, b.seed + 1, engine.cfg.vocab))
    loop.run_for(float(tr["warm_seconds"]), False)
    return loop


def run(b) -> None:
    from repro.dist.sharding import make_rules
    from repro.launch.mesh import make_host_mesh
    from repro.serve import Engine
    cfg, tr = b.config, b.traffic
    params = ref.make_params(b.seed, cfg)
    arch = arch_config(cfg)
    engine = Engine(params, arch, rules=make_rules(make_host_mesh()),
                    **cfg["serve"])
    warm_loop = warm(b, engine, tr)
    warm_defrags = engine.stats.defrags + engine.stats.page_defrags
    b.setup_done()

    loop = Loop(b, engine, Traffic(tr, b.seed, arch.vocab))
    # the window starts in steady state, every slot busy
    loop.recs, loop.order = warm_loop.recs, warm_loop.order
    loop.seq = warm_loop.seq
    counts = dict(syncs=0, tokens_processed=0, kv_token_reads=0)
    t0 = time.perf_counter()
    tok_traced = 0
    if b.tracing:
        with b.traced():
            tok_traced, _ = loop.run_for(float(tr["trace_seconds"]), True,
                                         counts)
    left = b.seconds - (time.perf_counter() - t0)
    tokens, t1 = loop.run_for(max(left, 0.0), True)
    tokens += tok_traced
    b.window_done(t0, t1)
    window = t1 - t0
    due = [r for r in loop.recs.values() if r.in_window]
    limit = t1 + float(tr["drain_seconds"])
    loop.run_for(float(tr["drain_seconds"]), False, until=lambda: all(
        r.done is not None for r in due) or time.perf_counter() > limit)
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
          f"{b.counts['defrags']} after", file=sys.stderr)

    # ---- the check: the program's state freed, the reference over a sample
    # of finished requests, the longest among them
    del engine, loop, warm_loop
    import gc
    gc.collect()
    check(b, params, cfg, done)


def check(b, params, cfg, done: List[Rec]) -> None:
    rng = np.random.RandomState((b.seed + 3) % 2 ** 32)
    done = sorted(done, key=lambda r: -len(r.toks))
    n = min(int(b.traffic["check_requests"]), len(done))
    pick = [done[0]] + [done[i] for i in sorted(
        rng.choice(np.arange(1, len(done)), size=n - 1, replace=False))]
    g = read_gaps(params, cfg, pick)
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
    g = np.concatenate(gaps)
    return dict(widest=float(g.max()), mean=float(g.mean()),
                flips=int((g > 0).sum()), served=int(g.size))
