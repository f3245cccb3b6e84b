"""repro.obs: spans, metrics, sync audit, and their threading through the
serve engine, the kernel registry, the launch CLIs, and the benchmark
harness.

The load-bearing claim is the sync-accounting one: ``obs.sync_audit()``
counts host<->device round-trip epochs at the jax/numpy interception
boundary, with no help from the engine's own bookkeeping — and for the real
continuous-batching engine the audited count must equal
``EngineStats.syncs`` *bitwise*, for k in {1, 4, 16}, for an attention
family and an SSM family. That is the serving-side measurement of the
paper's CA-k claim: k fused steps per round trip, verified against the
metal instead of trusted.
"""
import glob
import json
import re
import sys
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/

from repro import obs
from repro.configs import get_arch, smoke_config
from repro.kernels import registry
from repro.models import init_params
from repro.serve import Engine, Request
from repro.serve.api import EngineStats

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with obs disabled and empty buffers
    (metric handles survive reset, so module-level instrumentation keeps
    working)."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _profile(log_dir):
    """A JAX profile of the enclosed block, host spans and device ops, with
    no Python tracer."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _host_events(log_dir, names=None):
    """(name, start ns, end ns, args) of the host events in the profile
    under ``log_dir`` (only ``names`` when given), outermost first at equal
    starts."""
    from jax.profiler import ProfileData
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_span_nesting_and_chrome_trace_roundtrip(tmp_path):
    """Spans are profiler annotations: they land in a JAX profile, nested
    as they were opened, with their arguments."""
    obs.enable()
    with _profile(tmp_path):
        with obs.span("outer", phase="test"):
            assert obs.current() == "outer"
            with obs.span("inner"):
                assert obs.current() == "inner"
            obs.instant("marker", n=3)
        assert obs.current() == ""
    ev = {e[0]: e for e in _host_events(tmp_path,
                                        {"outer", "inner", "marker"})}
    assert set(ev) == {"outer", "inner", "marker"}
    _, o0, o1, oargs = ev["outer"]
    _, i0, i1, _ = ev["inner"]
    _, m0, m1, margs = ev["marker"]
    assert o0 <= i0 <= i1 <= o1
    assert i1 <= m0 <= m1 <= o1           # the instant: an empty span
    assert oargs == {"phase": "test"} and margs == {"n": 3}


def test_disabled_spans_are_shared_noop_and_record_nothing(tmp_path):
    """With no profile running a span records nothing: it only keeps the
    name stack, which it leaves empty; instants also need obs enabled."""
    assert not obs.enabled()
    with obs.span("a"):
        assert obs.current() == "a"
        with obs.span("b", x=1):
            assert obs.current() == "b"
        obs.instant("never")
    assert obs.current() == ""
    with _profile(tmp_path):
        with obs.span("kept"):
            obs.instant("disabled")
    names = {e[0] for e in _host_events(tmp_path)}
    assert "kept" in names
    assert not names & {"a", "b", "never", "disabled"}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_label_aggregation_and_disabled_noop():
    c = obs.counter("test_requests_total", "help text")
    c.inc(reason="eos")                 # disabled: must not record
    assert c.total() == 0.0
    obs.enable()
    c.inc(reason="eos")
    c.inc(2.0, reason="eos")
    c.inc(reason="length")
    assert c.value(reason="eos") == 3.0
    assert c.value(reason="length") == 1.0
    assert c.total() == 4.0
    g = obs.gauge("test_depth")
    g.set(7, kind="q")
    g.set(3, kind="q")                  # gauges overwrite, not accumulate
    assert g.value(kind="q") == 3.0


def test_histogram_buckets_and_prometheus_text_parses():
    obs.enable()
    h = obs.histogram("test_latency_seconds", "lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v, op="x")
    assert h.count(op="x") == 3 and h.sum(op="x") == pytest.approx(5.55)
    text = obs.to_prometheus()
    assert '# TYPE test_latency_seconds histogram' in text
    assert 'test_latency_seconds_bucket{le="0.1",op="x"} 1' in text
    assert 'test_latency_seconds_bucket{le="1",op="x"} 2' in text
    assert 'test_latency_seconds_bucket{le="+Inf",op="x"} 3' in text
    assert 'test_latency_seconds_count{op="x"} 3' in text
    # every non-comment line is a well-formed prometheus sample
    sample = re.compile(
        r'^[A-Za-z_:][A-Za-z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+$')
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), line


def test_jsonl_export_and_snapshot():
    obs.enable()
    obs.counter("test_c").inc(5, op="a")
    obs.histogram("test_h").observe(0.25)
    rows = [json.loads(l) for l in obs.metrics.to_jsonl().splitlines()]
    counters = [r for r in rows if r["name"] == "test_c"]
    assert counters == [dict(name="test_c", kind="counter",
                             labels={"op": "a"}, value=5.0)]
    snap = obs.metrics_snapshot()
    assert snap['test_c{op="a"}'] == 5.0
    assert snap["test_h_count"] == 1


def test_registry_rejects_kind_mismatch():
    obs.counter("test_kind_clash")
    with pytest.raises(TypeError, match="already registered as counter"):
        obs.histogram("test_kind_clash")


# ---------------------------------------------------------------------------
# sync audit (unit)
# ---------------------------------------------------------------------------

def test_sync_audit_epoch_coalescing_and_uninstall():
    f = jax.jit(lambda x: x * 2)
    x = jax.numpy.arange(8, dtype=jax.numpy.float32)
    np.asarray(f(x))                      # compile outside the audit
    with obs.sync_audit() as a:
        obs.mark_dispatch("t")
        y = f(x)
        np.asarray(y)                     # opens epoch 1
        np.asarray(y)                     # coalesces: same epoch
        obs.mark_dispatch("t")
        y2 = f(x)
        jax.block_until_ready(y2)         # opens epoch 2
        float(np.asarray(y2)[0])
    assert a.syncs == 2
    assert a.dispatches == 2
    assert a.transfers >= 3
    assert a.block_until_ready == 1
    # patches removed: reads outside any audit are invisible
    np.asarray(f(x))
    assert a.transfers >= 3 and not hasattr(np.asarray, "__wrapped__")


def test_sync_audit_ignores_host_only_reads():
    with obs.sync_audit() as a:
        np.asarray([1, 2, 3])             # host data: not a device read
        np.asarray(np.ones(4))
    assert a.syncs == 0 and a.transfers == 0


# ---------------------------------------------------------------------------
# sync audit vs the real engine (the acceptance criterion)
# ---------------------------------------------------------------------------

def _engine_requests(cfg, n):
    rng = np.random.RandomState(0)
    return [Request(id=f"r{i}",
                    prompt=rng.randint(0, cfg.vocab, size=3).tolist(),
                    max_new_tokens=8) for i in range(n)]


def _audited_drain(cfg, params, k):
    eng = Engine(params, cfg, num_slots=4, max_len=32, k=k, max_prompt=4)
    with obs.sync_audit() as audit:
        eng.run(_engine_requests(cfg, 4))
    return audit, eng.stats


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-780m"])
def test_engine_sync_audit_bitwise_equals_stats(arch):
    """The audited host round-trip count equals EngineStats.syncs exactly,
    and the CA-k relation holds: raising k divides the sync count by k (up
    to the final partial block)."""
    cfg = smoke_config(get_arch(arch))
    params = init_params(cfg, KEY)
    ks = (1, 4, 16) if arch == "internlm2-1.8b" else (1, 16)
    syncs = {}
    for k in ks:
        audit, stats = _audited_drain(cfg, params, k)
        assert audit.syncs == stats.syncs, \
            f"{arch} k={k}: audit {audit.as_dict()} vs stats {stats.syncs}"
        assert audit.dispatches == stats.syncs   # one marked dispatch/round
        assert stats.steps == stats.syncs * k
        syncs[k] = stats.syncs
    for k in ks[1:]:
        # k-step fusion amortizes: syncs(k)*k covers the same work as
        # syncs(1) plus at most one partial block of slack
        assert 0 <= syncs[k] * k - syncs[1] < k, (syncs, k)


def test_engine_audit_attributes_syncs_to_decode_span():
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, KEY)
    obs.enable()
    eng = Engine(params, cfg, num_slots=2, max_len=32, k=4, max_prompt=4)
    with obs.sync_audit() as audit:
        eng.run(_engine_requests(cfg, 2))
    assert audit.syncs == eng.stats.syncs
    # every sync lands inside the fetch span
    assert audit.by_span == {"serve.fetch": audit.syncs}


PHASES = ("serve.admit", "serve.dispatch", "serve.fetch", "serve.complete",
          "serve.defrag")


def test_profiled_engine_round_has_one_span_per_phase(tmp_path):
    """A profiled drain of the blocking loop: each round is an admit, a
    dispatch, a fetch and a complete span, in that order and side by side,
    one of each per sync; the defrag span opens only in rounds that
    compact the pool, after the complete."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, KEY)
    eng = Engine(params, cfg, num_slots=4, max_len=32, k=4, max_prompt=4,
                 page_size=4)
    # two of four requests retire after the first block: the holes left in
    # the slot and page spans make the engine compact both
    def reqs():
        return [Request(id=f"r{i}", prompt=[1 + i, 2, 3],
                        max_new_tokens=1 if i % 2 == 0 else 9)
                for i in range(4)]
    eng.run(reqs())                           # compile outside the profile
    before = eng.stats.defrags + eng.stats.page_defrags
    with _profile(tmp_path):
        eng.run(reqs())
    moved = eng.stats.defrags + eng.stats.page_defrags - before
    ev = _host_events(tmp_path, PHASES)
    names = [e[0] for e in ev]
    assert eng.stats.syncs and moved
    rounds = [n for n in names if n != "serve.defrag"]
    syncs = rounds.count("serve.fetch")
    assert rounds == list(PHASES[:4]) * syncs
    assert 1 <= names.count("serve.defrag") <= moved
    for a, b in zip(ev, ev[1:]):          # no phase nests in another
        assert a[2] <= b[1]
    for i, n in enumerate(names):
        if n == "serve.defrag":
            assert names[i - 1] == "serve.complete"
    dispatch = [e[3] for e in ev if e[0] == "serve.dispatch"]
    assert all(a.get("k") == 4 and 1 <= a.get("live") <= 4
               for a in dispatch)


def test_decode_block_hlo_carries_named_scopes():
    """The compiled decode block's op metadata names the part of the step
    each op belongs to; the block's own bookkeeping is ``decode.state``."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, KEY)
    eng = Engine(params, cfg, num_slots=2, max_len=32, k=4, max_prompt=4,
                 page_size=4)
    from repro.serve.sampling import SlotSampling
    samp = SlotSampling(temperature=eng._temp, top_p=eng._top_p,
                        top_k=eng._top_k, key=eng.pool.slot_keys)
    text = eng._block.lower(
        eng.params, eng.state, eng._prompt_buf, eng._prompt_len,
        eng._max_new, eng._active, samp, eng.pool.tables).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("decode.attn", "decode.kv_write", "decode.mlp",
                  "decode.head", "decode.state"):
        assert any(scope in n for n in names), scope
    # the K/V scatter into the pool is part of attention
    assert all("decode.attn/" in n for n in names if "decode.kv_write" in n)


def test_engine_work_counts_match_their_closed_form():
    """``EngineStats.tokens_processed`` / ``kv_token_reads``, counted by the
    engine from the lengths it fetches, equal their closed form over the
    retired requests: a request of prompt ``P`` and ``n`` outputs writes
    ``L = P + n - 1`` tokens at positions ``0 .. L-1``, and the token at
    ``p`` attends over ``p + 1`` positions, ``L (L + 1) / 2`` in all. More
    requests than slots, so rows are admitted and retired mid-stream."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, KEY)
    eng = Engine(params, cfg, num_slots=4, max_len=64, k=4, page_size=8)
    rng = np.random.default_rng(1234)
    reqs = [Request(id=str(i),
                    prompt=rng.integers(1, cfg.vocab, rng.integers(4, 13))
                    .tolist(),
                    max_new_tokens=int(rng.integers(8, 25)))
            for i in range(10)]
    obs.enable()
    out = eng.run(reqs)
    assert len(out) == len(reqs)
    lens = [r.prompt_len + len(r.tokens) - 1 for r in out]
    assert eng.stats.tokens_processed == sum(lens)
    assert eng.stats.kv_token_reads == sum(n * (n + 1) // 2 for n in lens)
    assert f"tokens_processed={sum(lens)}" in eng.stats.summary()
    r = obs.REGISTRY
    assert r.get("repro_serve_tokens_processed_total").total() == sum(lens)
    assert r.get("repro_serve_kv_token_reads_total").total() == \
        eng.stats.kv_token_reads


# ---------------------------------------------------------------------------
# registry counters + autotune schema versioning
# ---------------------------------------------------------------------------

def test_registry_dispatch_and_fallback_counters():
    @registry.register("obs_test_op", "pallas",
                       supports=lambda *a, **k: False)
    def _p(x):                                      # pragma: no cover
        return x

    @registry.register("obs_test_op", "xla")
    def _x(x):
        return x + 1

    obs.enable()
    with registry.use("pallas"):
        out = registry.dispatch("obs_test_op", 1)   # pallas declines -> xla
    assert out == 2
    disp = obs.REGISTRY.get("repro_kernel_dispatch_total")
    fall = obs.REGISTRY.get("repro_kernel_fallback_total")
    assert disp.value(op="obs_test_op", backend="xla") == 1
    assert fall.value(op="obs_test_op", requested="pallas") == 1
    with registry.use("xla"):
        registry.dispatch("obs_test_op", 1)
    assert disp.value(op="obs_test_op", backend="xla") == 2
    assert fall.total() == 1                        # direct hit: no fallback


def test_registry_fallback_warns_once_per_op():
    """A fallback is never silent: the first one per (op, requested
    backend) warns; later ones only count."""
    @registry.register("warn_test_op", "pallas",
                       supports=lambda *a, **k: False)
    def _p(x):                                      # pragma: no cover
        return x

    @registry.register("warn_test_op", "xla")
    def _x(x):
        return x + 1

    with registry.use("pallas"):
        with pytest.warns(UserWarning, match="'warn_test_op': the pallas"):
            assert registry.dispatch("warn_test_op", 1) == 2
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert registry.dispatch("warn_test_op", 2) == 3


def test_autotune_stale_schema_is_not_a_miss(tmp_path, monkeypatch):
    """A cache entry from another schema version is skipped by dispatch
    (its params may not mean what the current impl's tunables mean) and
    counted as ``stale`` — distinguishable from a genuine miss."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    key = registry._cache_key("gram", "pallas", (16, 64))
    lookups = obs.REGISTRY.get("repro_autotune_lookup_total")
    Xs = jax.random.normal(KEY, (16, 64))
    obs.enable()
    try:
        # legacy v1 entry: no schema_version field
        cache.write_text(json.dumps(
            {key: {"params": {"bd": 8, "bm": 64}, "us": 1.0}}))
        registry.reload_tuned()
        with registry.use("pallas"):
            registry.dispatch("gram", Xs)
        assert lookups.value(op="gram", outcome="stale") >= 1
        assert lookups.value(op="gram", outcome="hit") == 0
        # same entry stamped with the current schema: consumed as a hit
        cache.write_text(json.dumps(
            {key: {"params": {"bd": 8, "bm": 64}, "us": 1.0,
                   "schema_version": registry.SCHEMA_VERSION,
                   "device": "cpu"}}))
        registry.reload_tuned()
        with registry.use("pallas"):
            registry.dispatch("gram", Xs)
        assert lookups.value(op="gram", outcome="hit") >= 1
    finally:
        registry.reload_tuned()


def test_autotune_writes_current_schema(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    registry.reload_tuned()
    try:
        results = registry.autotune("gram", [(16, 64)], backends=["pallas"],
                                    iters=1, warmup=0)
        for entry in results.values():
            assert entry["schema_version"] == registry.SCHEMA_VERSION
            assert entry["device"] and entry["device"] != "unknown"
    finally:
        registry.reload_tuned()


# ---------------------------------------------------------------------------
# engine metrics + EngineStats derived properties
# ---------------------------------------------------------------------------

def test_engine_metrics_mirror_stats():
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, KEY)
    obs.enable()
    eng = Engine(params, cfg, num_slots=2, max_len=32, k=4, max_prompt=4)
    eng.run(_engine_requests(cfg, 3))
    s = eng.stats
    r = obs.REGISTRY
    assert r.get("repro_serve_syncs_total").total() == s.syncs
    assert r.get("repro_serve_steps_total").total() == s.steps
    assert r.get("repro_serve_tokens_total").total() == s.tokens_out
    assert r.get("repro_serve_prefill_tokens_total").total() == \
        s.prefill_tokens
    reqs = r.get("repro_serve_requests_total")
    assert reqs.value(reason="length") == s.retired
    assert r.get("repro_serve_ttft_seconds").count() == s.admitted
    assert r.get("repro_serve_latency_seconds").count() == s.retired
    assert r.get("repro_sched_queue_depth") is not None
    text = obs.to_prometheus()
    assert f"repro_serve_syncs_total {s.syncs}" in text


def test_engine_stats_derived_properties_and_summary():
    s = EngineStats(syncs=4, steps=16, tokens_out=12, admitted=3, retired=3,
                    prefix_hits=2, prefix_tokens=10)
    assert s.tokens_per_sync == 3.0
    assert s.prefix_hit_rate == pytest.approx(2 / 3)
    line = s.summary()
    assert line.startswith("summary: ")
    assert "tokens_per_sync=3.00" in line and "prefix_hit_rate=0.67" in line
    empty = EngineStats()
    assert empty.tokens_per_sync == 0.0 and empty.prefix_hit_rate == 0.0
    assert "prefix_hit_rate" not in empty.summary()


# ---------------------------------------------------------------------------
# launch CLI: --metrics / --trace-out (the in-process CI metrics-smoke leg)
# ---------------------------------------------------------------------------

def test_serve_cli_metrics_and_trace_export(tmp_path, capsys):
    from repro.launch.serve import main as serve_main
    mfile, tdir = tmp_path / "metrics.prom", tmp_path / "profile"
    serve_main(["--preset", "tiny", "--batch", "2", "--requests", "2",
                "--new-tokens", "8", "--k", "4",
                "--metrics", str(mfile), "--trace-out", str(tdir)])
    stdout = capsys.readouterr().out
    stats_syncs = int(re.search(r"stats: syncs=(\d+)", stdout).group(1))
    text = mfile.read_text()
    prom_syncs = int(re.search(
        r"^repro_serve_syncs_total (\d+)$", text, re.M).group(1))
    assert prom_syncs == stats_syncs
    assert "# TYPE repro_serve_ttft_seconds histogram" in text
    # one profile: the engine's phases on the host, a Perfetto trace
    names = {e[0] for e in _host_events(tdir, PHASES)}
    assert set(PHASES[:4]) <= names
    assert glob.glob(str(tdir / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
    assert "summary: " in stdout
    # the CLI disabled obs on exit and left no residue for later runs
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# benchmark harness: sentinel files + regression gate
# ---------------------------------------------------------------------------

def test_bench_run_writes_sentinel_on_suite_failure(tmp_path, monkeypatch):
    import benchmarks.run as brun
    fake = tmp_path / "fake_bench_suite.py"
    fake.write_text(
        "from benchmarks.common import emit\n"
        "def run():\n"
        "    emit('fake/row', 12.5, 'x=1')\n"
        "    raise RuntimeError('boom')\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(brun, "SUITES", {"kernels": "fake_bench_suite"})
    bench_dir = tmp_path / "bench"
    with pytest.raises(SystemExit):
        brun.main(["--only", "kernels", "--bench-dir", str(bench_dir)])
    records = json.loads((bench_dir / "BENCH_kernels.json").read_text())
    # the rows emitted before the crash survive, plus one sentinel
    assert [r["name"] for r in records] == ["fake/row", "kernels/ERROR"]
    assert records[0]["us_per_call"] == 12.5
    assert records[1]["us_per_call"] == brun.ERROR_SENTINEL
    assert "RuntimeError: boom" in records[1]["derived"]


def test_bench_compare_gates_on_regression_and_sentinels(tmp_path):
    from benchmarks.compare import main as cmp_main
    base = [dict(suite="serve", name="a", us_per_call=100.0, derived=""),
            dict(suite="serve", name="b", us_per_call=100.0, derived="")]
    ok = [dict(suite="serve", name="a", us_per_call=110.0, derived=""),
          dict(suite="serve", name="b", us_per_call=90.0, derived=""),
          dict(suite="serve", name="new_row", us_per_call=5.0, derived="")]
    regressed = [dict(suite="serve", name="a", us_per_call=120.0, derived=""),
                 dict(suite="serve", name="b", us_per_call=100.0, derived="")]
    sentinel = [dict(suite="serve", name="serve/ERROR", us_per_call=-1.0,
                     derived="error=RuntimeError: boom")]

    def write(name, recs):
        p = tmp_path / name
        p.write_text(json.dumps(recs))
        return str(p)

    b = write("base.json", base)
    assert cmp_main([write("ok.json", ok), b, "--threshold", "0.15"]) == 0
    assert cmp_main([write("bad.json", regressed), b,
                     "--threshold", "0.15"]) == 1
    assert cmp_main([write("died.json", sentinel), b]) == 1
    # a sentinel in the BASELINE is treated as absent, not a failure
    assert cmp_main([write("ok2.json", ok),
                     write("base_dead.json", base + sentinel)]) == 0


def test_bench_emit_embeds_obs_snapshot(monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "RECORDS", [])
    common.set_suite("test")
    common.emit("plain", 1.0)
    assert "obs" not in common.RECORDS[-1]
    obs.enable()
    obs.counter("test_bench_counter").inc(3)
    common.emit("with_obs", 2.0, metrics={"syncs": 7})
    rec = common.RECORDS[-1]
    assert rec["metrics"] == {"syncs": 7.0}
    assert rec["obs"]["test_bench_counter"] == 3.0
