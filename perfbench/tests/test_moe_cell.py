"""The MoE serving cell (``dsmoe16b.decode_sat``): its files load as the
contract asks, a sound run at a CPU size is correct, and the control (the
reference with int8 matmul operands) in the program's place is not."""
import json

from conftest import run_cell

from perfbench import harness

CELL = "dsmoe16b.decode_sat"
SPEC = json.loads((harness.REPO / "BENCHMARK.json").read_text())
METRICS = ("held_experts_roofline.serve_moe", "mfu.serve_moe",
           "hbm_share.serve_moe")


def tiny_moe(**traffic):
    """The cell's configuration and mix at a size the CPU runs in
    seconds: 1 dense and 2 MoE layers, 4 of 16 experts held, top-3."""
    ld = harness.load_cell(CELL)
    ld = dict(ld, end_to_end=[], per_layer=[])
    ld["config"] = dict(
        ld["config"], hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
        n_routed_experts=4, n_routed_experts_published=16,
        first_routed_expert=4, num_experts_per_tok=3,
        serve=dict(num_slots=4, max_len=128, page_size=16),
        reference_bucket=64)
    ld["traffic"] = dict(
        ld["traffic"], prompt=dict(dist="uniform", lo=4, hi=12),
        output=dict(dist="uniform", lo=8, hi=24), warm_seconds=0.5,
        trace_seconds=0.5, check_requests=3)
    ld["traffic"].update(traffic)
    ld["limits"] = dict(logit_gap_mean=5e-3)
    return ld


def test_cell_config_traffic_limits_and_metrics_load():
    cell = {w["name"]: w for w in SPEC["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    conf = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert conf["reduced"] == ["n_routed_experts"]
    ld = harness.load_cell(CELL)
    cfg = ld["config"]
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"]) \
        == (8, 64)
    assert set(conf["reduced"]) <= set(cfg["reduced"])
    assert ld["traffic"]["driver"] == "serve_moe"
    assert 0 < ld["limits"]["logit_gap_mean"] < 1
    got = {m["name"] for m in ld["end_to_end"]}
    assert got == {"setup_s", "out_tok_s", "tpot_p95_ms"}
    layer = {m["name"] for m in ld["per_layer"]}
    assert set(METRICS) | {"paged_attn_roofline.serve", "idle_share.serve",
                           "host_ms_per_sync.serve"} == layer
    for name in layer:
        assert (harness.BENCH / "metrics" / f"{name}.py").is_file()


def test_cell_arch_is_the_published_shape_at_the_share():
    from perfbench.drivers.serve_moe import arch_config
    a = arch_config(harness.load_cell(CELL)["config"])
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.head_dim) \
        == (28, 2048, 16, 16, 128)
    assert (a.n_experts, a.experts_held, a.first_expert, a.top_k) \
        == (64, 8, 0, 6)
    assert (a.moe_d_ff, a.dense_d_ff, a.n_shared_experts, a.vocab) \
        == (1408, 10944, 2, 102400)
    assert not a.norm_topk_prob and a.first_layer_dense
    assert a.norm_eps == 1e-6 and a.rope_theta == 1e4


def test_readers_find_nothing_without_a_trace():
    b = type("B", (), dict(trace=None, counts={}, config=harness.load_cell(
        CELL)["config"]))()
    for name in METRICS:
        assert harness.load_module(
            harness.BENCH / "metrics" / f"{name}.py").read(b, None) is None


def test_sound_moe_run_is_correct_and_counts_the_experts():
    import jax
    jax.clear_caches()
    runs = []
    res = run_cell(tiny_moe(), trace=1, patch=runs.append)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    c = runs[0].counts
    assert c["checked_requests"] == 3
    # 4 rows a step, top-3 of 16 experts, 4 held, 2 MoE layers
    steps = c["syncs"] * c["k"]
    assert 0 < c["moe_assignments"] <= steps * 2 * 4 * 3
    assert 0 < c["moe_experts_hit"] <= min(steps * 2 * 4,
                                           c["moe_assignments"])


def test_moe_control_fails_the_limit():
    # four MoE layers at wider widths and longer outputs, so that int8
    # error builds up over depth and the mean runs over 150 to 180 served
    # tokens; the checked requests are the window's first six submissions,
    # drawn from the seed, so their count does not depend on how fast the
    # CPU runs. On seeds 5 to 10 the program read 9.5e-6 to 1.74e-3 (a
    # routing choice that rounding flips moves a token's logits more than
    # rounding alone), the control 4.28e-3 to 1.17e-2
    ld = tiny_moe(check_requests=6, output=dict(dist="uniform", lo=16,
                                                hi=40))
    ld["config"] = dict(ld["config"], num_hidden_layers=5, hidden_size=512,
                        intermediate_size=2048, moe_intermediate_size=256,
                        vocab_size=4096)
    ld["limits"] = dict(logit_gap_mean=2.5e-3)
    for seed in (5, 6, 7):
        runs = []
        res = run_cell(ld, seed=seed, patch=lambda b: (
            setattr(b, "control", True), runs.append(b)))
        c = runs[0].counts
        prog = c["program_logit_gap_mean"]
        ctrl = res["checks"]["logit_gap_mean"]["value"]
        assert c["checked_requests"] == 6, c
        assert not res["correct"], (seed, prog, ctrl)
        assert prog <= ld["limits"]["logit_gap_mean"] < ctrl, (seed, prog,
                                                               ctrl)
