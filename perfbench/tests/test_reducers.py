"""Trace reductions, work counts, rates and tails, on small known inputs."""
import math

import pytest

from perfbench import costs, harness
from perfbench.devtrace import WINDOW, Op, Trace


def _trace():
    ms = 1_000_000
    ops = [Op("fusion.1", "fusion.1 | jit(solve)/gather", 0 * ms, 2 * ms, 0),
           Op("gram", "gram | pallas_call _gram_kernel", 1 * ms, 3 * ms, 0),
           Op("fusion.2", "fusion.2", 6 * ms, 2 * ms, 0),
           Op("gram", "gram | pallas_call _gram_kernel", 9 * ms, 5 * ms, 0)]
    host = [(WINDOW, 0, 10 * ms), ("bench.wait", 0, 5 * ms),
            ("bench.key_prep", 4 * ms, 2 * ms),
            ("bench.dispatch", 8 * ms, 1 * ms)]
    return Trace(ops=ops, host=host, t0=0, t1=10 * ms, n_chips=1)


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace()
    assert tr.busy_intervals(0) == [(0, 4_000_000), (6_000_000, 8_000_000),
                                    (9_000_000, 10_000_000)]
    assert tr.busy_s() == pytest.approx(7e-3)
    assert tr.idle_share() == pytest.approx(0.3)


def test_container_ops_count_as_busy_but_not_in_the_top_ops():
    tr = _trace()
    tr.ops.append(Op("while.3", "while.3 = (...) while(...)", 0, 10_000_000,
                     0))
    assert tr.gaps(0) == [] and tr.idle_share() == 0.0
    assert "while.3" not in [n for n, _ in tr.breakdown()["device_ops"]]


def test_gaps_are_labelled_by_the_covering_host_span():
    tr = _trace()
    assert tr.gaps(0) == [(4_000_000, 6_000_000), (8_000_000, 9_000_000)]
    bd = tr.breakdown()
    assert bd["idle_gaps"] == [["bench.key_prep", pytest.approx(2e-3)],
                               ["bench.dispatch", pytest.approx(1e-3)]]
    assert bd["device_ops"][0] == ["gram", pytest.approx(4e-3)]
    assert tr.host_seconds_outside_busy("bench.wait") == pytest.approx(1e-3)


def test_op_seconds_by_pattern_and_chip_average():
    tr = _trace()
    assert tr.op_seconds(r"^gram") == pytest.approx(4e-3)
    two = Trace(ops=tr.ops + [Op("gram", "gram _gram_kernel", 0, 2_000_000,
                                 1)],
                host=tr.host, t0=0, t1=10_000_000, n_chips=2)
    assert two.op_seconds(r"^gram") == pytest.approx(3e-3)


def test_gram_work_at_d54_is_unpadded():
    m = costs.lasso_sample_size(581_012, 0.1)
    assert m == 58_101
    assert costs.gram_flops(54, m) == 2 * 54 * 54 * 58_101
    assert costs.gram_bytes(54, m) == 4 * (54 * 58_101 + 54 * 54)
    # 27 FLOPs a byte: far under the v5e's 240, so bandwidth bounds it
    assert costs.gram_flops(54, m) / costs.gram_bytes(54, m) < 27.1
    flops = costs.lasso_solve_flops(54, 581_012, 256, 0.1)
    assert 9.1e10 < flops < 9.3e10


def test_internlm2_counts():
    p = costs.dense_lm_params(2048, 24, 16, 8, 128, 8192, 92544)
    total = sum(p.values())
    assert 1.88e9 < total < 1.90e9
    assert costs.kv_bytes_per_token(24, 8, 128) == 98_304
    assert costs.dense_lm_step_weight_bytes(p) == 2 * (
        p["layers"] + p["head"] + p["final_norm"])


def test_percentile_is_nearest_rank_over_every_value():
    v = list(range(1, 101))
    assert harness.percentile(v, 0.95) == 95
    assert harness.percentile([3.0], 0.95) == 3.0
    # a request that never finished counts as missing every limit
    assert harness.percentile([1.0] * 19 + [math.inf], 0.95) == 1.0
    assert harness.percentile([1.0] * 18 + [math.inf] * 2, 0.95) == math.inf


class _Engine:
    """Stands in for the engine: one slot, k tokens per step."""

    def __init__(self):
        from repro.serve.api import EngineStats, StreamDelta
        self.k, self.stats, self.D = 4, EngineStats(), StreamDelta
        self.pool = type("P", (), {"num_slots": 1})()
        self.scheduler, self.live = [], None

    def submit(self, req):
        self.scheduler.append(req)

    def stream_step(self):
        if self.live is None and self.scheduler:
            self.live, self.left = self.scheduler.pop(0), None
            self.left = self.live.max_new_tokens
        self.stats.syncs += 1
        n = min(self.k, self.left)
        self.left -= n
        done = self.left == 0
        d = self.D(id=self.live.id, tokens=[7] * n, done=done)
        if done:
            self.live = None
        return [d], []


def test_closed_loop_rates_count_every_token_of_the_window():
    from perfbench.drivers.serve import Loop, Traffic
    b = type("B", (), {"annotate": lambda self, n: _Null()})()
    tr = dict(block=4, prompt=dict(dist="uniform", lo=4, hi=4),
              output=dict(dist="uniform", lo=8, hi=8))
    loop = Loop(b, _Engine(), Traffic(tr, 1, 100))
    counts = dict(syncs=0, tokens_processed=0, kv_token_reads=0)
    tokens, _ = loop.run_for(0.05, True, counts)
    done = [r for r in loop.recs.values() if r.done is not None]
    assert tokens == sum(len(r.toks) for r in loop.recs.values())
    assert all(len(r.toks) == 8 for r in done)
    assert counts["syncs"] == loop.engine.stats.syncs
    # prompt 4 + 8 outputs: 11 cache writes per finished request
    assert counts["tokens_processed"] >= 11 * len(done)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
