"""The metric readers on a hand-made run record."""

import math

import pytest

from chipbench import costs, harness, spec, xtrace
from chipbench.stats import nearest_rank

SIZES = {"layers": 2, "d": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "ff": 128, "vocab": 256, "rope_theta": 1e4, "eps": 1e-5,
         "tied": False, "dtype": "bfloat16"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def req(rid, due, adm, first, fin, n, state="COMPLETED"):
    return harness.Req(rid, [1] * 10, n, due, adm, first, fin, state,
                       [0] * n)


def make_run(trace=None):
    cell = spec.Cell("c", {}, {"window_end": "drain"}, 1, (), (), {})
    reqs = [req("a", 0.0, 0.1, 0.5, 2.5, 5), req("b", 1.0, 1.5, 2.0, 4.0, 9),
            req("c", 3.0, None, None, None, 0, state="FAILED")]
    steps = [harness.Step(1.0, 4, [(10, 4), (11, 2)]),
             harness.Step(3.0, 2, [(14, 2)])]
    adm = [harness.Admission(0.1, 0.5, [10]),
           harness.Admission(1.5, 2.0, [10])]
    bursts = [("a", 1, 0.5), ("a", 4, 1.0), ("b", 1, 2.0), ("b", 2, 3.0),
              ("b", 8, 6.0)]
    return harness.Run(cell=cell, sizes=SIZES, peak=PEAK, seconds=5.0,
                       setup_s=12.0, setup_compile_s=3.0, window_compiles=0,
                       requests=reqs, bursts=bursts, steps=steps,
                       admissions=adm, trace=trace, trace_end_s=5.0)


def read(name, run):
    return spec.metric_reader(name)(run)


def test_host_clock_readers():
    run = make_run()
    assert read("output_tok_s", run) == pytest.approx(8 / 5.0)
    # a failed request is an infinite TTFT: the p90 of three is the worst
    assert read("ttft_p90_ms", run) == math.inf
    assert read("queue_wait_p90_ms.ttft", run) == math.inf
    assert read("prefill_ms.ttft", run) == pytest.approx(450.0)
    assert read("setup_s", run) == 12.0
    assert read("setup_compile_s", run) == 3.0
    assert read("decode_step_ms.ttft", run) is None  # no trace, nothing read


def test_nearest_rank():
    assert nearest_rank([3, 1, 2], 50) == 2
    assert nearest_rank(range(1, 11), 90) == 9
    assert nearest_rank([], 90) is None


def test_trace_readers():
    tr = xtrace.Summary(window_s=5.0, busy_s=4.0,
                        module_s={"macro": 0.06, "prefill": 0.5},
                        module_count={"macro": 2, "prefill": 2}, top_ops=[],
                        idle_gaps=[])
    run = make_run(tr)
    assert read("decode_step_ms.tput", run) == pytest.approx(60.0 / 6)
    assert read("device_idle_share.tput", run) == pytest.approx(20.0)
    least = sum(costs.decode_step_least_s(SIZES, s.contexts(j), PEAK)
                for s in run.steps for j in range(s.k))
    assert read("decode_roofline.tput", run) == pytest.approx(
        100 * least / 0.06)
    # 4 + 2 + 2 tokens decoded, each at its own context
    ctx = [11, 12, 13, 14, 12, 13, 15, 16]
    assert read("decode_mfu.ttft", run) == pytest.approx(
        100 * sum(costs.token_flops(SIZES, c) for c in ctx) / (0.06 * 197e12))


def test_step_contexts():
    s = harness.Step(0.0, 4, [(10, 4), (11, 2)])
    assert s.contexts(0) == [11, 12] and s.contexts(3) == [14]
