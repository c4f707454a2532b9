"""The span metric readers and the host-span reductions (``spanread``)."""

import time

import pytest

from chipbench import harness, spanread, spec, xtrace
from repro.serving.spans import Span

from test_chipbench_xtrace import xspace

SPAN_METRICS = ("queue_wait_behind_prefill_ms.ttft",
                "queue_wait_behind_macro_ms.ttft", "prefill_host_ms.ttft",
                "prefill_host_ms.tput", "prefill_useful_share.ttft",
                "prefill_useful_share.tput", "decode_slot_share.ttft",
                "decode_slot_share.tput", "setup_serve_s")


def req(rid, due, adm):
    done = adm is not None
    return harness.Req(rid, [1] * 10, 4, due, adm, adm and adm + 0.1,
                       adm and adm + 1.0,
                       "COMPLETED" if done else "FAILED", [0] * 4 * done)


def span(i, name, start, end, parent=None, **attrs):
    return Span(i, parent, name, start, end, attrs)


def make_run(spans):
    cell = spec.Cell("c", {}, {"window_end": "drain"}, 1, (), (), {})
    run = harness.Run(cell=cell, sizes={}, peak={}, seconds=5.0,
                      setup_s=12.0, setup_compile_s=0.0, window_compiles=0,
                      requests=[req("a", 0.0, 0.1), req("b", 0.2, 1.0),
                                req("c", 3.0, None)],
                      bursts=[], steps=[], admissions=[])
    if spans is not None:
        run.spans = spans
    return run


SPANS = [
    span(1, "serve/setup", -9.0, -1.0),
    # the warm-up's admission, before the window: not counted
    span(2, "serve/admit", -5.0, -4.0, rids=["_warmup"], rows=2,
         padded_len=64, useful_tokens=1, chunk=64),
    span(3, "serve/admit", 0.05, 0.5, rids=["a"], rows=2, padded_len=16,
         useful_tokens=10, chunk=16),
    span(4, "serve/admit/sync", 0.3, 0.45, parent=3),
    span(5, "serve/macro", 0.5, 0.85, rids=["a"], k=4, rows=2, emitted=3),
    # an admission round that admitted nothing: nobody's wait, no prefill
    span(8, "serve/admit", 0.86, 0.88),
    span(6, "serve/admit", 0.9, 1.3, rids=["b"], rows=2, padded_len=8,
         useful_tokens=6, chunk=8),
    span(7, "serve/admit/sync", 1.0, 1.25, parent=6),
]


def read(name, run):
    return spec.metric_reader(name)(run)


def test_span_readers():
    run = make_run(SPANS)
    # b waited 0.2-1.0: a's admission covers 0.2-0.5, a's macro-step
    # 0.5-0.85; a waited behind nothing
    assert read("queue_wait_behind_prefill_ms.ttft", run) == \
        pytest.approx(150.0)
    assert read("queue_wait_behind_macro_ms.ttft", run) == \
        pytest.approx(175.0)
    # (0.45 - 0.15 + 0.4 - 0.25) / 2
    assert read("prefill_host_ms.tput", run) == pytest.approx(225.0)
    assert read("prefill_useful_share.ttft", run) == \
        pytest.approx(100 * 16 / 48)
    assert read("decode_slot_share.tput", run) == pytest.approx(37.5)
    assert read("setup_serve_s", run) == pytest.approx(8.0)


@pytest.mark.parametrize("spans", [None, []])
def test_span_readers_without_spans(spans):
    run = make_run(spans)
    assert all(read(name, run) is None for name in SPAN_METRICS
               if not name.startswith("queue_wait"))
    if spans is None:  # a program with no recorder
        assert read("queue_wait_behind_macro_ms.ttft", run) is None


def test_on_window():
    (s,) = spanread.on_window([span(1, "serve/macro", 10.5, 11.0)], 10.0)
    assert (s.start, s.end) == (0.5, 1.0)


def host_plane(events, plane_id=98):
    """A text-proto host plane holding ``events`` = [(name, start_ns,
    dur_ns)] on one line."""
    ids = {n: i + 1 for i, n in enumerate(sorted({e[0] for e in events}))}
    evs = "".join(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                  f"{s * 1000} duration_ps: {d * 1000} }}\n"
                  for n, s, d in events)
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    return (f'planes {{ id: {plane_id} name: "/host:CPU"\n  lines {{ id: 7 '
            f'name: "engine" timestamp_ns: 0\n{evs}  }}\n{meta}}}\n')


HOST = [("serve/macro", 200, 700), ("serve/macro/emit", 350, 100),
        ("serve/intake", 950, 30), ("unrelated", 0, 50)]
DEVICE = [(xtrace.MODULES, "jit_macro_fn", 0, 300),
          (xtrace.OPS, "fusion.1", 0, 300),
          (xtrace.MODULES, "jit_macro_fn", 500, 100),
          (xtrace.OPS, "fusion.1", 500, 100)]


def test_host_spans_and_idle_by_span():
    from jax.profiler import ProfileData

    text = xspace(DEVICE, [(xtrace.OPEN, 0), (xtrace.CLOSE, 1000)]) + \
        host_plane(HOST)
    pd = ProfileData.from_text_proto(text)
    host = spanread.host_spans(pd)
    assert sorted((e.name, e.start_ns, e.end_ns) for e in host) == [
        ("serve/intake", 950, 980), ("serve/macro", 200, 900),
        ("serve/macro/emit", 350, 450)]
    devices, marks = xtrace.from_profile(pd)
    idle = spanread.idle_by_span(devices, marks, host)
    # idle 300-500 and 600-1000
    assert idle == pytest.approx({"serve/macro": 400e-9,
                                  "serve/macro/emit": 100e-9,
                                  "serve/intake": 30e-9, "none": 70e-9})
    assert sum(idle.values()) == pytest.approx(600e-9)
    assert spanread.leaf_share(idle, host) == pytest.approx(100 * 130 / 600)
    assert spanread.lines(make_run(None), idle, host)[0].endswith(
        "in a leaf span 21.7%")


def test_innermost_names_the_deepest_open_span():
    evs = [xtrace.Event("serve/admit", 0, 100),
           xtrace.Event("serve/admit/prepare", 10, 40),
           xtrace.Event("serve/admit/sync", 40, 90)]
    assert [(e.name, e.start_ns, e.end_ns)
            for e in spanread.innermost(evs)] == [
        ("serve/admit", 0, 10), ("serve/admit/prepare", 10, 40),
        ("serve/admit/sync", 40, 90), ("serve/admit", 90, 100)]


@pytest.mark.parametrize("name", ["tiny-batch", "tiny-chat"])
def test_traced_tiny_run_reports_every_span_metric(tiny, name):
    """A traced run passes the program's recorder to ``Runtime.serve``: the
    run has its spans on the window clock, every span reader returns a
    number, and the cell's span metrics are in the result."""
    bench, d = tiny
    cell = spec.load_cell(name, d, bench=bench, bench_dir=d)
    out = harness.run_cell(cell, seed=2**31 + 7, seconds=2.0, trace=True,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    run = out["run"]
    assert run.spans and any(s.start >= 0 for s in run.spans)
    values = {m: read(m, run) for m in SPAN_METRICS}
    assert all(v is not None for v in values.values()), values
    assert 0 < values["prefill_useful_share.ttft"] <= 100
    assert 0 < values["decode_slot_share.tput"] <= 100
    assert 0 < values["setup_serve_s"] < run.setup_s
    mine = {m.name for m in cell.per_layer}
    spans = mine & set(SPAN_METRICS)
    assert spans and spans <= set(out["metrics"])
    (line,) = spanread.lines(run, None)
    assert line.startswith("serve/macro spans in the traced window")
