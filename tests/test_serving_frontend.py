"""Multi-process serving front end: correctness anchors.

* TokenStream semantics: burst accumulation, TTFT stamping on the first
  non-empty burst, terminal idempotence
* attaching a stream adds ZERO host syncs and changes no tokens (the
  engine publishes only at boundaries it already synchronized on)
* serve_ipc is a real decision site: both ops (workers, coalesce) ledger
  predicted rows, overrides pin verdicts, measurements attach
* one multi-process equivalence run — over two KV page sizes —
  token-identical to the in-process engine, with the emission transcript
  detokenizing exactly the engine's tokens
* crash drills: a dead emission worker fails in-flight requests typed and
  leaves the engine drained + reusable; dead intake workers turn routed
  submissions into typed failures, never a crashed serve
* intake workers validate: invalid submissions come back typed
* within-group prefix sharing: a multi-slot admission group is split so
  the shared-prefix hit rate no longer depends on 1-slot serialization
* the idle loop sleeps TO the next arrival (computed), with the pinned
  virtual clock jumping instead of spinning
"""

import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Runtime, set_default_runtime, synthetic_trace
from repro.serving import (
    ContinuousServeEngine,
    FrontendConfig,
    Request,
    ServingFrontend,
    TokenStream,
)
from repro.serving.scheduler import ServeScheduler

PROMPT_LEN = 7
MAX_NEW = 6
MAX_LEN = PROMPT_LEN + MAX_NEW
ARCH = "tinyllama-1.1b"


@pytest.fixture(autouse=True)
def _fresh_runtime():
    set_default_runtime(Runtime())
    yield
    set_default_runtime(None)


def _build(key=0):
    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(key))
    return cfg, model, params


def _prompts(cfg, b, p=PROMPT_LEN, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (b, p)).astype(np.int32)


# ---------------------------------------------------------------------------
# TokenStream semantics
# ---------------------------------------------------------------------------


def test_token_stream_bursts_ttft_and_terminal_idempotence():
    s = TokenStream()
    s.publish("a", (), done=False, t=0.5)       # empty burst: no TTFT yet
    assert s.first_token_s("a") is None
    s.publish("a", (1, 2), done=False, t=1.0)
    s.publish("a", (3,), done=True, t=2.0)
    s.publish("a", (9,), done=True, t=3.0)      # after terminal: no-op
    assert s.tokens("a") == [1, 2, 3]
    assert s.is_done("a")
    assert s.first_token_s("a") == 1.0          # first NON-EMPTY burst
    assert s.published_events == 3
    assert s.published_tokens == 3
    assert s.rids() == ["a"]
    assert [e.done for e in s.events("a")] == [False, False, True]


# ---------------------------------------------------------------------------
# In-process streaming: zero added syncs, token-complete
# ---------------------------------------------------------------------------


def test_stream_adds_zero_syncs_and_streams_every_token():
    cfg, model, params = _build()
    prompts = _prompts(cfg, 3)

    def reqs():
        return [Request(f"r{i}", prompts[i], MAX_NEW) for i in range(3)]

    plain = ContinuousServeEngine(model, params, n_slots=2, max_len=MAX_LEN,
                                  eos_id=0)
    rep0 = plain.run(reqs(), now_fn=lambda: 0.0)
    stream = TokenStream()
    streaming = ContinuousServeEngine(model, params, n_slots=2,
                                      max_len=MAX_LEN, eos_id=0,
                                      stream=stream)
    rep1 = streaming.run(reqs(), now_fn=lambda: 0.0)

    assert rep1.host_syncs == rep0.host_syncs   # streaming cost no syncs
    by_rid = {r.rid: r for r in rep1.requests}
    for i in range(3):
        rid = f"r{i}"
        assert np.array_equal(rep1.output(rid, MAX_NEW),
                              rep0.output(rid, MAX_NEW))
        assert stream.tokens(rid) == [int(t) for t in by_rid[rid].tokens]
        assert stream.is_done(rid)
        assert stream.first_token_s(rid) is not None
        assert by_rid[rid].ttft_s is not None
    assert rep1.streamed_tokens == sum(len(r.tokens) for r in rep1.requests)
    assert rep1.stream_events >= 3
    assert set(rep1.ttft_percentiles()) == {"ttft_p50", "ttft_p95",
                                            "ttft_p99"}


# ---------------------------------------------------------------------------
# serve_ipc: the eleventh decision site
# ---------------------------------------------------------------------------


def test_serve_ipc_decision_sites_ledger_and_override():
    cfg = get_config(ARCH).reduced()
    rt = Runtime()
    sch = ServeScheduler(cfg, rt.engine, max_len=MAX_LEN)
    w, dec_w = sch.serve_ipc_workers(8, msg_bytes=512, prompt_len=PROMPT_LEN)
    c, dec_c = sch.serve_ipc_coalesce(4, event_bytes=128)
    rows = [e for e in rt.ledger.entries if e.site == "serve_ipc"]
    assert {e.query.get("op") for e in rows} == {"workers", "coalesce"}
    assert all(e.predicted_s >= 0 for e in rows)
    assert w in (0, 1, 2, 4)    # inline baseline or a worker candidate
    assert c >= 1
    # an explicit deployment pins the worker verdict to the candidate, and
    # a worker verdict prices real IPC (round trips + serialization)
    w2, dec_w2 = sch.serve_ipc_workers(8, msg_bytes=512,
                                       prompt_len=PROMPT_LEN,
                                       candidates=(2,), override="frontend")
    assert w2 == 2
    assert dec_w2.predicted_s > 0
    sch.record_measured(dec_w, 1.25e-4, note="test attach")
    measured = [e for e in rt.ledger.entries
                if e.site == "serve_ipc" and e.measured_s is not None]
    assert measured and measured[-1].measured_s == pytest.approx(1.25e-4)


def test_static_mode_rejects_frontend_and_bad_worker_counts():
    cfg, model, params = _build()
    rt = Runtime()
    trace = synthetic_trace(1, prompt_len=PROMPT_LEN, max_new=2,
                            vocab_size=cfg.vocab_size, arrival="all", seed=0)
    common = dict(model=model, params=params, max_len=MAX_LEN, eos_id=0)
    with pytest.raises(ValueError):
        rt.serve(cfg, trace, mode="static", frontend=2, **common)
    with pytest.raises(ValueError):
        rt.serve(cfg, trace, mode="static", stream=True, **common)
    with pytest.raises(ValueError):
        rt.serve(cfg, trace, mode="continuous", slots=1, frontend=0,
                 **common)


# ---------------------------------------------------------------------------
# Multi-process equivalence (two page sizes) and the emission transcript
# ---------------------------------------------------------------------------


def test_frontend_serve_token_identical_over_two_page_sizes():
    rt = Runtime()
    cfg, model, params = _build()
    common = dict(model=model, params=params, max_len=MAX_LEN, eos_id=0,
                  mode="continuous", slots=2)

    def trace():
        return synthetic_trace(4, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                               vocab_size=cfg.vocab_size, arrival="all",
                               seed=0)

    # base and fe run the default 16-token pages, fe_small 4-token pages
    base = rt.serve(cfg, trace(), **common)
    fe = rt.serve(cfg, trace(), frontend=2, stream=True, **common)
    fe_small = rt.serve(cfg, trace(),
                        frontend=FrontendConfig(workers=1, coalesce=2),
                        stream=True, block_size=4, **common)

    for res in (fe, fe_small):
        assert res.report.state_counts().get("COMPLETED") == 4
        for rid, ref in base.outputs.items():
            assert np.array_equal(res.outputs[rid], ref)
        # the emission worker's transcript IS the engine's token sequence
        assert res.texts is not None and set(res.texts) == set(base.outputs)
        toks = {r.rid: r.tokens for r in res.report.requests}
        for rid in toks:
            assert res.texts[rid] == " ".join(str(int(t))
                                              for t in toks[rid])
        assert res.report.ipc_messages > 0 and res.report.ipc_bytes > 0
        assert res.report.streamed_tokens == sum(len(t)
                                                 for t in toks.values())
    assert fe.report.frontend_workers == 2
    assert fe_small.report.frontend_workers == 1

    rows = [e for e in rt.ledger.entries if e.site == "serve_ipc"]
    assert {e.query.get("op") for e in rows} == {"workers", "coalesce"}
    assert any(e.measured_s is not None for e in rows)


# ---------------------------------------------------------------------------
# Crash drills: typed failure + drain, never a hung serve
# ---------------------------------------------------------------------------


def test_dead_emission_worker_fails_typed_and_engine_stays_usable():
    # respawn=0 opts out of self-healing: a dead worker goes straight to
    # the typed-FAILED path (the pre-self-healing contract, still the
    # fallback once the respawn budget exhausts)
    cfg, model, params = _build()
    prompts = _prompts(cfg, 3)
    fe = ServingFrontend(FrontendConfig(workers=1, respawn=0),
                         max_len=MAX_LEN)
    fe.start()
    try:
        engine = ContinuousServeEngine(model, params, n_slots=2,
                                       max_len=MAX_LEN, eos_id=0,
                                       stream=fe.stream())
        fe.kill_emission_worker()
        rep = engine.run([Request(f"r{i}", prompts[i], MAX_NEW)
                          for i in range(3)], now_fn=lambda: 0.0)
        assert rep.all_terminal
        assert rep.state_counts() == {"FAILED": 3}
        for r in rep.requests:
            assert "frontend stream broken" in (r.reason or "")
        # drain invariant: the pool is clean, the engine immediately
        # serves a fresh trace in-process
        engine.stream = None
        rep2 = engine.run([Request(f"s{i}", prompts[i], MAX_NEW)
                           for i in range(3)], now_fn=lambda: 0.0)
        assert rep2.state_counts() == {"COMPLETED": 3}
    finally:
        fe.close()


def test_dead_intake_workers_yield_typed_failures():
    # respawn=0: no healing, routed submissions become typed failures
    fe = ServingFrontend(FrontendConfig(workers=1, respawn=0),
                         max_len=MAX_LEN)
    fe.start()
    try:
        fe.kill_intake_workers()
        validated, failures = fe.submit([
            {"rid": "a", "prompt": [1, 2], "max_new_tokens": 2},
            {"rid": "b", "prompt": [3], "max_new_tokens": 2},
        ])
        assert validated == {}
        assert set(failures) == {"a", "b"}
        assert all(why.startswith("frontend:") for why in failures.values())
    finally:
        fe.close()


def test_intake_workers_validate_and_type_invalid_submissions():
    fe = ServingFrontend(FrontendConfig(workers=2), max_len=MAX_LEN)
    fe.start()
    try:
        assert len(fe.ping_round_trips_s) == 3  # 2 intake + 1 emission
        assert all(t > 0 for t in fe.ping_round_trips_s)
        validated, failures = fe.submit([
            {"rid": "ok", "prompt": [1, 2, 3], "max_new_tokens": 2},
            {"rid": "long", "prompt": list(range(1, MAX_LEN + 2)),
             "max_new_tokens": 4},
            {"rid": "bad", "prompt": "not-token-ids", "max_new_tokens": 2},
        ])
        assert set(validated) == {"ok"}
        assert validated["ok"]["prompt_len"] == 3
        assert set(failures) == {"long", "bad"}
        assert fe.ipc_messages > 0 and fe.ipc_bytes > 0
    finally:
        fe.close()


# ---------------------------------------------------------------------------
# Within-group prefix sharing
# ---------------------------------------------------------------------------


def test_within_group_prefix_sharing_matches_serialized_hit_rate():
    rt = Runtime()
    cfg, model, params = _build()
    common = dict(model=model, params=params, max_len=MAX_LEN, eos_id=0,
                  mode="continuous")

    def trace():
        return synthetic_trace(4, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                               vocab_size=cfg.vocab_size, arrival="all",
                               seed=0, prefix_share=1.0, prefix_len=4)

    base = rt.serve(cfg, trace(), slots=3, **common)
    reuse_kw = dict(block_size=2, prefix_cache="force")
    shared = rt.serve(cfg, trace(), slots=3, **reuse_kw, **common)
    serialized = rt.serve(cfg, trace(), slots=1, **reuse_kw, **common)

    rep = shared.report
    # the admission group was SPLIT: the donor prefilled the shared prefix
    # once and the rest hit its pages — the same reuse the 1-slot
    # serialized run gets, no longer an artifact of serialization
    assert rep.prefix_hit_tokens > 0
    assert rep.prefix_hit_tokens == serialized.report.prefix_hit_tokens
    assert rep.prefilled_tokens == serialized.report.prefilled_tokens
    assert rep.prefilled_tokens < 4 * PROMPT_LEN
    for rid, ref in base.outputs.items():
        assert np.array_equal(shared.outputs[rid], ref)
        assert np.array_equal(serialized.outputs[rid], ref)


# ---------------------------------------------------------------------------
# Computed idle sleep
# ---------------------------------------------------------------------------


def test_idle_jumps_on_pinned_clock():
    cfg, model, params = _build()
    prompts = _prompts(cfg, 2)
    engine = ContinuousServeEngine(model, params, n_slots=1, max_len=MAX_LEN,
                                   eos_id=0)
    # 100 VIRTUAL seconds between arrivals on a pinned clock: the idle
    # branch must jump the offset to the next arrival, not sleep wall time
    t0 = time.perf_counter()
    rep = engine.run([Request("r0", prompts[0], 2, arrival_s=0.0),
                      Request("r1", prompts[1], 2, arrival_s=100.0)],
                     now_fn=lambda: 0.0)
    wall = time.perf_counter() - t0
    assert rep.state_counts() == {"COMPLETED": 2}
    assert wall < 30.0      # compile dominates; the 100 s gap cost nothing


def test_idle_sleeps_to_next_arrival_not_fixed_polls(monkeypatch):
    import repro.serving.engine as eng_mod
    cfg, model, params = _build()
    prompts = _prompts(cfg, 3)
    engine = ContinuousServeEngine(model, params, n_slots=1, max_len=MAX_LEN,
                                   eos_id=0)
    engine.run([Request("warm", prompts[2], 2)], now_fn=lambda: 0.0)

    real_sleep, sleeps = time.sleep, []

    def spy(seconds):
        sleeps.append(seconds)
        real_sleep(seconds)

    monkeypatch.setattr(eng_mod.time, "sleep", spy)
    gap = 0.3
    rep = engine.run([Request("r0", prompts[0], 2, arrival_s=0.0),
                      Request("r1", prompts[1], 2, arrival_s=gap)])
    assert rep.state_counts() == {"COMPLETED": 2}
    # ONE computed sleep covers (nearly) the whole idle gap — the old
    # fixed 50 ms poll would have woken ~6 times instead
    assert max(sleeps) >= 0.5 * gap
    assert len(sleeps) <= 6


# ---------------------------------------------------------------------------
# Self-healing: bounded auto-respawn of crashed workers
# ---------------------------------------------------------------------------


def test_crashed_intake_workers_respawn_and_submissions_validate():
    fe = ServingFrontend(FrontendConfig(workers=2, respawn=2),
                         max_len=MAX_LEN)
    fe.start()
    try:
        subs = [{"rid": f"r{i}", "prompt": [1, 2, 3], "max_new_tokens": 2}
                for i in range(4)]
        validated, failures = fe.submit(subs)
        assert set(validated) == {f"r{i}" for i in range(4)} and not failures
        fe.kill_intake_workers()
        validated, failures = fe.submit(subs)
        assert set(validated) == {f"r{i}" for i in range(4)} and not failures
        assert fe.respawns >= 1
        # the replacements are real processes holding the crashed slots
        assert all(p.is_alive() for p in fe._intake_procs)
    finally:
        fe.close()


def test_crashed_emission_worker_respawns_with_replayed_transcript():
    fe = ServingFrontend(FrontendConfig(workers=1, respawn=2),
                         max_len=MAX_LEN)
    fe.start()
    try:
        stream = fe.stream()
        stream.publish("a", (1, 2), done=False, t=0.0)
        stream.publish("b", (7,), done=False, t=0.0)
        fe.kill_emission_worker()
        # next burst hits the dead worker: respawn + replay, no data loss
        stream.publish("a", (3,), done=True, t=0.1)
        stream.publish("b", (8,), done=True, t=0.1)
        transcript = fe.finish()
        assert fe.respawns == 1
        assert transcript["a"]["tokens"] == [1, 2, 3]
        assert transcript["b"]["tokens"] == [7, 8]
        assert transcript["a"]["text"] == "1 2 3"
    finally:
        fe.close()


def test_emission_respawn_survives_crash_before_finish():
    fe = ServingFrontend(FrontendConfig(workers=1, respawn=1),
                         max_len=MAX_LEN)
    fe.start()
    try:
        stream = fe.stream()
        stream.publish("a", (4, 5), done=True, t=0.0)
        # crash AFTER the last burst: finish() itself must heal + replay
        fe.kill_emission_worker()
        transcript = fe.finish()
        assert fe.respawns == 1
        assert transcript["a"]["tokens"] == [4, 5]
    finally:
        fe.close()
