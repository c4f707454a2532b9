"""Host spans of the serve path (``repro.serving.spans``).

* a recorder changes no token and no ``ServeReport`` count
* the span tree is well formed: unique ids, children inside their parents,
  every admission and macro-step with all of its phases — also when the
  watchdog runs each step on a worker thread
* span attributes add up to the report's counters
* without a recorder no ``TraceAnnotation`` is made
* ``Runtime.serve(tracer=...)`` records the set-up phases
* a step that compiles shows it (``compile_s``)
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Runtime, set_default_runtime
from repro.serving import ContinuousServeEngine, Request
from repro.serving.spans import SpanRecorder

SLOTS = 2
MAX_LEN = 24
# (prompt length, max new tokens, arrival) — more requests than slots, so
# slots turn over; two later arrivals make the loop wait for them
TRACE = [(5, 6, 0.0), (9, 3, 0.0), (7, 8, 0.0), (4, 5, 0.0),
         (11, 4, 0.5), (6, 7, 1.0)]
ADMIT_PHASES = {"serve/admit/prepare", "serve/admit/dispatch",
                "serve/admit/sync", "serve/admit/finish"}
MACRO_PHASES = {"serve/macro/plan", "serve/macro/dispatch",
                "serve/macro/sync", "serve/macro/emit"}
VARIANTS = {
    "dense": {},
    "paged": {"paged": True, "block_size": 4},
    # the watchdog runs each dispatch and sync on a worker thread
    "guarded": {"paged": True, "block_size": 4, "watchdog_s": 60.0},
}


@pytest.fixture(autouse=True)
def _fresh_runtime():
    set_default_runtime(Runtime())
    yield
    set_default_runtime(None)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", rng.integers(1, cfg.vocab_size, (p,)).astype(
        np.int32), n, arrival_s=a) for i, (p, n, a) in enumerate(TRACE)]


def _serve(tiny, tracer=None, **kw):
    cfg, model, params = tiny
    engine = ContinuousServeEngine(model, params, n_slots=SLOTS,
                                   max_len=MAX_LEN, eos_id=-1, pad_id=0,
                                   tracer=tracer, **kw)
    return engine.run(_requests(cfg), now_fn=lambda: 0.0)


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def served(request, tiny):
    """(report without a recorder, report with one, its spans)."""
    kw = VARIANTS[request.param]
    plain = _serve(tiny, **kw)
    with SpanRecorder() as rec:
        traced = _serve(tiny, rec, **kw)
    return plain, traced, rec.spans


def test_recorder_changes_no_token_or_count(served):
    plain, traced, _ = served
    assert traced.outputs().keys() == plain.outputs().keys()
    for rid, out in plain.outputs().items():
        np.testing.assert_array_equal(traced.outputs()[rid], out)
    assert traced.as_dict() == plain.as_dict()
    assert plain.prefill_padded_tokens > plain.prefilled_tokens > 0
    assert plain.decode_slot_steps > 0


def test_span_tree_is_well_formed(served):
    _, _, spans = served
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)
    for s in spans:
        assert s.name.startswith("serve/") and s.start <= s.end
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.start <= s.start and s.end <= p.end, (p.name, s.name)
            assert s.name.startswith(p.name + "/")
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, set()).add(s.name)
    admits = [s for s in spans if s.name == "serve/admit"]
    macros = [s for s in spans if s.name == "serve/macro"]
    assert admits and macros
    for s in admits:
        assert ADMIT_PHASES <= kids[s.id], kids[s.id]
    for s in macros:
        assert kids[s.id] == MACRO_PHASES
    assert {s.name for s in spans if s.parent is None} == {
        "serve/intake", "serve/admit", "serve/macro", "serve/wait_arrival"}


def test_span_attributes_add_up_to_the_counters(served, request):
    _, rep, spans = served
    # paged engines prefill each group on its own rows (1 or 2 of 2 slots)
    narrow = VARIANTS[request.node.callspec.params["served"]].get("paged")
    admits = [s.attrs for s in spans if s.name == "serve/admit"]
    macros = [s.attrs for s in spans if s.name == "serve/macro"]
    assert sum(a["useful_tokens"] for a in admits) == rep.prefilled_tokens
    assert sum(a["rows"] * a["padded_len"] for a in admits) == \
        rep.prefill_padded_tokens
    assert sum(m["rows"] * m["k"] for m in macros) == rep.decode_slot_steps
    first_tokens = sum(len(a["rids"]) for a in admits)
    assert first_tokens == len(TRACE)
    assert first_tokens + sum(m["emitted"] for m in macros) == \
        rep.generated_tokens
    assert all(a["slots"] == SLOTS and a["padded_len"] % a["chunk"] == 0
               for a in admits)
    assert [a["rows"] for a in admits] == [
        len(a["rids"]) if narrow else SLOTS for a in admits]
    assert rep.narrow_admissions == sum(a["rows"] < SLOTS for a in admits)
    assert rep.narrow_admissions > 0 if narrow else \
        rep.narrow_admissions == 0
    assert all(m["live"] == len(m["rids"]) <= m["rows"] for m in macros)
    assert sum(s.attrs["arrived"] for s in spans
               if s.name == "serve/intake") == len(TRACE)


def test_no_annotation_without_recorder(tiny, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation made with no recorder")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    rep = _serve(tiny, paged=True, block_size=4)
    assert rep.all_terminal and rep.generated_tokens > 0
    with SpanRecorder() as rec, pytest.raises(AssertionError):
        _serve(tiny, rec)  # the patch is live: a recorder would make one


def test_runtime_serve_records_set_up(tiny):
    cfg, model, params = tiny
    with SpanRecorder() as rec:
        res = Runtime().serve(cfg, _requests(cfg), mode="continuous",
                              model=model, params=params, slots=SLOTS,
                              max_len=MAX_LEN, eos_id=-1, pad_id=0,
                              paged=True, block_size=4, tracer=rec)
    assert res.report.all_terminal
    by_name = {}
    for s in rec.spans:
        by_name.setdefault(s.name, []).append(s)
    (setup,) = by_name["serve/setup"]
    (init,) = by_name["serve/setup/engine_init"]
    (warm,) = by_name["serve/setup/warmup"]
    assert init.parent == warm.parent == setup.id
    assert init.end <= warm.start
    ks = [s.attrs["k"] for s in by_name["serve/setup/warmup_macro"]]
    assert ks and all(s.parent == warm.id
                      for s in by_name["serve/setup/warmup_macro"])
    assert len(set(ks)) == len(ks)
    # the warm-up's dummy request is admitted under the warm-up span; the
    # trace's own spans start after set-up ends
    warm_admits = [s for s in by_name["serve/admit"] if s.parent == warm.id]
    assert len(warm_admits) == 1
    top = [s for s in rec.spans if s.parent is None and s is not setup]
    assert top and min(s.start for s in top) >= setup.end
    with pytest.raises(ValueError, match="continuous"):
        Runtime().serve(cfg, _requests(cfg)[:2], mode="static", model=model,
                        params=params, tracer=rec)


def test_first_call_of_a_new_horizon_shows_its_compile(tiny):
    # a persistent-cache hit would skip the backend compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with SpanRecorder() as rec:
            _serve(tiny, rec, macro_step=3)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    macros = [s for s in rec.spans if s.name == "serve/macro/dispatch"]
    assert len(macros) > 1
    assert macros[0].attrs.get("compile_s", 0.0) > 0
    # the same horizon again finds its program compiled
    assert all("compile_s" not in s.attrs for s in macros[1:])
