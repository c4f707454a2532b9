"""Continuous-batching serving: correctness anchors.

* static-vs-continuous token equivalence (the engine rewrite's invariant),
  across model families (chunked prefill + the chunk-1 replay fallback),
  including slot queueing/reuse (n_slots < n_requests)
* slot reuse after eviction matches a fresh engine (decode-state reset)
* EOS early-stop + deterministic padding in both engines
* scheduler decisions land as site=serve overhead-ledger rows
* explicit max_len validation (no silent slack)
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costs.engine import CostEngine
from repro.models import build_model
from repro.models.model import mrope_positions
from repro.runtime import Runtime, set_default_runtime
from repro.serving import (
    ContinuousServeEngine,
    Request,
    ServeEngine,
    supports_chunked_prefill,
)

PROMPT_LEN = 7
MAX_NEW = 9
MAX_LEN = PROMPT_LEN + MAX_NEW


@pytest.fixture(autouse=True)
def _fresh_runtime():
    # each test gets its own session (isolated engine + ledger); engines
    # that are not passed one explicitly fall back to this default Runtime
    set_default_runtime(Runtime())
    yield
    set_default_runtime(None)


def _build(arch, key=0, **overrides):
    cfg = get_config(arch).reduced()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(key))
    return cfg, model, params


def _prompts(cfg, b, p=PROMPT_LEN, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (b, p)).astype(np.int32)


def _run_continuous(model, params, prompts, max_new, *, n_slots, **kw):
    engine = ContinuousServeEngine(
        model, params, n_slots=n_slots, max_len=MAX_LEN, eos_id=0, **kw)
    reqs = [Request(f"r{i}", prompts[i], max_new) for i in range(len(prompts))]
    report = engine.run(reqs, now_fn=lambda: 0.0)
    return np.stack([report.output(f"r{i}", max_new)
                     for i in range(len(prompts))]), report


# ---------------------------------------------------------------------------
# Token-for-token equivalence with the static baseline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [
    "tinyllama-1.1b",       # dense attn -> chunked prefill
    "qwen2-vl-72b",         # mrope positions through the shared helper
    "rwkv6-3b",             # recurrent -> chunk-1 replay fallback
    "recurrentgemma-2b",    # hybrid local ring buffer -> replay fallback
    "moonshot-v1-16b-a3b",  # latent attention, leading dense layer, experts
])
def test_continuous_matches_static(arch):
    cfg, model, params = _build(arch)
    prompts = _prompts(cfg, 3)
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    # n_slots < n_requests: forces queueing and slot reuse after eviction
    got, _ = _run_continuous(model, params, prompts, MAX_NEW, n_slots=2)
    np.testing.assert_array_equal(got, expected)


def test_continuous_matches_static_scan_layout():
    """Uniform stacks with >= 4 layers store decode state scanned (slot axis
    1); slot insert/reset must hit the right axis there too."""
    cfg, model, params = _build("tinyllama-1.1b", n_layers=4)
    prompts = _prompts(cfg, 3)
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    got, _ = _run_continuous(model, params, prompts, MAX_NEW, n_slots=2)
    np.testing.assert_array_equal(got, expected)


def test_chunked_prefill_matches_replay():
    """Chunked prefill (multi-token chunks through decode_step) must emit
    the same tokens as the per-token replay it replaces."""
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 2)
    replay, _ = _run_continuous(model, params, prompts, MAX_NEW,
                                n_slots=2, prefill_chunk=1)
    chunked, _ = _run_continuous(model, params, prompts, MAX_NEW,
                                 n_slots=2, prefill_chunk=4)
    np.testing.assert_array_equal(chunked, replay)


def test_ragged_prompts_match_single_request_runs():
    """Per-slot cache positions: requests with different prompt lengths
    decode concurrently yet match isolated single-request runs."""
    cfg, model, params = _build("tinyllama-1.1b")
    rng = np.random.default_rng(3)
    lens = [4, 7, 10]
    prompts = [rng.integers(1, cfg.vocab_size, (p,)).astype(np.int32)
               for p in lens]
    max_len = max(lens) + MAX_NEW
    engine = ContinuousServeEngine(model, params, n_slots=3,
                                   max_len=max_len, eos_id=0)
    report = engine.run(
        [Request(f"r{i}", prompts[i], MAX_NEW) for i in range(3)],
        now_fn=lambda: 0.0)
    static = ServeEngine(model, params, max_len=max_len, eos_id=0)
    for i in range(3):
        expected = static.generate(prompts[i][None], max_new_tokens=MAX_NEW)[0]
        np.testing.assert_array_equal(report.output(f"r{i}", MAX_NEW), expected)


def test_staggered_arrivals_under_pinned_clock():
    """A frozen test clock with nonzero arrivals must event-skip to the next
    arrival (not sleep forever), and stay token-identical to the baseline."""
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 3)
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    engine = ContinuousServeEngine(model, params, n_slots=1,
                                   max_len=MAX_LEN, eos_id=0)
    report = engine.run(
        [Request(f"r{i}", prompts[i], MAX_NEW, arrival_s=0.1 * i)
         for i in range(3)],
        now_fn=lambda: 0.0)
    got = np.stack([report.output(f"r{i}", MAX_NEW) for i in range(3)])
    np.testing.assert_array_equal(got, expected)
    assert all(r.ttft_s is not None and r.ttft_s >= 0 for r in report.requests)


# ---------------------------------------------------------------------------
# Slot reuse / reset correctness
# ---------------------------------------------------------------------------


def test_slot_reuse_after_eviction_matches_fresh_engine():
    """A request served on a recycled slot must see no trace of the evicted
    one: its output equals the same request on a fresh engine."""
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 2, seed=7)
    engine = ContinuousServeEngine(model, params, n_slots=1,
                                   max_len=MAX_LEN, eos_id=0)
    report = engine.run(
        [Request("first", prompts[0], MAX_NEW),
         Request("reused", prompts[1], MAX_NEW)],
        now_fn=lambda: 0.0)
    fresh = ContinuousServeEngine(model, params, n_slots=1,
                                  max_len=MAX_LEN, eos_id=0)
    fresh_report = fresh.run([Request("alone", prompts[1], MAX_NEW)],
                             now_fn=lambda: 0.0)
    np.testing.assert_array_equal(report.output("reused", MAX_NEW),
                                  fresh_report.output("alone", MAX_NEW))


# ---------------------------------------------------------------------------
# EOS handling
# ---------------------------------------------------------------------------


def _pick_eos(model, params, prompts, step=3):
    """Choose as EOS the token the first row actually emits at ``step``
    (so EOS genuinely triggers mid-generation)."""
    probe = ServeEngine(model, params, max_len=MAX_LEN, eos_id=-1)
    base = probe.generate(prompts, max_new_tokens=MAX_NEW)
    return base, int(base[0, step])


def test_static_eos_early_stop_and_padding():
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 2)
    base, eos = _pick_eos(model, params, prompts)
    engine = ServeEngine(model, params, max_len=MAX_LEN, eos_id=eos, pad_id=0)
    out = engine.generate(prompts, max_new_tokens=MAX_NEW)
    row = out[0]
    k = int(np.flatnonzero(row == eos)[0])
    # tokens before EOS match the unconstrained run, EOS kept, rest padded
    np.testing.assert_array_equal(row[: k + 1], base[0, : k + 1])
    assert np.all(row[k + 1 :] == 0)
    # rows that never emit EOS are unchanged
    if eos not in base[1]:
        np.testing.assert_array_equal(out[1], base[1])


def test_continuous_eos_matches_static():
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 2)
    _, eos = _pick_eos(model, params, prompts)
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=eos, pad_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    engine = ContinuousServeEngine(model, params, n_slots=2, max_len=MAX_LEN,
                                   eos_id=eos, pad_id=0)
    report = engine.run([Request(f"r{i}", prompts[i], MAX_NEW)
                         for i in range(2)], now_fn=lambda: 0.0)
    got = np.stack([report.output(f"r{i}", MAX_NEW) for i in range(2)])
    np.testing.assert_array_equal(got, expected)
    # the finished request must have stopped early (freed its slot)
    finished = next(r for r in report.requests if eos in r.tokens)
    assert len(finished.tokens) < MAX_NEW or finished.tokens[-1] == eos


# ---------------------------------------------------------------------------
# Macro-step decode (the host-sync-free hot path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [
    "tinyllama-1.1b",       # dense attn -> chunked prefill
    "qwen2-vl-72b",         # mrope positions computed on device
    "rwkv6-3b",             # recurrent -> chunk-1 replay fallback
    "recurrentgemma-2b",    # hybrid local ring buffer -> replay fallback
])
def test_macro_step_eos_turnover_ragged_budgets_match_static(arch):
    """Pinned K=4 macro-steps with a mid-macro EOS, slot turnover
    (n_slots < n_requests) and ragged per-request ``max_new_tokens`` stay
    token-identical to the static baseline."""
    cfg, model, params = _build(arch)
    prompts = _prompts(cfg, 3)
    base, eos = _pick_eos(model, params, prompts)  # EOS fires at step 3 of r0
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=eos, pad_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    budgets = [MAX_NEW, 5, 6]  # ragged: slots hit budget mid-macro-step
    engine = ContinuousServeEngine(model, params, n_slots=2, max_len=MAX_LEN,
                                   eos_id=eos, pad_id=0, macro_step=4)
    report = engine.run(
        [Request(f"r{i}", prompts[i], budgets[i]) for i in range(3)],
        now_fn=lambda: 0.0)
    for i in range(3):
        np.testing.assert_array_equal(
            report.output(f"r{i}", budgets[i]), expected[i, : budgets[i]])


def test_k1_macro_step_degenerates_to_per_token_loop():
    """macro_step=1 must reproduce today's one-sync-per-token behavior
    exactly, and K>1 must emit the same tokens with fewer host syncs."""
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 3)
    static = ServeEngine(model, params, max_len=MAX_LEN, eos_id=0)
    expected = static.generate(prompts, max_new_tokens=MAX_NEW)
    got_k1, rep_k1 = _run_continuous(model, params, prompts, MAX_NEW,
                                     n_slots=2, macro_step=1)
    got_k8, rep_k8 = _run_continuous(model, params, prompts, MAX_NEW,
                                     n_slots=2, macro_step=8)
    np.testing.assert_array_equal(got_k1, expected)
    np.testing.assert_array_equal(got_k8, expected)
    # K=1 pays ~one sync per generated token on the decode path; K=8
    # amortizes it 8x (both also pay one sync per prefill group)
    assert rep_k8.host_syncs < rep_k1.host_syncs
    assert rep_k1.host_syncs_per_token <= 1.0 + 1e-9


def test_sync_and_dispatch_counters_in_report():
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 3)
    _, report = _run_continuous(model, params, prompts, MAX_NEW,
                                n_slots=2, macro_step=4)
    d = report.as_dict()
    assert d["host_syncs"] == report.host_syncs > 0
    assert d["device_dispatches"] == report.device_dispatches >= report.host_syncs
    assert d["host_syncs_per_token"] == pytest.approx(
        report.host_syncs / report.generated_tokens)
    # the whole point: fewer host syncs than generated tokens
    assert report.host_syncs < report.generated_tokens


def test_donated_state_is_not_aliased_by_live_buffers():
    """The pooled decode state is donated through prefill/macro-step/reset:
    stale references to pre-donation buffers must raise (in-place update,
    not copy-on-write), and the engine must stay reusable run after run
    (no accidental reuse of a deleted buffer inside the engine)."""
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 2, seed=11)
    engine = ContinuousServeEngine(model, params, n_slots=2, max_len=MAX_LEN,
                                   eos_id=0, macro_step=4)
    stale = jax.tree.leaves(engine.pool.state)
    reqs = lambda: [Request(f"r{i}", prompts[i], MAX_NEW) for i in range(2)]  # noqa: E731
    rep1 = engine.run(reqs(), now_fn=lambda: 0.0)
    assert any_deleted(stale), "donation did not consume the old state"
    rep2 = engine.run(reqs(), now_fn=lambda: 0.0)  # no RuntimeError on reuse
    for i in range(2):
        np.testing.assert_array_equal(rep1.output(f"r{i}", MAX_NEW),
                                      rep2.output(f"r{i}", MAX_NEW))


def any_deleted(leaves) -> bool:
    for leaf in leaves:
        try:
            np.asarray(leaf)
        except RuntimeError:
            return True
    return False


def test_emitted_count_vectorized():
    from repro.serving import emitted_count

    out = np.array([[5, 7, 0, 9],    # EOS at index 2 -> 3 tokens
                    [1, 2, 3, 4],    # no EOS -> all 4
                    [0, 0, 0, 0]])   # EOS first -> 1
    assert emitted_count(out, eos_id=0) == 3 + 4 + 1
    assert emitted_count(np.zeros((0, 4), np.int32), eos_id=0) == 0


# ---------------------------------------------------------------------------
# Scheduler decisions on the overhead ledger
# ---------------------------------------------------------------------------


def test_ledger_has_site_serve_rows():
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 3)
    rt = Runtime()
    set_default_runtime(rt)
    _run_continuous(model, params, prompts, MAX_NEW, n_slots=2)
    rows = [e for e in rt.ledger.entries if e.site == "serve"]
    assert rows, "no site=serve rows in the overhead ledger"
    ops = {e.query.get("op") for e in rows}
    assert {"admission", "prefill_chunk"} <= ops
    # the decode composition is now the macro-horizon decision site
    macro = [e for e in rt.ledger.entries if e.site == "serve_macro"]
    assert macro, "no site=serve_macro rows in the overhead ledger"
    measured = [e for e in rows + macro if e.measured_s is not None]
    assert measured, "no measured wall times attached to serve decisions"
    # decisions carry real predicted breakdowns
    assert all(e.predicted_s > 0 for e in rows + macro)


def test_macro_horizon_decision_trades_sync_against_waste():
    """The serve_macro sweep amortizes the host sync over K on uniform
    budgets, but shrinks the horizon when a slot is about to finish."""
    from repro.serving.scheduler import ServeScheduler

    engine = CostEngine()
    cfg = get_config("tinyllama-1.1b").reduced()
    sched = ServeScheduler(cfg, engine, max_len=MAX_LEN)
    k_uniform, dec = sched.macro_horizon((8, 8, 8))
    assert k_uniform > 1  # sync amortization wins on uniform budgets
    assert dec.query.kind == "serve_macro"
    assert dec.baseline.strategy == "K_1"
    k_ragged, _ = sched.macro_horizon((1, 8, 8))
    assert k_ragged <= k_uniform  # imminent finish caps the horizon
    k_pinned, _ = sched.macro_horizon((8, 8, 8), override=1)
    assert k_pinned == 1
    # candidates are FILTERED to the fixed set, never clamped to ad-hoc Ks
    k_small, dec_small = sched.macro_horizon((3,))
    assert k_small in sched.macro_candidates


def test_prefill_chunk_decision_prefers_replay_only_for_non_attn():
    from repro.serving.scheduler import ServeScheduler

    engine = CostEngine()
    attn_cfg = get_config("tinyllama-1.1b").reduced()
    sched = ServeScheduler(attn_cfg, engine, max_len=MAX_LEN)
    chunk, dec = sched.prefill_chunk(64, active_decodes=0)
    assert chunk > 1  # big chunks amortize the weight stream on empty pools
    assert dec.query.kind == "serve"
    rwkv_cfg = get_config("rwkv6-3b").reduced()
    sched_rwkv = ServeScheduler(rwkv_cfg, engine, max_len=MAX_LEN)
    chunk_rwkv, _ = sched_rwkv.prefill_chunk(64, active_decodes=0)
    assert chunk_rwkv == 1  # replay fallback is pinned for recurrent decode


# ---------------------------------------------------------------------------
# Explicit max_len validation (the retired "+ 8" slack)
# ---------------------------------------------------------------------------


def test_overflowing_request_errors_clearly():
    cfg, model, params = _build("tinyllama-1.1b")
    prompts = _prompts(cfg, 1)
    static = ServeEngine(model, params, max_len=PROMPT_LEN + 2, eos_id=0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        static.generate(prompts, max_new_tokens=MAX_NEW)
    cont = ContinuousServeEngine(model, params, n_slots=1,
                                 max_len=PROMPT_LEN + 2, eos_id=0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        cont.run([Request("r0", prompts[0], MAX_NEW)], now_fn=lambda: 0.0)


# ---------------------------------------------------------------------------
# Shared mrope positions helper
# ---------------------------------------------------------------------------


def test_mrope_positions_helper():
    scalar = np.asarray(mrope_positions(2, 3, 5))
    assert scalar.shape == (2, 3, 3)
    np.testing.assert_array_equal(scalar[0, :, 0], [5, 6, 7])
    np.testing.assert_array_equal(scalar[1], scalar[0])
    assert (scalar == scalar[..., :1]).all()  # three planes share the index
    vec = np.asarray(mrope_positions(2, 2, np.array([3, 10], np.int32)))
    np.testing.assert_array_equal(vec[0, :, 0], [3, 4])
    np.testing.assert_array_equal(vec[1, :, 0], [10, 11])
