"""Paged KV pool + radix prefix cache.

* BlockPool unit behavior: refcounts, null-block invariants, LRU eviction
  of idle trie leaves, lookup cap at prompt_len - 1, dedupe swaps, drain
* the serve_prefix decision site: crossover (skipped prefill compute vs
  lookup/pin + CoW cost) and the 'use_prefix'/'full_prefill' override
* paged greedy decode is TOKEN-IDENTICAL to the dense static baseline
  across every served family, through slot turnover, with block tables
  threaded into the jitted programs (no recompiles beyond the dense count)
* shared-prefix traffic: the prefix prefills once, later requests pin its
  pages and prefill only their suffix (>=2x fewer prefilled tokens), with
  serve_prefix ledgered predicted-vs-measured and CoW serving partial tails
* lifecycle interplay: preemption/deadline eviction releases the victim's
  pages (trie-pinned prefix blocks survive and resume re-pins them), and a
  fatal-abort drain reclaims the WHOLE BlockPool
* an admission prefills its group's rows (a power of two), not every
  slot, with the tokens of the every-slot program; warm-up compiles every
  row bucket; engines with other per-slot state keep every slot
* forced 8-device mesh: paged + sharded decode stays token-identical
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_distributed import run_distributed

from repro.configs import get_config
from repro.core.costs.engine import CostEngine
from repro.models import build_model
from repro.runtime import Runtime, set_default_runtime, synthetic_trace
from repro.serving import (
    BlockPool,
    ContinuousServeEngine,
    FatalFault,
    FaultInjector,
    FaultSpec,
    Request,
    RequestState,
    ServeScheduler,
    default_kv_blocks,
)
from repro.serving.spans import COMPILE_EVENT, SpanRecorder

PROMPT_LEN = 7
MAX_NEW = 9
MAX_LEN = PROMPT_LEN + MAX_NEW
BLOCK = 4  # pages smaller than a prompt, so every request spans several


@pytest.fixture(autouse=True)
def _fresh_runtime():
    set_default_runtime(Runtime())
    yield
    set_default_runtime(None)


def _build(arch="tinyllama-1.1b", key=0, **overrides):
    cfg = get_config(arch).reduced()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(key))
    return cfg, model, params


def _prompts(cfg, b, p=PROMPT_LEN, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (b, p)).astype(np.int32)


def _engine(model, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("eos_id", 0)
    return ContinuousServeEngine(model, params, **kw)


def _run(engine, prompts, max_new=MAX_NEW):
    reqs = [Request(f"r{i}", prompts[i], max_new)
            for i in range(len(prompts))]
    return engine.run(reqs, now_fn=lambda: 0.0)


def _tokens(rep):
    return {r.rid: list(r.tokens) for r in rep.requests}


# ---------------------------------------------------------------------------
# BlockPool + radix trie (pure host bookkeeping, no model)
# ---------------------------------------------------------------------------


def test_block_pool_refcounts_and_null_block():
    pool = BlockPool(6, BLOCK)
    assert pool.free_blocks == 5 and pool.used_blocks == 0
    bids = pool.alloc(3)
    assert 0 not in bids and len(set(bids)) == 3
    assert pool.used_blocks == 3
    pool.incref(bids[0])
    pool.release(bids)  # one slot ref dropped from each
    assert pool.used_blocks == 1  # bids[0] survives its extra ref
    pool.decref(bids[0])
    assert pool.used_blocks == 0 and pool.free_blocks == 5
    # null block is permanently pinned and ref-ops on it are no-ops
    pool.incref(0)
    pool.decref(0)
    assert pool.refcount(0) == 1
    with pytest.raises(RuntimeError, match="decref on free block"):
        pool.decref(bids[0])
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(6)


def test_lookup_caps_hit_at_prompt_minus_one():
    """At least one suffix token must always prefill — the first generated
    token comes from a real forward pass, so a FULL-prompt trie hit is
    capped one token short."""
    pool = BlockPool(8, BLOCK)
    toks = tuple(range(100, 108))  # two full blocks
    bids = pool.alloc(2)
    pool.insert(toks, bids)
    m = pool.lookup(toks)  # same 8 tokens: cap = 7 -> 1 full block + tail 3
    assert [b for b in m.block_ids] == [bids[0]]
    assert m.tail_donor == bids[1] and m.tail_len == 3
    assert m.hit_tokens(BLOCK) == 7
    # lookup PINNED both: refcounts = 1 slot + 1 trie (+1 temp for donor)
    assert pool.refcount(bids[0]) == 3  # slot + trie + lookup pin
    assert pool.refcount(bids[1]) == 3


def test_trie_insert_dedupe_returns_swaps():
    pool = BlockPool(8, BLOCK)
    toks = tuple(range(4))
    first = pool.alloc(1)
    pool.insert(toks, first)
    dup = pool.alloc(1)
    swaps = pool.insert(toks, dup)  # identical key, different block
    assert swaps == [(0, dup[0], first[0])]
    assert pool.refcount(dup[0]) == 0  # duplicate released by insert
    assert pool.refcount(first[0]) == 3  # slot + trie + converged slot


def test_lru_eviction_frees_idle_trie_leaves_only():
    pool = BlockPool(4, BLOCK)  # 3 allocatable pages
    a = pool.alloc(2)
    pool.insert(tuple(range(8)), a)  # chain: a[0] -> a[1]
    pool.release(a)  # slot refs dropped; both live only in the trie
    # demand all 3 pages: the LEAF a[1] evicts first, then its parent
    got = pool.alloc(3)
    assert len(got) == 3 and pool.evictions == 2
    assert pool.trie_blocks == 0
    # pinned blocks are never evicted
    pool2 = BlockPool(4, BLOCK)
    b = pool2.alloc(2)
    pool2.insert(tuple(range(8)), b)  # keep the slot refs: all pinned
    assert not pool2.ensure(2)  # 1 free + 2 pinned: demand can't be met
    with pytest.raises(RuntimeError, match="exhausted"):
        pool2.alloc(2)


def test_drain_reclaims_every_block():
    pool = BlockPool(8, BLOCK)
    bids = pool.alloc(3)
    pool.insert(tuple(range(12)), bids)
    pool.lookup(tuple(range(12)))  # extra pins
    pool.drain()
    assert pool.used_blocks == 0 and pool.free_blocks == 7
    assert pool.trie_blocks == 0
    assert pool.lookup(tuple(range(12))).hit_tokens(BLOCK) == 0


def test_default_kv_blocks_covers_all_slots_full_length():
    assert default_kv_blocks(3, 16, 4) == 13  # 3*4 pages + null
    assert default_kv_blocks(1, 5, 4) == 3  # ceil(5/4)=2 + null


# ---------------------------------------------------------------------------
# serve_prefix: the tenth calibrated decision site
# ---------------------------------------------------------------------------


def test_serve_prefix_crossover_and_override():
    eng = CostEngine()
    big = dict(cow_blocks=0, chunk=512, block_size=16,
               flops_per_token=2e10, weight_bytes=1e10)
    # a 7B-class prompt: skipping 512 tokens of prefill dwarfs the host
    # lookup walk -> reuse wins and value is the applied hit length
    dec = eng.decide_serve_prefix(1024, hit_tokens=512, **big)
    assert dec.choice == "use_prefix" and dec.value == 512
    assert dec.predicted.total < dec.baseline.total
    # no hit -> nothing to reuse
    assert eng.decide_serve_prefix(1024, hit_tokens=0, **big).value == 0
    # toy-scale: a CoW page copy (one dispatch) outweighs the skipped
    # six tokens of compute -> honest full_prefill
    toy = dict(cow_blocks=1, chunk=8, block_size=4,
               flops_per_token=2e5, weight_bytes=1e5)
    assert eng.decide_serve_prefix(8, hit_tokens=6, **toy).value == 0
    # override pins the verdict either way, still priced + ledgered
    assert eng.decide_serve_prefix(
        8, hit_tokens=6, override="use_prefix", **toy).value == 6
    assert eng.decide_serve_prefix(
        1024, hit_tokens=512, override="full_prefill", **big).value == 0
    rows = [e for e in eng.ledger.entries if e.site == "serve_prefix"]
    assert len(rows) == 5 and all(e.predicted_s >= 0 for e in rows)


def test_prefill_chunk_never_pads_past_max_len():
    """Chunk widths whose padded prompt overflows max_len are dropped from
    the sweep (the clamped final chunk would overwrite real cache rows)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    sched = ServeScheduler(cfg, CostEngine(), max_len=14)
    chunk, _ = sched.prefill_chunk(13, active_decodes=0)
    assert -(-13 // chunk) * chunk <= 14


# ---------------------------------------------------------------------------
# Paged decode: token identity across families + slot turnover
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-vl-72b",
                                  "rwkv6-3b", "recurrentgemma-2b"])
def test_paged_matches_static_token_identical(arch):
    """Paged continuous serve (pages smaller than a prompt, 6 requests
    turning over 2 slots) must reproduce the dense static baseline exactly.
    Attention-free state (rwkv/window rings) stays per-slot dense — those
    families exercise the mixed paged/dense state tree."""
    cfg, model, params = _build(arch)
    prompts = _prompts(cfg, 6)
    rt = Runtime()
    static = rt.serve(cfg, [Request(f"r{i}", prompts[i], MAX_NEW)
                            for i in range(6)],
                      mode="static", model=model, params=params,
                      max_len=MAX_LEN, eos_id=0)
    engine = _engine(model, params, paged=True, block_size=BLOCK)
    rep = _run(engine, prompts)
    assert rep.state_counts() == {"COMPLETED": 6}
    for i in range(6):
        np.testing.assert_array_equal(
            rep.output(f"r{i}", MAX_NEW), static.outputs[f"r{i}"])
    # KV accounting surfaced host-side (mirrors only, never a device sync)
    assert rep.reserved_blocks > 0 and rep.live_tokens > 0
    d = rep.as_dict()
    for k in ("live_tokens", "reserved_blocks", "prefix_hit_tokens",
              "prefilled_tokens", "cow_count", "prefix_hit_rate"):
        assert k in d
    # prefix reuse only arms on all-attention stacks; paged storage itself
    # works everywhere decoder-only
    assert engine.prefix_cache == (arch in ("tinyllama-1.1b",
                                            "qwen2-vl-72b"))
    # every slot released; only trie-resident pages may stay allocated
    assert engine.pool.free_count == engine.pool.n_slots
    assert engine.pool.blocks.used_blocks == engine.pool.blocks.trie_blocks


def test_paged_scan_layer_layout():
    """n_layers=4 triggers the scan-stacked layer layout: pk/pv gain a
    leading layer axis and the block axis moves to position 1."""
    cfg, model, params = _build(n_layers=4)
    prompts = _prompts(cfg, 3, seed=3)
    rt = Runtime()
    static = rt.serve(cfg, [Request(f"r{i}", prompts[i], MAX_NEW)
                            for i in range(3)],
                      mode="static", model=model, params=params,
                      max_len=MAX_LEN, eos_id=0)
    engine = _engine(model, params, paged=True, block_size=BLOCK)
    rep = _run(engine, prompts)
    for i in range(3):
        np.testing.assert_array_equal(
            rep.output(f"r{i}", MAX_NEW), static.outputs[f"r{i}"])


def test_paged_engine_rejects_bad_configs():
    cfg, model, params = _build()
    with pytest.raises(ValueError, match="block_size"):
        _engine(model, params, paged=True, block_size=0)
    rt = Runtime()
    trace = synthetic_trace(1, prompt_len=4, max_new=2,
                            vocab_size=cfg.vocab_size, seed=0)
    with pytest.raises(ValueError, match="static"):
        rt.serve(cfg, trace, mode="static", model=model, params=params,
                 paged=True)


# ---------------------------------------------------------------------------
# Shared-prefix traffic: prefill once, reuse everywhere
# ---------------------------------------------------------------------------


def test_shared_prefix_prefills_once_and_ledgers_tenth_site():
    """Six requests share a 6-token prefix; admission is serialized
    (1 slot) so every request past the first sees the trie populated.
    Prefilled tokens must drop >=2x vs the hit-less bound, the partial
    2-token tail must come from copy-on-write (8-token prompts = two full
    pages at block 4, and only FULL pages publish to the trie — the tail
    hit rides the second page of the first request), and every admission
    must land a serve_prefix ledger row with a measured wall time."""
    cfg, model, params = _build()
    rt = Runtime()
    set_default_runtime(rt)
    p_len, new = 8, 8  # p_len + new == MAX_LEN
    prompts = _prompts(cfg, 6, p=p_len, seed=7)
    prompts[:, :6] = prompts[0, :6]  # shared system prefix
    reqs = [Request(f"r{i}", prompts[i], new) for i in range(6)]
    static = rt.serve(cfg, [Request(f"r{i}", prompts[i], new)
                            for i in range(6)],
                      mode="static", model=model, params=params,
                      max_len=MAX_LEN, eos_id=0)
    engine = _engine(model, params, n_slots=1, paged=True, block_size=BLOCK,
                     prefix_cache="force")
    rep = engine.run(reqs, now_fn=lambda: 0.0)
    for i in range(6):
        np.testing.assert_array_equal(
            rep.output(f"r{i}", new), static.outputs[f"r{i}"])
    total = 6 * p_len
    assert rep.prefix_hit_tokens + rep.prefilled_tokens == total
    assert rep.prefilled_tokens * 2 <= total, (
        f"prefilled {rep.prefilled_tokens} of {total}")
    # 6-token prefix at block 4 = one shared page + a 2-token CoW tail
    assert rep.cow_count == 5
    assert 0.0 < rep.prefix_hit_rate < 1.0
    rows = [e for e in rt.ledger.entries if e.site == "serve_prefix"]
    assert len(rows) == 12  # decision + measured re-record per admission
    assert sum(1 for e in rows if e.measured_s is not None) == 6
    assert sum(1 for e in rows if e.choice == "use_prefix") >= 5


def test_prefix_auto_verdict_is_costed_not_forced():
    """prefix_cache=True asks the CostEngine per prompt; at toy scale the
    honest verdict is full_prefill (lookup + CoW outweigh six tokens of
    compute), so tokens still match and the site is still ledgered."""
    cfg, model, params = _build()
    rt = Runtime()
    set_default_runtime(rt)
    prompts = _prompts(cfg, 3, seed=7)
    prompts[:, :6] = prompts[0, :6]
    engine = _engine(model, params, n_slots=1, paged=True, block_size=BLOCK)
    rep = _run(engine, prompts)
    assert rep.state_counts() == {"COMPLETED": 3}
    rows = [e for e in rt.ledger.entries if e.site == "serve_prefix"]
    assert rows, "auto mode must still query the serve_prefix site"


# ---------------------------------------------------------------------------
# Lifecycle interplay: preemption / deadline / fatal abort
# ---------------------------------------------------------------------------


def _tick_clock(dt=1e-3):
    t = [0.0]

    def now():
        t[0] += dt
        return t[0]

    return now


def test_preemption_releases_blocks_and_resume_repins_prefix():
    """A preempted victim's pages go back to the pool (only trie pins
    survive), and its re-admission re-pins the prefix it published before
    eviction — the resume prefill is suffix-only and token-identical."""
    cfg, model, params = _build()
    prompts = _prompts(cfg, 2, seed=5)
    # the default 1-slot pool (5 pages) would LRU-evict low's idle trie
    # pages while high decodes to max_len; size the pool so the published
    # prefix survives for the resume to re-pin
    engine = _engine(model, params, n_slots=1, macro_step=1, eos_id=-1,
                     paged=True, block_size=BLOCK, kv_blocks=16,
                     prefix_cache="force")
    low = Request("low", prompts[0], MAX_NEW, priority=0)
    high = Request("high", prompts[1], MAX_NEW, arrival_s=0.01, priority=5)
    rep = engine.run([low, high], now_fn=_tick_clock())
    assert rep.state_counts() == {"COMPLETED": 2}
    assert low.preemptions >= 1
    # the resume re-pinned blocks low published before eviction
    assert rep.prefix_hit_tokens > 0
    fresh = _engine(model, params, n_slots=1, eos_id=-1)
    for req, seed_prompt in ((low, prompts[0]), (high, prompts[1])):
        solo = fresh.run([Request("solo", seed_prompt, MAX_NEW)],
                         now_fn=lambda: 0.0)
        assert list(req.tokens) == list(solo.requests[0].tokens)
    # nothing leaked: slots free, only trie residents still hold pages
    assert engine.pool.free_count == 1
    pool = engine.pool.blocks
    assert pool.used_blocks == pool.trie_blocks


def test_deadline_eviction_releases_paged_slot():
    cfg, model, params = _build()
    prompts = _prompts(cfg, 1)
    engine = _engine(model, params, n_slots=1, macro_step=1, eos_id=-1,
                     paged=True, block_size=BLOCK)
    req = Request("r0", prompts[0], MAX_NEW, deadline_s=0.05)
    rep = engine.run([req], now_fn=_tick_clock(dt=5e-3))
    assert req.state == RequestState.TIMED_OUT
    assert engine.pool.free_count == 1
    pool = engine.pool.blocks
    assert pool.used_blocks == pool.trie_blocks


def test_fatal_abort_drains_whole_block_pool():
    """The PR 7 drain invariant extends to paging: a fatal abort leaves
    the BlockPool fully reclaimed (trie included) and the engine serves
    the next trace token-identically."""
    cfg, model, params = _build()
    prompts = _prompts(cfg, 2, seed=11)
    clean_engine = _engine(model, params, paged=True, block_size=BLOCK)
    clean = _tokens(_run(clean_engine, prompts))
    engine = _engine(
        model, params, macro_step=1, paged=True, block_size=BLOCK,
        injector=FaultInjector((FaultSpec("raise", site="macro",
                                          after=0, fatal=True),)))
    reqs = [Request(f"r{i}", prompts[i], MAX_NEW) for i in range(2)]
    with pytest.raises(FatalFault):
        engine.run(reqs, now_fn=lambda: 0.0)
    assert all(r.state.terminal for r in reqs)
    assert engine.pool.free_count == engine.pool.n_slots
    pool = engine.pool.blocks
    assert pool.used_blocks == 0 and pool.trie_blocks == 0
    assert pool.free_blocks == pool.n_blocks - 1
    engine.injector = None
    rep = _run(engine, prompts)
    assert rep.state_counts() == {"COMPLETED": 2}
    assert _tokens(rep) == clean


# ---------------------------------------------------------------------------
# Admission prefill on the group's rows, not every slot
# ---------------------------------------------------------------------------


def _trace(cfg, shape, seed=11):
    """Requests from (prompt_len, arrival_s, priority) triples."""
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(1, cfg.vocab_size, (p,)).astype(
        np.int32), MAX_NEW, arrival_s=a, priority=pr)
        for i, (p, a, pr) in enumerate(shape)]


def _admissions(engine, reqs, now_fn):
    """The report and each admission's (group size, rows, padded length)."""
    with SpanRecorder() as rec:
        engine.tracer = rec
        rep = engine.run(reqs, now_fn=now_fn)
    engine.tracer = None
    return rep, [(len(s.attrs["rids"]), s.attrs["rows"], s.attrs["padded_len"])
                 for s in rec.spans if s.name == "serve/admit"
                 and s.attrs.get("rids")]


# case -> (n_slots, (prompt_len, arrival_s, priority) per request, engine
# keywords, group sizes and prefill rows of the admissions)
NARROW_CASES = {
    "one": (8, [(7, 0.0, 0)], {}, [(1, 1)]),
    # 3 rows pad to 4: one row carries the dropped index n_slots
    "three": (8, [(7, 0.0, 0), (5, 0.0, 0), (6, 0.0, 0)], {}, [(3, 4)]),
    "all_slots": (8, [(4 + i % 4, 0.0, 0) for i in range(8)], {}, [(8, 8)]),
    # the later pair pins the first request's published pages
    "prefix_hit": (8, [(7, 0.0, 0), (7, 1.0, 0), (7, 1.0, 0)],
                   {"prefix_cache": "force"}, [(1, 1), (2, 2)]),
    # "high" evicts a low-priority slot; the victim re-admits alone
    "preempted": (2, [(7, 0.0, 0), (6, 0.0, 0), (5, 0.005, 5)],
                  {"macro_step": 1}, [(2, 2), (1, 1), (1, 1)]),
}


@pytest.mark.parametrize("case", sorted(NARROW_CASES))
def test_narrow_prefill_matches_every_slot_prefill(case):
    """An admission prefills only its group's rows, padded to a power of
    two, and serves the same tokens as a prefill over every slot; the
    padded-token counter and ``narrow_admissions`` follow the rows."""
    n_slots, shape, kw, want = NARROW_CASES[case]
    cfg, model, params = _build()
    runs = {}
    for name in ("narrow", "wide"):
        engine = _engine(model, params, n_slots=n_slots, eos_id=-1,
                         paged=True, block_size=BLOCK, kv_blocks=64, **kw)
        if name == "wide":  # the program over every slot, steered here
            engine._row_buckets = [n_slots]
        reqs = _trace(cfg, shape)
        if case == "prefix_hit":
            for r in reqs[1:]:
                r.prompt[:6] = reqs[0].prompt[:6]
        # preemption needs arrivals while slots decode: a ticking clock
        runs[name] = _admissions(engine, reqs, _tick_clock()
                                 if case == "preempted" else lambda: 0.0)
    (rep, adm), (wide, wide_adm) = runs["narrow"], runs["wide"]
    assert rep.state_counts() == {"COMPLETED": len(shape)}
    assert _tokens(rep) == _tokens(wide)
    assert [(g, r) for g, r, _ in adm] == want
    assert [(g, n_slots) for g, _ in want] == [(g, r) for g, r, _ in wide_adm]
    assert rep.prefill_padded_tokens == sum(r * n for _, r, n in adm)
    assert wide.prefill_padded_tokens == sum(r * n for _, r, n in wide_adm)
    assert rep.narrow_admissions == sum(r < n_slots for _, r in want)
    assert wide.narrow_admissions == 0
    if case == "prefix_hit":
        assert rep.prefix_hit_tokens == wide.prefix_hit_tokens > 0
    if case == "preempted":
        assert rep.preemptions == wide.preemptions >= 1


def test_narrow_prefill_leaves_other_slots_alone():
    """The narrow program moves only its group's positions (pad rows are
    dropped) and gives each row the first token the every-slot program
    gives that slot."""
    cfg, model, params = _build()
    engine = _engine(model, params, n_slots=8, paged=True, block_size=BLOCK)
    bt = np.zeros((8, -(-MAX_LEN // BLOCK)), np.int32)
    slots = [1, 4, 6]
    for j, s in enumerate(slots):
        bt[s, :2] = [1 + 2 * j, 2 + 2 * j]
    pos0 = np.arange(8, dtype=np.int32) + 3
    lengths = np.zeros((8,), np.int32)
    lengths[slots] = [7, 5, 6]
    starts = np.zeros((8,), np.int32)
    toks = _prompts(cfg, 3, seed=4)
    wide_toks = np.zeros((1, 8, PROMPT_LEN), np.int32)
    wide_toks[0, slots] = toks
    narrow_toks = np.zeros((1, 4, PROMPT_LEN), np.int32)
    narrow_toks[0, :3] = toks
    out = {}
    for name, chunks in (("narrow", narrow_toks), ("wide", wide_toks)):
        state = dict(engine.pool.state, pos=jnp.asarray(pos0))
        first, state = engine._prefill(
            params, state, jnp.asarray(chunks), jnp.asarray(lengths),
            jnp.asarray(starts), jnp.asarray(bt))
        engine.pool.state = state
        out[name] = (np.asarray(first), np.asarray(state["pos"]))
    first, pos = out["narrow"]
    want = pos0.copy()
    want[slots] = lengths[slots]
    np.testing.assert_array_equal(pos, want)
    np.testing.assert_array_equal(out["wide"][1], want)
    assert first.shape == (4,)
    np.testing.assert_array_equal(first[:3], out["wide"][0][slots])


def test_warmup_compiles_every_row_bucket():
    """After ``warmup`` at the trace's longest prompt, a run whose groups
    take every row bucket (4, 2 and 1 of 4 slots) compiles nothing."""
    cfg, model, params = _build()
    engine = _engine(model, params, n_slots=4, eos_id=-1, macro_step=2,
                     paged=True, block_size=BLOCK)
    shape = ([(PROMPT_LEN, 0.0, 0)] * 4 + [(5, 1.0, 0)] * 2
             + [(6, 2.0, 0)])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compiles = []

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    try:
        jax.clear_caches()  # what earlier tests compiled is not warm-up's
        engine.warmup(PROMPT_LEN, MAX_NEW)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            rep, adm = _admissions(engine, _trace(cfg, shape), lambda: 0.0)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert [(g, r) for g, r, _ in adm] == [(4, 4), (2, 2), (1, 1)]
    assert rep.state_counts() == {"COMPLETED": len(shape)}
    assert compiles == []


@pytest.mark.parametrize("arch,paged", [
    ("tinyllama-1.1b", False),    # dense per-slot caches
    ("rwkv6-3b", True),           # recurrent state per slot
    ("recurrentgemma-2b", True),  # rglru state and local window rings
])
def test_engines_outside_the_rule_prefill_every_slot(arch, paged):
    """Engines with per-slot state besides the positions keep the prefill
    over every slot.  (Enc-dec engines cannot be paged, so they fall under
    the dense case.)"""
    cfg, model, params = _build(arch)
    kw = {"paged": True, "block_size": BLOCK} if paged else {}
    engine = _engine(model, params, n_slots=4, eos_id=-1, **kw)
    rep, adm = _admissions(
        engine, _trace(cfg, [(7, 0.0, 0), (5, 1.0, 0), (6, 1.0, 0)]),
        lambda: 0.0)
    assert rep.state_counts() == {"COMPLETED": 3}
    assert [(g, r) for g, r, _ in adm] == [(1, 4), (2, 4)]
    assert rep.narrow_admissions == 0


# ---------------------------------------------------------------------------
# Mesh execution (subprocess: forced 8-device CPU)
# ---------------------------------------------------------------------------


def test_sharded_paged_token_identity():
    """Paged block tables threaded through the sharded macro-step/prefill
    programs: forced tp=8 + paging must match the single-device static
    baseline through slot turnover, prefix reuse forced on."""
    out = run_distributed("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.runtime import Runtime, synthetic_trace

        cfg = get_config("tinyllama-1.1b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rt = Runtime()
        common = dict(model=model, params=params, max_len=16, eos_id=0)
        trace = lambda: synthetic_trace(6, prompt_len=8, max_new=8,
                                        vocab_size=cfg.vocab_size,
                                        arrival="all", seed=0,
                                        prefix_share=1.0, prefix_len=6)
        static = rt.serve(cfg, trace(), mode="static", **common)
        paged = rt.serve(cfg, trace(), mode="continuous", slots=2,
                         mesh_shape={"data": 1, "model": 8},
                         shard_params="shard", paged=True, block_size=4,
                         prefix_cache="force", **common)
        s = np.stack([static.outputs[f"r{i}"] for i in range(6)])
        c = np.stack([paged.report.output(f"r{i}", 8) for i in range(6)])
        np.testing.assert_array_equal(c, s)
        rep = paged.report
        assert rep.device_count == 8
        assert rep.reserved_blocks > 0
        assert rep.prefix_hit_tokens > 0, "second admission wave must hit"
        print("PAGED_SHARD_OK hits", rep.prefix_hit_tokens)
    """)
    assert "PAGED_SHARD_OK" in out
