"""Whole runs of a tiny cell on the CPU, past the harness's look for a chip:
a sound run is correct; the float8 control and each fault the timed path
can have are caught by the comparison with the plain reference."""

import time

import jax.numpy as jnp
import pytest

from chipbench import harness, spec

SEED = 2**31 + 99


def run(tiny, name, control=False):
    bench, d = tiny
    cell = spec.load_cell(name, d, bench=bench, bench_dir=d)
    return harness.run_cell(cell, seed=SEED, seconds=2.0, trace=False,
                            t_start=time.perf_counter(), require_chip=False,
                            control=control, log=lambda msg: None)


@pytest.mark.parametrize("name", ["tiny-batch", "tiny-chat"])
def test_sound_run_is_correct(tiny, name):
    out = run(tiny, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["readings"]["compared_tokens"] >= 50
    assert "max_gap_fp8" not in out["readings"]
    assert set(out["metrics"]) == {m.name for m in out["run"].cell.end_to_end}


@pytest.mark.parametrize("name", ["tiny-batch", "tiny-chat"])
def test_control_is_not_correct(tiny, name):
    """The float8 control in the program's place: the run itself reports
    ``correct`` false, on the control's gap; the program's reading, taken
    beside it, stays under the limit."""
    out = run(tiny, name, control=True)
    gap = out["checks"]["max_gap"]
    assert not out["correct"], out["checks"]
    assert gap["value"] == out["readings"]["max_gap_fp8"] > gap["limit"]
    assert out["readings"]["max_gap"] < gap["limit"]


def altered_token(orig):
    def make(model, horizon, **kw):
        fn = orig(model, horizon, **kw)

        def macro_fn(params, state, tok, active, budget, block_tables=None):
            em, st = fn(params, state, tok, active, budget, block_tables)
            return (em + 1) % model.cfg.vocab_size, st
        return macro_fn
    return make


def state_unchanged(orig):
    def make(model, horizon, **kw):
        fn = orig(model, horizon, **kw)

        def macro_fn(params, state, tok, active, budget, block_tables=None):
            em, _ = fn(params, state, tok, active, budget, block_tables)
            return em, state
        return macro_fn
    return make


def half_batch(orig):
    def make(model, horizon, **kw):
        fn = orig(model, horizon, **kw)

        def macro_fn(params, state, tok, active, budget, block_tables=None):
            em, st = fn(params, state, tok, active, budget, block_tables)
            keep = (jnp.arange(em.shape[0]) % 2 == 0)[:, None]
            return jnp.where(keep, em, tok[:, None]), st
        return macro_fn
    return make


def altered_first_token(orig):
    def make(model, *a, **kw):
        fn = orig(model, *a, **kw)

        def prefill_fn(params, state, chunks, lengths, starts=None,
                       block_tables=None):
            first, st = fn(params, state, chunks, lengths, starts,
                           block_tables)
            return (first + 1) % model.cfg.vocab_size, st
        return prefill_fn
    return make


@pytest.mark.parametrize("fault,factory", [
    (altered_token, "make_decode_macro_step"),
    (state_unchanged, "make_decode_macro_step"),
    (half_batch, "make_decode_macro_step"),
    (altered_first_token, "make_batched_prefill"),
])
@pytest.mark.parametrize("name", ["tiny-batch", "tiny-chat"])
def test_fault_is_caught(tiny, name, fault, factory, monkeypatch):
    from repro.serving import engine

    monkeypatch.setattr(engine, factory, fault(getattr(engine, factory)))
    out = run(tiny, name)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_gap"]
    assert gap["value"] > gap["limit"]
