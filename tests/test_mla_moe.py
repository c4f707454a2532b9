"""Latent attention (MLA), the latent paged cache and the expert layer that
holds some of the router's experts, against the benchmark's plain reference
(``chipbench/references/mla_moe_decoder.py``) at a tiny size on the CPU.

Everything here computes in float32 on both sides, so the tolerances are
float32 rounding: the plain and the absorbed form, and the program and the
reference, sum the same products in different orders.
"""

import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn_lib
from repro.models import build_model
from repro.models import ffn as ffn_lib
from repro.training.step import make_batched_prefill, make_decode_macro_step

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import spec, weights  # noqa: E402

# float32 on both sides: products summed in another order differ by a few
# ulps of the largest logit (about 0.5), far below 1e-4
LOGIT_ATOL = 1e-4


@pytest.fixture(scope="module")
def tiny():
    """(family, reference, sizes, drawn weights, program model, params) of
    the Moonlight configuration at a tiny size, in float32."""
    config = json.loads(
        (ROOT / "chipbench/configs/moonlight-16b-a3b-e8.json").read_text())
    config.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  moe_intermediate_size=32, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=3,
                  vocab_size=256, max_position_embeddings=128,
                  dtype="float32")
    fam = spec.find_module("families", "mla_moe_decoder")
    ref = spec.find_module("references", "mla_moe_decoder")
    s = fam.sizes(config)
    model = build_model(fam.program_config(config))
    w = weights.draw(fam, s, 2**31 + 3)
    return fam, ref, s, w, model, weights.program_params(fam, w, model)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def test_published_config_counts():
    cfg = get_config("moonshot-v1-16b-a3b")
    assert (cfg.n_layers, cfg.first_k_dense, cfg.kv_lora_rank,
            cfg.latent_width, cfg.n_experts, cfg.moe_shared_experts) == \
        (27, 1, 512, 576, 64, 2)
    assert cfg.param_count() == pytest.approx(15.96e9, rel=1e-3)
    assert cfg.active_param_count() == pytest.approx(2.91e9, rel=2e-3)
    # one chip of an 8-chip expert group: 8 of the 64 experts
    held = dataclasses.replace(cfg, held_expert_count=8)
    assert held.param_count() == pytest.approx(3.3645e9, rel=1e-4)
    # 576 bfloat16 values per token and layer, no head axis
    assert cfg.n_layers * cfg.kv_bytes_per_token(2) == 27 * 576 * 2


def test_absorbed_scores_equal_plain_form():
    """Scores against the cached latent (absorbed) are the plain form's
    q . k with per-head keys expanded from the latent."""
    b, s, t, h, nope, rope, r = 2, 3, 11, 4, 16, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q_nope = jax.random.normal(ks[0], (b, s, h, nope))
    q_rope = jax.random.normal(ks[1], (b, s, h, rope))
    latent = jax.random.normal(ks[2], (b, t, r))
    k_rope = jax.random.normal(ks[3], (b, t, rope))
    wkv_b = jax.random.normal(ks[4], (r, h, nope + 16)) / r ** 0.5
    k, _ = attn_lib.mla_plain_kv(latent, k_rope, wkv_b, nope)
    plain = jnp.einsum("bshd,bthd->bhst",
                       jnp.concatenate([q_nope, q_rope], -1), k)
    absorbed = attn_lib.mla_absorbed_scores(q_nope, q_rope, latent, k_rope,
                                            wkv_b[..., :nope])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain),
                               rtol=1e-5, atol=1e-4)


def test_absorbed_decode_equals_plain_attention():
    """The whole absorbed attention (values absorbed on the output side)
    against causal attention over the expanded keys and values."""
    b, t, h, nope, rope, r, dv = 2, 9, 4, 16, 8, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, t, h, nope + rope))
    latent = jax.random.normal(ks[1], (b, t, r))
    k_rope = jax.random.normal(ks[2], (b, t, rope))
    wkv_b = jax.random.normal(ks[3], (r, h, nope + dv)) / r ** 0.5
    k, v = attn_lib.mla_plain_kv(latent, k_rope, wkv_b, nope)
    plain = attn_lib.dense_attention(q, k, v, causal=True)
    absorbed = attn_lib.mla_decode_attention(
        q[..., :nope], q[..., nope:], jnp.concatenate([latent, k_rope], -1),
        t, wkv_b, scale=(nope + rope) ** -0.5)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight layers, each holding 8 of the 64 experts and routing over all
    of them, add up to the layer that holds all 64, with the shared MLP
    (computed alike on every chip) counted once."""
    d, f, n, held = 16, 8, 64, 8
    p = ffn_lib.moe_init(jax.random.PRNGKey(2), d, f, n, "swiglu",
                         shared_ff=2 * f, scoring="sigmoid")
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, d))
    kw = dict(top_k=6, activation="swiglu", scoring="sigmoid", scale=2.446)
    whole, _, counts = ffn_lib.moe_apply(p, x, **kw)
    parts, hits = [], []
    for first in range(0, n, held):
        share = {k: p[k] for k in ("router", "router_bias")}
        share.update({k: p[k][first:first + held]
                      for k in ("w_in", "w_gate", "w_out")})
        y, _, c = ffn_lib.moe_apply(share, x, first=first, **kw)
        parts.append(y)
        hits.append(c)
    shared = ffn_lib.ffn_apply(p["shared"], x, "swiglu")
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    # every routed pair lands on exactly one share
    assert int(counts[0]) == sum(int(c[0]) for c in hits) == 10 * 6


def test_near_ties_are_held_experts_at_the_cut(tiny):
    """The reference's near ties: a position where some expert layer has a
    held expert (0..7) within the tie width (here bfloat16's, 2^-8) of the
    cut between the 6th and the 7th choice.  Two experts that are not held
    tied at the cut swap nothing the layer computes, so they are no tie."""
    _, ref, s, *_ = tiny
    n, tie = s["experts"], ref.tie_width(dict(s, dtype="bfloat16"))
    assert tie == 2.0**-8 and ref.tie_width(s) == 2.0**-24  # float32
    apart = jnp.linspace(1.0, 0.0, n)  # 1/63 between neighbours
    perm = jnp.roll(jnp.arange(n), -20)  # experts 20..25 lead: none held
    unheld = apart[jnp.argsort(perm)]
    unheld = unheld.at[26].set(unheld[25] - tie / 10)  # 7th, not held, tied
    held = apart.at[6].set(apart[5] - tie / 10)  # 7th is held, tied
    choice = jnp.stack([jnp.stack([apart, unheld, apart]),
                        jnp.stack([apart, apart, held])])  # (layers, S, E)
    near = np.asarray(ref.tie_distance(s, choice) < tie)
    assert near.tolist() == [False, False, True]


def test_gaps_leave_out_near_ties_only(tiny):
    """``gaps`` reads 0 exactly where ``near_tie`` is set, and the
    reference's own gap elsewhere."""
    _, ref, s, w, *_ = tiny
    tokens = jnp.asarray(_tokens(24, seed=5))
    plain = ref.gaps(w, s, tokens, tie=0.0)
    assert not np.asarray(plain["near_tie"]).any()
    for tie in (2e-2, 1.0):
        got = ref.gaps(w, s, tokens, tie=tie)
        near = np.asarray(got["near_tie"])
        want = np.where(near, 0.0, np.asarray(plain["gap"]))
        np.testing.assert_array_equal(np.asarray(got["gap"]), want)
    assert near.all()


def _paged_state(model, slots, max_len, bs):
    blocks = slots * -(-max_len // bs) + 1
    tables = np.arange(1, blocks, dtype=np.int32).reshape(slots, -1)
    return (model.init_decode_state(slots, max_len, per_slot=True,
                                    paging=(blocks, bs)),
            jnp.asarray(tables))


def test_prefill_then_paged_decode_match_the_reference(tiny):
    """The program's prefill (one chunk through the latent paged pool) and
    then its token-by-token decode, both absorbed, give the reference's
    full-forward logits at every position."""
    _, ref, s, w, model, params = tiny
    tokens = _tokens(21)
    want = np.asarray(ref.logits(w, s, jnp.asarray(tokens)))
    state, tables = _paged_state(model, 1, 32, 4)
    assert {leaf.ndim for leaf in jax.tree.leaves(state["layers"])} == {3, 4}
    step = jax.jit(model.decode_step)
    prompt = 13
    logits, state = step(params, state, {
        "tokens": jnp.asarray(tokens[None, :prompt]), "block_tables": tables})
    got = [np.asarray(logits[0])]
    for t in range(prompt, len(tokens)):
        logits, state = step(params, state, {
            "tokens": jnp.asarray(tokens[None, t:t + 1]),
            "block_tables": tables})
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=0,
                               atol=LOGIT_ATOL)


def test_plain_forward_matches_the_reference(tiny):
    _, ref, s, w, model, params = tiny
    tokens = _tokens(17, seed=1)
    got, _ = jax.jit(model.forward_logits)(params,
                                           {"tokens": jnp.asarray(tokens)[None]})
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(ref.logits(w, s, jnp.asarray(tokens))),
                               rtol=0, atol=LOGIT_ATOL)


def _recount(ref, w, s, seqs, spans):
    """[held pairs, held experts reached] from the reference's routing:
    ``seqs`` the sequences, ``spans`` per program step the (sequence,
    positions) it computed."""
    ids = [np.asarray(ref.forward(w, s, jnp.asarray(q))[1]) for q in seqs]
    held = np.arange(s["held"])
    pairs = reached = 0
    for step in spans:
        for layer in range(ids[0].shape[0]):
            hit = np.zeros(s["held"], bool)
            for i, pos in step:
                chosen = ids[i][layer, pos]  # (positions, top_k)
                pairs += int(np.isin(chosen, held).sum())
                hit |= np.isin(held, chosen)
            reached += int(hit.sum())
    return [pairs, reached]


def test_counts_match_a_recount_of_the_reference_routing(tiny):
    """The prefill's and the macro-step's held-expert counts equal a host
    recount from the reference's own routing of the same tokens (the
    program and the reference choose the same experts here: float32 on
    both sides, and no choice flips)."""
    _, ref, s, w, model, params = tiny
    bs, max_len, k = 4, 32, 3
    state, tables = _paged_state(model, 2, max_len, bs)
    prompts = [_tokens(9, seed=2), _tokens(6, seed=3)]
    chunks = np.zeros((1, 2, 9), np.int32)
    for i, q in enumerate(prompts):
        chunks[0, i, :len(q)] = q
    lengths = jnp.asarray([9, 6], jnp.int32)
    first, state, counts = jax.jit(make_batched_prefill(model))(
        params, state, jnp.asarray(chunks), lengths, None, tables)
    want = _recount(ref, w, s, prompts,
                    [[(0, np.arange(9)), (1, np.arange(6))]])
    assert [int(c) for c in counts] == want
    macro = jax.jit(make_decode_macro_step(model, k, eos_id=-1, pad_id=0))
    emitted, state, counts = macro(params, state, first,
                                   jnp.asarray([True, True]),
                                   jnp.asarray([k, 2], jnp.int32), tables)
    emitted = np.asarray(emitted)
    seqs = [np.concatenate([prompts[0], [int(first[0])], emitted[0]]),
            np.concatenate([prompts[1], [int(first[1])], emitted[1]])]
    # inner step j feeds each live row its last token, at its length + j
    steps = [[(i, [len(prompts[i]) + j]) for i, n in enumerate([k, 2])
              if j < n] for j in range(k)]
    assert [int(c) for c in counts] == _recount(ref, w, s, seqs, steps)


def test_scopes_name_the_parts_in_the_compiled_programs(tiny):
    """The latent attention and the expert layer's router, experts and
    shared MLP carry their scopes in the ``op_name`` of the compiled
    prefill and macro-step, where the device trace finds them."""
    _, _, _, _, model, params = tiny
    state, tables = _paged_state(model, 2, 16, 4)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    programs = {
        "prefill_fn": (make_batched_prefill(model),
                       (i32(1, 2, 8), i32(2), i32(2), tables)),
        "macro_fn": (make_decode_macro_step(model, 2, eos_id=-1, pad_id=0),
                     (i32(2), jax.ShapeDtypeStruct((2,), jnp.bool_), i32(2),
                      tables)),
    }
    for name, (fn, args) in programs.items():
        text = jax.jit(fn).lower(params, state, *args).compile().as_text()
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("mla", "moe_router", "moe_experts", "moe_shared"):
            assert any(n.startswith(f"jit({name})/") and
                       scope in n.split("/") for n in names), (name, scope)
