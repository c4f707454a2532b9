#!/usr/bin/env python3
"""Smoke test of the serve path on a TPU: the quickest proof that the system
still starts on the chip.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded paths, on a 4-chip host

One chip, in order:

  (a) device check: platform, device kind, count; not a TPU -> exit 2
  (b) serve phi3-mini-3.8b at its published widths (32 layers, d_model 3072,
      random weights from the seed) through ``repro.launch.serve``: 8
      requests of 512 prompt tokens and 64 new ones, all arriving at once,
      4 slots, paged KV in 16-token pages, 2 front-end worker processes,
      and both engines, so the static engine gives a token reference from
      the same weights; every request must complete with 64 tokens
  (c) token agreement between the engines, and each engine's tokens against
      a plain full-sequence forward fed the same tokens (teacher forcing):
      a token that is not the forward's argmax must tie with it to within a
      few bf16 ulps of the top logit
  (d) the paper-domain Pallas kernels, compiled: ``adaptive_matmul`` at
      4096x8192x3072 bf16 and ``distributed_sort`` of 2^20 f32 keys through
      the bitonic kernel, each against XLA on the chip

With ``--chips 4`` only: the continuous serve on a {data: 1, model: 4} mesh
with sharded weights against the same trace served unsharded, and the
sample sort on a 4-device mesh against ``jnp.sort``, with the collective
count and each device's peak memory.  The unsharded tokens are held to the
one-chip tolerance; the sharded ones to it plus twice the measured
deviation between the sharded and unsharded plain forwards, which must
itself stay within ``LAYOUT_ULPS``.

Every phase prints its wall time and the time spent compiling.  Any failed
phase exits non-zero; only a clean run ends with the one-line JSON result
``{"ok": true, "device": {...}}``.  The compile cache follows
``$JAX_COMPILATION_CACHE_DIR`` (else ``<checkout>/.jax_cache``); the cost
model's caches go to a temporary directory the run creates and removes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
ARCH = "phi3-mini-3.8b"
REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS = 8, 512, 64, 4
SEED = 0
SERVE_ARGS = [
    "--arch", ARCH, "--requests", str(REQUESTS),
    "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
    "--slots", str(SLOTS), "--arrival", "all", "--seed", str(SEED),
    # random weights make any end-of-sequence token meaningless: with none,
    # every request must generate exactly MAX_NEW tokens
    "--eos-id", "-1",
    "--block-size", "16",
]
MATMUL_MNK = (4096, 8192, 3072)
SORT_KEYS = 1 << 20
# a token other than the reference argmax is a tie when its reference logit
# is within TIE_ULPS bf16 ulps (2^-8 relative) of the top logit
TIE_ULPS = 4
# the tensor-parallel forward rounds each row-parallel partial product to
# bf16 before the all-reduce, so its logits drift from the one-chip
# forward's by rounding: a few ulps of the largest logit.  A wrong shard
# moves them by its whole magnitude (256 ulps); the bound sits between.
LAYOUT_ULPS = 32


class PhaseFailed(RuntimeError):
    pass


class CompileClock:
    """Sums the backend compiles JAX reports; times phases against them."""

    def __init__(self):
        import jax

        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    @contextlib.contextmanager
    def phase(self, tag: str, what: str):
        """Prints one line for the phase: status, wall time, compile time."""
        t0, c0, n0 = time.perf_counter(), self.seconds, self.programs
        status = "FAILED"
        try:
            yield
            status = "ok"
        finally:
            print(f"[{tag}] {what}: {status}, wall "
                  f"{time.perf_counter() - t0:.1f} s, compile "
                  f"{self.seconds - c0:.1f} s ({self.programs - n0} "
                  f"programs)", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def device_check(chips: int):
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[a] device: platform {d.platform}, kind {d.device_kind!r}, "
          f"count {len(devices)}", flush=True)
    if d.platform != "tpu":
        print("[a] FAILED: no TPU; this smoke runs only on the chip",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) != chips:
        print(f"[a] FAILED: {len(devices)} devices, --chips {chips}",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def serve(extra):
    """One launcher run; every request must complete with MAX_NEW tokens,
    with no step retried (no fault is injected here)."""
    from repro.launch import serve as launcher

    rt, results = launcher.run(SERVE_ARGS + list(extra))
    failed = launcher.failed_requests(results)
    check(not failed, f"{len(failed)} request(s) FAILED: "
          + ", ".join(f"{r.rid} [{r.reason}]" for r in failed))
    for res in results:
        check(len(res.outputs) == REQUESTS, f"[{res.mode}] lost requests")
        if res.report is not None:
            rep = res.report
            check(rep.state_counts() == {"COMPLETED": REQUESTS},
                  f"[{res.mode}] states {rep.state_counts()}")
            check(all(len(r.tokens) == MAX_NEW for r in rep.requests),
                  f"[{res.mode}] a request stopped short of {MAX_NEW}")
            check(rep.step_retries == 0 and rep.watchdog_fires == 0,
                  f"[{res.mode}] {rep.step_retries} step retries, "
                  f"{rep.watchdog_fires} watchdog fires")
    return rt, results


def token_matrix(res, rids):
    import numpy as np

    return np.stack([np.asarray(res.outputs[rid], np.int32) for rid in rids])


def agreement(a, b) -> str:
    """Share of equal tokens, and each row's first differing position."""
    import numpy as np

    same = a == b
    first = [int(np.argmin(row)) if not row.all() else None for row in same]
    return (f"{same.mean():.4f} of {same.size} tokens agree; first "
            f"divergence per request {first}")


def reference_logits(res, tokens):
    """Teacher-forced plain forward of the weights, in the layout they were
    served in, and the prompts ``res`` served, fed ``tokens``: the logits
    (requests, MAX_NEW, vocab) that predict each generated token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = res.engine.model
    prompts = np.stack([np.asarray(r.prompt, np.int32)
                        for r in res.report.requests])
    vocab = model.cfg.vocab_size
    check(bool(((tokens >= 0) & (tokens < vocab)).all()),
          f"token out of [0, {vocab})")

    @jax.jit
    def forward(params, prompts, tokens):
        seq = jnp.concatenate([prompts, tokens[:, :-1]], axis=1)
        logits, _ = model.forward_logits(params, {"tokens": seq})
        return logits[:, prompts.shape[1] - 1:]  # predicts tokens[:, i]

    pred = forward(res.engine.params, prompts, tokens)
    check(bool(jnp.isfinite(pred).all()), "non-finite reference logits")
    return pred


def score_tokens(label: str, tokens, pred, slack=0.0) -> int:
    """Prints how ``tokens`` stand against the reference logits ``pred``;
    returns how many are neither its argmax nor tied with it to within
    ``TIE_ULPS`` bf16 ulps of the top logit, plus ``slack`` (per position)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    top2 = jax.lax.top_k(jnp.asarray(pred), 2)[0]
    top = top2[..., 0]
    mine = jnp.take_along_axis(jnp.asarray(pred), tokens[..., None], -1)
    argmax, gap, margin, tol = (np.asarray(v) for v in (
        jnp.argmax(pred, -1), top - mine[..., 0], top - top2[..., 1],
        TIE_ULPS * 2.0 ** -8 * jnp.abs(top) + slack))
    off = argmax != tokens
    bad = off & (gap > tol)
    # how often the runner-up is within the tolerance at all: the rate at
    # which two correct engines can fork
    print(f"    {label}: {int((~off).sum())}/{off.size} argmax, "
          f"{int((off & ~bad).sum())} ties, {int(bad.sum())} beyond; worst "
          f"gap {float((gap / tol)[off].max()) if off.any() else 0.0:.3f} of "
          f"its tolerance ({float(tol.min()):.4g}..{float(tol.max()):.4g}); "
          f"runner-up within tolerance at {int((margin <= tol).sum())} "
          f"positions", flush=True)
    return int(bad.sum())


def reference_check(res, tokens_by_label) -> None:
    """Each token matrix against the teacher-forced plain forward of
    ``res``'s weights and prompts: every generated token must be the
    forward's argmax or tie with it."""
    for label, tokens in tokens_by_label.items():
        bad = score_tokens(f"{label} vs plain forward", tokens,
                           reference_logits(res, tokens))
        check(not bad, f"{label}: {bad} token(s) neither the reference "
              f"argmax nor tied with it")


def one_chip(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.dispatch import adaptive_matmul
    from repro.core.sort import distributed_sort

    with clock.phase("b", f"serve {ARCH} static + continuous, paged, "
                     f"2 workers"):
        _, (static, cont) = serve(["--engine", "both", "--workers", "2"])
    with clock.phase("c", "token agreement and plain-forward reference"):
        rids = [r.rid for r in cont.report.requests]
        s_tok, c_tok = token_matrix(static, rids), token_matrix(cont, rids)
        print(f"    static vs continuous: {agreement(s_tok, c_tok)}")
        reference_check(cont, {"static": s_tok, "continuous": c_tok})
        del static, cont

    key = jax.random.PRNGKey(SEED)
    with clock.phase("d", "Pallas kernels compiled, against XLA"):
        m, n, k = MATMUL_MNK
        ka, kb, ks = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = jax.random.normal(kb, (k, n), jnp.bfloat16)
        mm = jax.jit(lambda a, b: adaptive_matmul(a, b, use_kernel=True))
        out = kernel_run(mm, "matmul", a, b)
        want = jnp.dot(a, b, preferred_element_type=jnp.float32)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)
                            / (jnp.abs(want) + 1.0)))
        print(f"    matmul {m}x{n}x{k} bf16: max error {err:.3g} "
              f"relative to |XLA f32| + 1")
        check(err <= 2.0 ** -7, f"matmul off XLA by {err:.3g}")  # 1 bf16 ulp

        x = jax.random.normal(ks, (SORT_KEYS,), jnp.float32)
        srt = jax.jit(lambda x: distributed_sort(x, local_sort="pallas")[0])
        out = kernel_run(srt, "bitonic sort", x)
        equal = bool(jnp.array_equal(out, jnp.sort(x)))
        print(f"    sort of {SORT_KEYS} f32 keys equals jnp.sort: {equal}")
        check(equal, "bitonic sort differs from jnp.sort")


def kernel_run(fn, name: str, *args):
    """Compile ``fn``, check a Pallas kernel is in it, run and time it."""
    compiled = fn.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no compiled Pallas kernel in the program")
    out = compiled(*args).block_until_ready()
    t0 = time.perf_counter()
    out = compiled(*args).block_until_ready()
    print(f"    {name}: compiled Pallas kernel (tpu_custom_call), second "
          f"call {1e3 * (time.perf_counter() - t0):.2f} ms")
    return out


def four_chips(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sort import distributed_sort
    from repro.launch.mesh import make_mesh

    with clock.phase("4b", f"serve {ARCH} continuous, sharded over "
                     f"model=4"):
        _, (sharded,) = serve(["--engine", "continuous", "--mesh",
                               "data=1,model=4", "--serve-shard", "shard"])
        rep = sharded.report
        check(rep.mesh_shape == {"data": 1, "model": 4},
              f"mesh {rep.mesh_shape}")
        print(f"    mesh {rep.mesh_shape}, collective ops "
              f"{rep.collective_ops}")
        check(rep.collective_ops > 0, "sharded serve ran no collectives")
        for d in jax.devices():
            st = d.memory_stats() or {}
            print(f"    device {d.id}: peak "
                  f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB, in use "
                  f"{st.get('bytes_in_use', 0) / 2**30:.2f} GiB")
        rids = [r.rid for r in rep.requests]
        sh_tok = token_matrix(sharded, rids)
        # a plain forward of the same sharded weights: its bf16 all-reduces
        # round at other points than the engine's programs, so this is
        # printed, and the check below measures the layout's rounding
        tp_pred = reference_logits(sharded, sh_tok)
        score_tokens("sharded vs sharded plain forward", sh_tok, tp_pred)
        tp_pred = np.asarray(tp_pred)
        del sharded, rep
    with clock.phase("4c", f"serve {ARCH} continuous, unsharded, same "
                     f"trace"):
        _, (plain,) = serve(["--engine", "continuous"])
        pl_tok = token_matrix(plain, rids)
        print(f"    sharded vs unsharded: {agreement(sh_tok, pl_tok)}")
        reference_check(plain, {"unsharded": pl_tok})
        # the layout's rounding, measured: both forwards fed the sharded
        # engine's tokens differ by delta per position; a wrong shard would
        # show as a deviation of the logits' own size
        pred = reference_logits(plain, sh_tok)
        delta = np.asarray(jnp.abs(pred - tp_pred).max(-1))
        dev = delta / (2.0 ** -8 * np.asarray(jnp.abs(pred).max(-1)))
        print(f"    sharded vs unsharded forward logits: max deviation "
              f"{dev.max():.2f} bf16 ulps of the largest logit, median "
              f"{np.median(dev):.2f}", flush=True)
        check(dev.max() <= LAYOUT_ULPS, f"sharded forward off the unsharded "
              f"one by {dev.max():.2f} ulps > {LAYOUT_ULPS}")
        # a sharded token's gap under the unsharded forward is its engine's
        # rounding (the one-chip tolerance) plus at most 2 delta of layout
        score_tokens("sharded vs unsharded plain forward", sh_tok, pred)
        bad = score_tokens("sharded vs unsharded plain forward, tolerance "
                           "+ 2 delta", sh_tok, pred, slack=2 * delta)
        check(not bad, f"sharded: {bad} token(s) beyond the tolerance and "
              f"the measured layout deviation")
        del plain
    with clock.phase("4d", "sample sort on a 4-device mesh against jnp.sort"):
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(SEED), (SORT_KEYS,),
                              jnp.float32)
        out, rep = distributed_sort(x, mesh, "data", pivot="sampled",
                                    force_parallel=True)
        equal = bool(jnp.array_equal(out, jnp.sort(x)))
        print(f"    {rep.strategy} over {rep.chips} devices, bucket "
              f"imbalance {rep.imbalance:.3f}, equals jnp.sort: {equal}")
        check(rep.strategy == "sample_sort", f"ran {rep.strategy}")
        check(equal, "sample sort differs from jnp.sort")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serve and sample sort")
    args = ap.parse_args(argv)

    device = device_check(args.chips)
    sys.path.insert(0, str(REPO / "src"))
    cost_cache = tempfile.mkdtemp(prefix="repro-cost-")
    os.environ["REPRO_COST_CACHE"] = cost_cache
    try:
        from repro.launch.process import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        (four_chips if args.chips == 4 else one_chip)(CompileClock())
    except PhaseFailed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(cost_cache, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
