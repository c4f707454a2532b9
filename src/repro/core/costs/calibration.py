"""Calibration layer: microbenchmark the RUNNING backend into a HardwareSpec.

The analytic model (costs/model.py) is only as good as its constants.  The
paper's crossover points are hardware-parameter-sensitive (Yavits et al.;
Haque et al.), so datasheet numbers for the TARGET hardware (TPU v5e) are
the wrong oracle when the program actually executes somewhere else — the CI
CPU backend, an interpret-mode Pallas run, a different TPU generation.

``calibrate()`` measures, on whatever backend jax is using right now:

  * kernel launch latency      — dispatch of a trivial jitted program
  * host-sync latency          — device->host fetch of a tiny ready buffer
  * effective memory bandwidth — large-array copy traffic / wall time
  * matmul throughput          — FLOP/s at a well-tiled order, per dtype
  * IPC round trip + bandwidth — ping-pong through a spawned echo child
                                 (the serve_ipc front-end site's constants)
  * collective base latency    — tiny psum under a mesh (multi-device only)
  * interconnect bandwidth     — large psum, ring-model inverted to the
                                 per-link figure (multi-device only)

and returns a ``HardwareSpec`` with those fields replaced.  Results persist
to a JSON cache keyed by a backend fingerprint (platform, device kind and
count, jax version) so repeated runs — and every decision site behind the
CostEngine — share one calibration instead of re-benchmarking.

Everything here is best-effort: any individual probe failure falls back to
the base spec's value for that field.  Calibration never runs implicitly;
it only runs via ``CostEngine.calibrated()`` — which ``repro.Runtime``
invokes when ``RuntimeConfig.calibrate`` is set (legacy
``REPRO_CALIBRATE=1`` maps onto it via ``RuntimeConfig.from_env``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from pathlib import Path
from typing import Optional

from repro.hw import V5E, HardwareSpec

_SCHEMA_VERSION = 1


def backend_fingerprint() -> str:
    """Stable id of the running backend: what the calibration cache keys on."""
    import jax

    dev = jax.devices()[0]
    parts = (
        jax.default_backend(),
        getattr(dev, "device_kind", "unknown"),
        str(jax.device_count()),
        jax.__version__,
    )
    raw = "|".join(parts)
    return f"{parts[0]}-{hashlib.sha256(raw.encode()).hexdigest()[:12]}"


def default_cache_dir() -> Path:
    """Fallback cache home when no cache_dir is injected.  Environment
    relocation ($REPRO_COST_CACHE) is RuntimeConfig.from_env()'s job — this
    function deliberately reads nothing from the environment."""
    return Path.home() / ".cache" / "repro" / "calibration"


# ---------------------------------------------------------------------------
# Microbenchmarks
# ---------------------------------------------------------------------------


def _timeit(fn, reps: int) -> float:
    fn()  # warm up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _measure_launch_latency(reps: int = 50) -> float:
    """Wall time of dispatching a trivial jitted program — the measured
    analogue of the paper's thread-creation overhead."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda x: x + 1.0)
    return _timeit(lambda: f(x).block_until_ready(), reps)


def _measure_memory_bw(nbytes: int = 1 << 26, reps: int = 5) -> float:
    """Effective bytes/s of a read+write sweep over ``nbytes``."""
    import jax
    import jax.numpy as jnp

    n = nbytes // 4
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    dt = _timeit(lambda: f(x).block_until_ready(), reps)
    return 2.0 * nbytes / max(dt, 1e-9)  # read + write


def _measure_matmul_flops(order: int = 1024, reps: int = 3,
                          dtype: str = "float32") -> float:
    """Achieved FLOP/s of an order^3 matmul in ``dtype``."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((order, order), dtype=dtype)
    f = jax.jit(lambda a: a @ a)
    dt = _timeit(lambda: f(a).block_until_ready(), reps)
    return 2.0 * order**3 / max(dt, 1e-9)


def _measure_host_sync(reps: int = 50) -> float:
    """Wall time of one device->host round trip on a tiny READY buffer —
    the per-token tax the serve macro-step amortizes over K tokens.  The
    buffer is materialized and synchronized up front so the probe times the
    transfer + host bookkeeping, not the compute it waits on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    y = jax.jit(lambda x: x + 1.0)(jnp.zeros((8,), jnp.float32))
    y.block_until_ready()
    return _timeit(lambda: np.asarray(y), reps)


def _measure_prefix_lookup(reps: int = 20000, block_size: int = 16) -> float:
    """Host wall time of ONE radix-trie hop — building a block's token
    tuple and probing a children dict with it, the per-block unit the
    serve_prefix site charges for the admission lookup/pin walk.  Pure
    host Python: no device involved."""
    tokens = list(range(block_size * 64))
    children = {tuple(tokens[i * block_size:(i + 1) * block_size]): i
                for i in range(64)}
    t0 = time.perf_counter()
    for r in range(reps):
        i = (r % 64) * block_size
        children.get(tuple(tokens[i:i + block_size]))
    return (time.perf_counter() - t0) / reps


def _ipc_echo_child(conn) -> None:
    """Echo server for the IPC probes (module-level: spawn-importable)."""
    while True:
        msg = conn.recv()
        if msg is None:
            return
        conn.send(msg)


_IPC_PROBE_CACHE: Optional[tuple] = None


def _measure_ipc(small_reps: int = 200, large_reps: int = 5,
                 large_bytes: int = 1 << 20) -> tuple:
    """(round_trip_s, bytes_per_s) of parent<->child pipe messaging — the
    two constants behind the serve_ipc cost site.  One spawned echo child
    serves both probes: small-message ping-pong gives the per-message
    round trip; the LARGE-payload round trip minus that base, divided into
    the bytes moved (both directions), gives serialization + transport
    bandwidth.  Spawn (not fork): the caller may hold live XLA threads.
    Cached module-wide so the two ``attempt`` entries share one child."""
    global _IPC_PROBE_CACHE
    if _IPC_PROBE_CACHE is not None:
        return _IPC_PROBE_CACHE
    import multiprocessing as mp

    from repro.launch.process import cpu_only_children

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    proc = ctx.Process(target=_ipc_echo_child, args=(child,), daemon=True)
    with cpu_only_children():  # the echo child never holds the chip
        proc.start()
    try:
        def round_trip(payload):
            parent.send(payload)
            return parent.recv()

        round_trip(b"x")  # warm-up / readiness barrier
        rt = _timeit(lambda: round_trip(b"x"), small_reps)
        blob = b"\0" * large_bytes
        dt = _timeit(lambda: round_trip(blob), large_reps)
        bw = 2.0 * large_bytes / max(dt - rt, 1e-9)
        _IPC_PROBE_CACHE = (rt, bw)
        return _IPC_PROBE_CACHE
    finally:
        try:
            parent.send(None)
        except OSError:
            pass
        proc.join(timeout=5.0)
        if proc.is_alive():
            proc.terminate()
        parent.close()
        child.close()


def _measure_collective_base(reps: int = 20) -> Optional[float]:
    """Base latency of a tiny all-reduce; None on single-device backends."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    n = jax.device_count()
    if n < 2:
        return None
    mesh = make_mesh((n,), ("cal",))
    f = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "cal"), mesh=mesh,
        in_specs=P("cal"), out_specs=P(),
    ))
    x = jnp.ones((n,), jnp.float32)
    return _timeit(lambda: f(x).block_until_ready(), reps)


def _measure_interconnect_bw(nbytes: int = 1 << 22, reps: int = 5,
                             links: int = V5E.ici_links) -> Optional[float]:
    """Effective per-link interconnect bandwidth (bytes/s) from a LARGE
    all-reduce over every visible device — the bandwidth half of the
    serve_shard communication term (``_measure_collective_base`` is the
    latency half).  Inverts the ring-all-reduce model ``collective_time``
    charges (2·(c-1)/c · bytes over ici_links/2 effective links) so the
    analytic model reproduces the measured transfer on this backend.
    None on single-device backends."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    c = jax.device_count()
    if c < 2:
        return None
    mesh = make_mesh((c,), ("cal",))
    f = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "cal"), mesh=mesh,
        in_specs=P("cal"), out_specs=P(),
    ))
    n = max(nbytes // 4 // c * c, c)
    x = jnp.ones((n,), jnp.float32)
    dt = _timeit(lambda: f(x).block_until_ready(), reps)
    base = _measure_collective_base() or 0.0
    wire_bytes = 2.0 * (c - 1) / c * (n * 4)
    eff_bw = wire_bytes / max(dt - base, 1e-9)
    # collective_time uses bw = ici_bw_per_link * ici_links / 2 * ici_eff;
    # report the per-link figure for the base spec's link count (ici_eff is
    # an OverheadModel derate, deliberately left in place)
    return eff_bw * 2.0 / max(links, 1)


# ---------------------------------------------------------------------------
# calibrate + persistence
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    spec: HardwareSpec
    fingerprint: str
    from_cache: bool
    measurements: dict  # raw probe values (doc/debug)
    # persisted per-site correction state (corrections.py) riding in the
    # same fingerprint-keyed cache entry, and the path it lives at — the
    # engine writes healed specs/corrections back through this
    corrections: dict = dataclasses.field(default_factory=dict)
    path: Optional[Path] = None


# Per-field probe dispatch: which microbenchmark calibrates each
# HardwareSpec field.  Keeping this a table (not a hard-coded sequence)
# is what makes TARGETED recalibration possible: drift at one CostQuery
# site re-runs only the probes for the fields that site depends on
# (hw.SITE_FIELDS), instead of re-benchmarking the whole spec.  Every
# probe takes (base_spec, matmul_order) even when it needs neither, so
# the runner stays uniform.
PROBES = {
    "kernel_launch_s": lambda base, order: _measure_launch_latency(),
    "host_sync_s": lambda base, order: _measure_host_sync(),
    "prefix_lookup_s": lambda base, order: _measure_prefix_lookup(),
    "ipc_round_trip_s": lambda base, order: _measure_ipc()[0],
    "ipc_bytes_per_s": lambda base, order: _measure_ipc()[1],
    "hbm_bw": lambda base, order: _measure_memory_bw(),
    "peak_flops_f32":
        lambda base, order: _measure_matmul_flops(order, dtype="float32"),
    "peak_flops_bf16":
        lambda base, order: _measure_matmul_flops(order, dtype="bfloat16"),
    "collective_base_s": lambda base, order: _measure_collective_base(),
    "ici_bw_per_link":
        lambda base, order: _measure_interconnect_bw(links=base.ici_links),
}


def run_probe_fields(fields, base: HardwareSpec = V5E, *,
                     matmul_order: int = 1024) -> dict:
    """Run the probes for ``fields`` only, best-effort: a field with no
    probe is skipped; a probe that fails (or declines, e.g. collective
    probes on a single-device backend) reports None so the caller keeps
    the current value for that field."""
    probes = {}
    for name in fields:
        fn = PROBES.get(name)
        if fn is None:
            continue
        try:
            probes[name] = fn(base, matmul_order)
        except Exception:  # any backend quirk: keep the base value
            probes[name] = None
    return probes


def _run_probes(base: HardwareSpec, *, matmul_order: int) -> dict:
    return run_probe_fields(PROBES.keys(), base, matmul_order=matmul_order)


def calibrate(base: HardwareSpec = V5E, *, cache_dir: Optional[Path] = None,
              force: bool = False, matmul_order: int = 1024) -> CalibrationResult:
    """Return a HardwareSpec calibrated to the running backend.

    Reads the JSON cache first (keyed by ``backend_fingerprint()``); runs the
    microbenchmarks only on a miss or ``force=True``.
    """
    fp = backend_fingerprint()
    cache_dir = Path(cache_dir) if cache_dir is not None else default_cache_dir()
    cache_path = cache_dir / f"{fp}.json"

    if not force:
        cached = load_calibration(cache_path, fingerprint=fp)
        if cached is not None:
            return CalibrationResult(cached["spec"], fp, True,
                                     cached.get("measurements", {}),
                                     corrections=cached.get("corrections", {}),
                                     path=cache_path)

    probes = _run_probes(base, matmul_order=matmul_order)
    updates = {k: v for k, v in probes.items() if v is not None}
    spec = dataclasses.replace(
        base, name=f"calibrated-{fp}", **updates)
    save_calibration(cache_path, spec, fingerprint=fp, measurements=probes)
    # a forced re-calibration drops any persisted corrections on purpose:
    # they corrected the OLD spec, and a fresh spec must not inherit them
    return CalibrationResult(spec, fp, False, probes, path=cache_path)


def save_calibration(path: Path, spec: HardwareSpec, *, fingerprint: str,
                     measurements: Optional[dict] = None,
                     corrections: Optional[dict] = None) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "schema": _SCHEMA_VERSION,
        "fingerprint": fingerprint,
        "spec": spec.to_dict(),
        "measurements": measurements or {},
        # per-site correction state (corrections.py) — additive key, so
        # pre-corrections caches stay schema-valid and load with {}
        "corrections": corrections or {},
    }
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(payload, indent=1))
    tmp.replace(path)


def load_calibration(path: Path, *, fingerprint: Optional[str] = None
                     ) -> Optional[dict]:
    """Load {spec, measurements} from ``path``; None on miss/mismatch."""
    try:
        payload = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return None
    if payload.get("schema") != _SCHEMA_VERSION:
        return None
    if fingerprint is not None and payload.get("fingerprint") != fingerprint:
        return None
    # a cache written before a HardwareSpec field existed would silently
    # pin that field to its datasheet default forever — re-calibrate instead
    missing = {f.name for f in dataclasses.fields(HardwareSpec)} - set(
        payload.get("spec", {}))
    if missing:
        return None
    return {"spec": HardwareSpec.from_dict(payload["spec"]),
            "measurements": payload.get("measurements", {}),
            "corrections": payload.get("corrections", {})}
