"""Sharding rules: logical-axis PartitionSpecs for params and activations.

Mesh axes:
  ``pod``   — inter-pod (DCN) axis, present only on multi-pod meshes
  ``data``  — intra-pod data parallelism; params/opt-state FSDP-shard here
  ``model`` — tensor/expert parallelism

The rules live in ONE place (``param_shardings``) keyed by param-tree paths,
so the overhead-driven planner (core/planner.py) can rewrite them and the
checkpointing layer can store logical specs that survive mesh reshapes.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Execution context threaded through model code.

    Carries the mesh, axis names and the knobs the overhead planner tunes
    (activation specs, attention/rnn chunk sizes, MoE capacity).
    """

    mesh: Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    use_ep: bool = True
    attn_chunk: int = 1024
    rnn_chunk: int = 64
    # §Perf iteration 2: cf=1.25 (from 2.0) — slot-buffer flops/bytes scale
    # linearly with cf; 1.25 is the standard training setting with an aux
    # balance loss (drops <1% tokens at convergence).
    moe_capacity_factor: float = 1.25
    # dry-run probes only: unroll internal lax.scans (chunked attention, WKV,
    # chunked CE) so XLA cost_analysis — which does NOT multiply while-loop
    # bodies by trip count — sees every iteration in flat HLO.
    unroll_scans: bool = False
    # inference: replicate params over the data axes (no FSDP gathers); set
    # by the overhead-model fit check in launch/dryrun.py and serve paths.
    infer_replicate_params: bool = False
    # the CostEngine whose plan produced this ctx (ledger + decision cache);
    # model code (e.g. MoE dispatch) consults it at trace time.  None ->
    # call sites fall back to the default Runtime's engine.
    cost_engine: Optional[Any] = None
    # sequence parallelism: shard the residual stream's seq dim over the
    # model axis between layers (beyond-paper memory optimization — the
    # saved scan carries shrink by the TP degree; attention re-gathers)
    seq_shard: bool = True

    @property
    def dp(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp(self) -> int:
        return self.mesh.shape.get(self.model_axis, 1)

    @property
    def dp_spec(self):
        if not self.data_axes:  # pure-TP ctx (serve meshes): no data axis
            return None
        return tuple(self.data_axes) if len(self.data_axes) > 1 else self.data_axes[0]

    def constrain(self, x, spec: P):
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _batch_axis(self, b: int):
        return self.dp_spec if b % self.dp == 0 else None

    def constrain_act(self, x):
        """Hidden states (B, S, D)."""
        b, s, _ = x.shape
        bspec = self._batch_axis(b)
        if self.seq_shard and s % self.tp == 0 and s >= 2 * self.tp:
            return self.constrain(x, P(bspec, self.model_axis, None))
        return self.constrain(x, P(bspec, None, None))

    def constrain_heads(self, x):
        """(B, S, H, hd): shard heads over model axis."""
        b, _, h, _ = x.shape
        hspec = self.model_axis if h % self.tp == 0 else None
        return self.constrain(x, P(self._batch_axis(b), None, hspec, None))

    def constrain_kv_heads(self, x):
        """KV heads may not divide the model axis (MQA kv=1): replicate then."""
        return self.constrain_heads(x)

    def tokens_spec(self):
        return P(self.dp_spec, None)


# ---------------------------------------------------------------------------
# Param sharding rules (path-pattern -> spec builder)
# ---------------------------------------------------------------------------


def _spec_for(path: str, arr, *, fsdp, model: str, mesh_shape: Dict[str, int],
              scanned: bool) -> P:
    """Return the PartitionSpec for one parameter.

    ``fsdp`` is the (possibly compound) data-axis group; ``model`` the TP axis.
    ``scanned`` params carry a leading layer axis (never sharded).
    """

    def wrap(*dims):
        return P(*((None,) + dims)) if scanned else P(*dims)

    ndim = arr.ndim - (1 if scanned else 0)

    def fits(dim_idx: int, axis) -> bool:
        if axis is None:  # replicated group: always placeable (as None)
            return True
        size = 1
        for a in (axis if isinstance(axis, tuple) else (axis,)):
            size *= mesh_shape.get(a, 1)
        shape = arr.shape[1:] if scanned else arr.shape
        return shape[dim_idx] % size == 0

    # --- embeddings: vocab on model axis, d on fsdp
    if re.search(r"(embed|unembed)", path):
        if fits(0, model) and fits(1, fsdp):
            return wrap(model, fsdp)
        return wrap(None, None)
    # --- attention projections
    if re.search(r"attn/w[qkv]$", path) or re.search(r"cross/w[qkv]$", path):
        # (D, H, hd): heads on model, D on fsdp
        if fits(1, model) and fits(0, fsdp):
            return wrap(fsdp, model, None)
        if fits(0, fsdp):
            return wrap(fsdp, None, None)
        return wrap(*([None] * ndim))
    # --- latent attention (MLA): the down-projection to the latent and the
    # shared rope key (D, r + rope) is one matrix for every head; the
    # up-projection (r, H, nope + dv) splits by head like w[qkv]
    if re.search(r"attn/wkv_a$", path):
        if fits(0, fsdp):
            return wrap(fsdp, None)
        return wrap(None, None)
    if re.search(r"attn/wkv_b$", path):
        if fits(1, model):
            return wrap(None, model, None)
        return wrap(None, None, None)
    if re.search(r"(attn|cross)/wo$", path):
        # (H*hd, D)
        if fits(0, model) and fits(1, fsdp):
            return wrap(model, fsdp)
        return wrap(None, None)
    # --- MoE experts: (E, D, F) / (E, F, D): experts on model, D on fsdp
    if re.search(r"ffn/(w_in|w_gate|w_out)$", path) and arr.ndim - (1 if scanned else 0) == 3:
        if fits(0, model):
            return wrap(model, fsdp if fits(1, fsdp) else None, None)
        return wrap(None, None, None)
    if re.search(r"ffn/router$", path):
        return wrap(None, None)
    # --- dense FFN: (D, F) in / (F, D) out; a MoE layer's shared MLP too
    if re.search(r"ffn/(shared/)?(w_in|w_gate)$", path):
        if fits(1, model) and fits(0, fsdp):
            return wrap(fsdp, model)
        return wrap(None, None)
    if re.search(r"ffn/(shared/)?w_out$", path):
        if fits(0, model) and fits(1, fsdp):
            return wrap(model, fsdp)
        return wrap(None, None)
    # --- RWKV square projections (D, D): shard output dim on model
    if re.search(r"(time|channel)/w_[rkvgo]$", path) and ndim == 2:
        if fits(1, model) and fits(0, fsdp):
            return wrap(fsdp, model) if path.endswith(("w_k",)) else wrap(fsdp, None)
        return wrap(None, None)
    if re.search(r"channel/w_v$", path) and ndim == 2:
        if fits(0, model):
            return wrap(model, None)
        return wrap(None, None)
    # --- RG-LRU projections
    if re.search(r"rglru/(w_x|w_gate)$", path):
        if fits(0, fsdp):
            return wrap(fsdp, None)
        return wrap(None, None)
    if re.search(r"rglru/w_out$", path):
        if fits(1, fsdp):
            return wrap(None, fsdp)
        return wrap(None, None)
    # --- vectors / norms / small: replicate
    return wrap(*([None] * ndim))


def _fit_override(spec: P, arr, mesh_shape: Dict[str, int], scanned: bool) -> P:
    """Adapt a planner override spec to one parameter.

    Override specs describe the LOGICAL (unscanned) shape; stacked-scan
    params get a leading None for the layer axis.  Dims whose size does not
    divide the assigned axis group fall back to replicated (None) — the same
    feasibility-before-speedup rule ``_spec_for`` applies.
    """
    dims = tuple(spec)
    if scanned:
        dims = (None,) + dims
    dims = dims[: arr.ndim] + (None,) * (arr.ndim - len(dims))
    fitted = []
    for i, ax in enumerate(dims):
        if ax is None:
            fitted.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh_shape.get(a, 1)
        fitted.append(ax if arr.shape[i] % size == 0 else None)
    return P(*fitted)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(
    params_shape: Any,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    model_axis: str = "model",
    scanned_prefix: str = "layers",
    overrides: Optional[Dict[str, P]] = None,
) -> Any:
    """Build a pytree of NamedShardings matching ``params_shape``.

    ``overrides``: path-regex -> spec, applied first (planner hook).  Specs
    address the logical (unscanned) shape; see ``_fit_override``.
    ``data_axes=()`` replicates params over the data axes (inference mode:
    no FSDP -> no per-step weight all-gathers; overhead-model decision).
    """
    if data_axes:
        fsdp = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    else:
        fsdp = None
    mesh_shape = dict(mesh.shape)

    def rule(path, arr):
        ps = _path_str(path)
        # stacked-scan params carry a leading layer axis: any subtree under a
        # "layers" segment that is NOT per-layer ("layer_<i>") keyed, and
        # period-scan groups ("groups/<pos>/...").  Works for "layers/...",
        # "params/layers/...", "opt/mu/layers/..." alike.
        scanned = ("layer_" not in ps and "rest_" not in ps) and (
            re.search(r"(^|/)(layers|groups/\d+)/", ps) is not None
        )
        if overrides:
            for pat, spec in overrides.items():
                if re.search(pat, ps):
                    return NamedSharding(
                        mesh, _fit_override(spec, arr, mesh_shape, scanned))
        spec = _spec_for(ps, arr, fsdp=fsdp, model=model_axis,
                         mesh_shape=mesh_shape, scanned=scanned)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def _dp_size(mesh, data_axes) -> int:
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]
    return n


def batch_sharding(batch_shape: Any, mesh: Mesh, data_axes=("data",)) -> Any:
    """Inputs: shard leading (batch) dim over the data axes (when divisible)."""
    dp_spec = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    dp = _dp_size(mesh, data_axes)

    def rule(arr):
        lead = dp_spec if arr.shape and arr.shape[0] % dp == 0 else None
        spec = P(*((lead,) + (None,) * (arr.ndim - 1)))
        return NamedSharding(mesh, spec)

    return jax.tree.map(rule, batch_shape)


def serve_state_sharding(state_shape: Any, mesh: Mesh, *,
                         model_axis: str = "model") -> Any:
    """Pooled decode-state placement for tensor-parallel serving.

    The slot (batch) axis is never sharded — slots turn over under
    host-driven masks, and the serve mesh's data axis is degenerate.  KV
    caches ((L?, B, S, H, hd) leaves keyed 'k'/'v') shard their kv-head dim
    over the model axis when divisible (column-parallel attention writes
    shard-local heads, so cache updates stay communication-free); heads
    that don't divide fall back to the cache-length dim, then to
    replication — the same feasibility-before-speedup rule as
    ``param_shardings``.  Positions and recurrent/conv states replicate:
    they are per-slot vectors or square per-head states the model axis has
    no clean dim for; so does a latent pool (``pl``, MLA), whose one entry
    per token every head reads.
    """
    tp = mesh.shape.get(model_axis, 1)

    def rule(path, arr):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        # leading stacked-layer axis: scan states and period-scan groups
        lead = 1 if ("groups" in keys or not any(
            k.startswith(("rest_", "layer_", "dec_", "cross_")) or k == "pos"
            for k in keys)) else 0
        lead = min(lead, max(arr.ndim - 1, 0))
        dims = [None] * arr.ndim
        if tp > 1 and keys and keys[-1] in ("k", "v") and arr.ndim == lead + 4:
            s, h = arr.shape[lead + 1], arr.shape[lead + 2]
            if h % tp == 0:
                dims[lead + 2] = model_axis
            elif s % tp == 0 and s >= 2 * tp:
                dims[lead + 1] = model_axis
        if tp > 1 and keys and keys[-1] in ("pk", "pv") and arr.ndim == lead + 4:
            # paged page pools (L?, n_blocks, block_size, H, hd): shard the
            # kv-head dim like dense caches; never the block axis — block
            # ids index it from dynamically-gathered tables, and a shard
            # split there would turn every gather into a collective
            h = arr.shape[lead + 2]
            if h % tp == 0:
                dims[lead + 2] = model_axis
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(rule, state_shape)


def validate_serve_mesh(cfg, mesh_shape: Dict[str, int],
                        model_axis: str = "model") -> None:
    """Fail fast — with a fix, not a GSPMD traceback — when a requested
    serve mesh cannot tensor-shard ``cfg``: the model axis must divide the
    dims the serve param rules split (FFN width, the attention projection
    output H*hd, d_model for the residual constraint, and the vocab for the
    sharded unembed)."""
    tp = int(mesh_shape.get(model_axis, 1))
    if tp <= 1:
        return
    hd = cfg.resolved_head_dim
    problems = []
    if cfg.d_ff % tp:
        problems.append(f"d_ff={cfg.d_ff}")
    if (cfg.n_heads * hd) % tp:
        problems.append(f"n_heads*head_dim={cfg.n_heads * hd}")
    if cfg.d_model % tp:
        problems.append(f"d_model={cfg.d_model}")
    if cfg.vocab_size % tp:
        problems.append(f"vocab_size={cfg.vocab_size}")
    if problems:
        raise ValueError(
            f"mesh model axis {model_axis}={tp} does not divide "
            f"{', '.join(problems)} for arch {cfg.name!r}; pick a model-axis "
            f"size that divides the head/FFN dims (or 1 to replicate)")


def state_sharding(state_shape: Any, mesh: Mesh, data_axes=("data",),
                   model_axis: str = "model", scanned: bool = True):
    """Decode caches/states: (L?, B, ...) — batch dim over data; KV-cache
    sequence dims (path key 'k'/'v', 4D + optional layer axis) additionally
    over the model axis so 32k-a-side caches fit HBM."""
    dp_spec = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    dp = _dp_size(mesh, data_axes)
    tp = mesh.shape.get(model_axis, 1)

    def rule(path, arr):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        lead = 1 if scanned else 0
        if "groups" in keys:  # period-scan states: stacked
            lead = 1
        elif any(k.startswith(("rest_", "layer_", "dec_", "cross_")) for k in keys):
            lead = 0
        dims = [None] * arr.ndim
        if arr.ndim > lead and arr.shape[lead] % dp == 0:
            dims[lead] = dp_spec
        # kv caches: (L?, B, S, H, hd) — shard S over model
        if keys and keys[-1] in ("k", "v") and arr.ndim == lead + 4:
            s = arr.shape[lead + 1]
            if s % tp == 0 and s >= 2 * tp:
                dims[lead + 1] = model_axis
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(rule, state_shape)
