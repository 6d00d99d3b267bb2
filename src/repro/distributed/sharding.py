"""Logical-axis → mesh-axis sharding rules (MaxText-style).

Every parameter is declared with logical axes (see ``models.layers``); this
module maps them to ``PartitionSpec``s for a concrete mesh and strategy:

  * TP ("model" axis): vocab, mlp, heads, experts, rnn widths
  * DP/FSDP ("data" [+ "pod"] axes): batch dim of activations; optionally the
    "embed" dim of ≥2-D weights (fully-sharded weights — required for the
    biggest archs to fit 16 GB/chip even at inference, see DESIGN.md §5)
  * SP/CP: KV-cache sequence dim shards over "model" when the arch's KV-head
    count cannot (GQA with few KV heads)

Every rule is *shape-checked*: an axis whose dim is not divisible by the
mesh axes it maps to silently degrades to replication (e.g. batch=1 in
``long_500k``). That makes one rule-set serve all 40 (arch × shape) cells.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Tree = Any
AxisName = Union[str, Tuple[str, ...], None]


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """Strategy knobs (the §Perf hillclimb levers)."""

    dp_axes: Tuple[str, ...] = ("data",)       # ("pod","data") when multi-pod
    tp_axis: str = "model"
    fsdp_weights: bool = True                  # shard "embed" of ≥2D weights over dp
    shard_cache_seq: bool = True               # CP the KV seq when kv_heads can't TP
    logical_rules: Tuple[Tuple[str, AxisName], ...] = ()  # extra overrides

    def rules(self) -> Dict[str, AxisName]:
        base: Dict[str, AxisName] = {
            "layers": None,
            "batch": self.dp_axes,
            "seq": None,
            "cache_seq": None,           # upgraded per-arch (see build_cache_specs)
            "vocab": self.tp_axis,
            "embed": None,               # upgraded to dp for ≥2D weights if fsdp
            "mlp": self.tp_axis,
            "heads": self.tp_axis,
            "kv_heads": self.tp_axis,
            "head_dim": None,
            "experts": self.tp_axis,
            "rnn": self.tp_axis,
        }
        base.update(dict(self.logical_rules))
        return base


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _mesh_size(mesh: Mesh, name: AxisName) -> int:
    if name is None:
        return 1
    sizes = _axis_sizes(mesh)
    if isinstance(name, str):
        return sizes[name]
    return int(np.prod([sizes[n] for n in name]))


def _spec_for(
    shape: Tuple[int, ...],
    axes: Tuple[Optional[str], ...],
    rules: Dict[str, AxisName],
    mesh: Mesh,
    fsdp_weights: bool,
    dp_axes: Tuple[str, ...],
) -> P:
    """Shape-checked spec: drop any mapping whose dim is not divisible or
    whose mesh axis is already used by an earlier dim."""
    used: set = set()
    entries = []
    axes = tuple(axes)
    is_weight = len([a for a in axes if a not in (None, "layers")]) >= 2
    # Vocab-dim weights (embedding/unembedding tables) stay out of FSDP:
    # a table sharded on BOTH dims defeats GSPMD's gather partitioning
    # (involuntary full rematerialization) — vocab-sharding alone suffices.
    fsdp_ok = is_weight and "vocab" not in axes
    for dim, ax in zip(shape, axes):
        target: AxisName = rules.get(ax) if ax is not None else None
        if ax == "embed" and fsdp_weights and fsdp_ok and target is None:
            target = dp_axes
        if target is None:
            entries.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        names = tuple(n for n in names if n not in used)
        if not names:
            entries.append(None)
            continue
        size = _mesh_size(mesh, names)
        if size <= 1 or dim % size != 0:
            entries.append(None)
            continue
        used.update(names)
        entries.append(names[0] if len(names) == 1 else names)
    # strip trailing Nones for tidiness
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def ambient_dp_axes() -> Optional[Tuple[str, ...]]:
    """Data-parallel axes of the mesh currently in context, or None.

    Model code uses this to constrain *internally created* state (zero-init
    recurrent states, caches built inside ``forward``) to batch sharding —
    GSPMD cannot infer useful shardings for such intermediates, and leaving
    them replicated multiplies their footprint by the mesh size. Outside a
    mesh context (CPU smoke tests) this returns None and no constraint is
    applied.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return None
    return tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None when there is none or when
    tracing inside a manual (``shard_map``) region, where layout
    constraints on the global view do not apply."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return None
    return mesh


def constrain_batch_dim(x, batch_dim: int = 1):
    """with_sharding_constraint(batch dim → dp axes) if a mesh is ambient."""
    import jax.numpy as jnp  # noqa: PLC0415
    from jax.sharding import PartitionSpec  # noqa: PLC0415

    dp = ambient_dp_axes()
    if dp is None:
        return x
    if x.ndim <= batch_dim or x.shape[batch_dim] % _grid(dp) != 0:
        return x
    spec = [None] * x.ndim
    spec[batch_dim] = dp if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def _grid(dp: Tuple[str, ...]) -> int:
    sizes = _ambient_mesh().shape
    out = 1
    for a in dp:
        out *= sizes[a]
    return out


# --------------------------------------------------------------------------- #
# Sequence parallelism (runtime toggle)                                       #
# --------------------------------------------------------------------------- #
# When ON, the residual stream between transformer blocks is constrained to
# P(dp, tp, None) — the Korthikanti-style layout: norms/residuals run
# seq-sharded, GSPMD inserts all-gather before qkv/mlp and reduce-scatter
# after, replacing the plain TP all-reduces (half the bytes) and dividing
# layer-boundary activation storage by the TP width. Toggled per dry-run
# variant (see launch.plan / EXPERIMENTS.md §Perf).
_SEQUENCE_PARALLEL = {"on": False}


def set_sequence_parallel(on: bool) -> None:
    _SEQUENCE_PARALLEL["on"] = bool(on)


def sequence_parallel_enabled() -> bool:
    return _SEQUENCE_PARALLEL["on"]


def constrain_kv_for_cache(k, n_kv_heads: int, seq_dim: int = 1):
    """Align freshly-computed prefill K/V (B, S, KV, D) with the cache's
    layout *before* the cache write.

    When KV heads don't divide the TP axis the cache shards its sequence dim
    over "model" (context parallelism); the K/V produced inside the block
    inherit a heads/replicated layout, and the per-layer cache writes then
    reshard 2·L times per prefill — tens of seconds of all-gather at 32k
    (§Perf H2). Constraining here makes the write layout-aligned.
    """
    from jax.sharding import PartitionSpec  # noqa: PLC0415

    mesh = _ambient_mesh()
    if mesh is None:
        return k
    sizes = mesh.shape
    tp = "model" if "model" in sizes else None
    if tp is None or n_kv_heads % sizes[tp] == 0:
        return k  # heads shard fine; no CP needed
    if k.shape[seq_dim] % sizes[tp] != 0:
        return k
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    spec = [None] * k.ndim
    if dp and k.shape[0] % _grid(dp) == 0:
        spec[0] = dp if len(dp) > 1 else dp[0]
    spec[seq_dim] = tp
    return jax.lax.with_sharding_constraint(k, PartitionSpec(*spec))


def constrain_logits(x):
    """Constrain logits (..., V) to batch-dp × vocab-tp when a mesh is
    ambient (same GSPMD-propagation insurance as ``embed_tokens``)."""
    from jax.sharding import PartitionSpec  # noqa: PLC0415

    mesh = _ambient_mesh()
    if mesh is None:
        return x
    sizes = mesh.shape
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = "model" if "model" in sizes else None
    spec = [None] * x.ndim
    if dp and x.shape[0] % _grid(dp) == 0:
        spec[0] = dp if len(dp) > 1 else dp[0]
    if tp and x.shape[-1] % sizes[tp] == 0:
        spec[-1] = tp
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def constrain_residual(h):
    """Apply the residual-stream constraint to (B, S, D) activations."""
    if not _SEQUENCE_PARALLEL["on"] or h.ndim != 3:
        return h
    from jax.sharding import PartitionSpec  # noqa: PLC0415

    mesh = _ambient_mesh()
    if mesh is None:
        return h
    sizes = mesh.shape
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = "model" if "model" in sizes else None
    b_ok = dp and h.shape[0] % _grid(dp) == 0
    s_ok = tp and h.shape[1] % sizes[tp] == 0
    spec = PartitionSpec(
        (dp if len(dp) > 1 else dp[0]) if b_ok else None,
        tp if s_ok else None,
        None,
    )
    return jax.lax.with_sharding_constraint(h, spec)


def build_param_specs(
    abstract_params: Tree,
    logical_axes: Tree,
    mesh: Mesh,
    scfg: ShardingConfig,
) -> Tree:
    rules = scfg.rules()

    def one(aval, axes):
        return NamedSharding(
            mesh,
            _spec_for(aval.shape, axes, rules, mesh, scfg.fsdp_weights, scfg.dp_axes),
        )

    # abstract_params' leaves (ShapeDtypeStruct) align with logical_axes'
    # tuple leaves via flatten_up_to, so no custom is_leaf is needed.
    return jax.tree_util.tree_map(one, abstract_params, logical_axes)


# --------------------------------------------------------------------------- #
# Cache sharding (serve steps)                                                #
# --------------------------------------------------------------------------- #
def _cache_axes_for_key(path: Tuple[str, ...], shape: Tuple[int, ...], kv_shardable: bool):
    """Logical axes for cache arrays, keyed by their dict path/rank."""
    key = path[-1]
    if key in ("k", "v", "cross_k", "cross_v"):
        # (L, B, S, KV, HD): TP the KV heads when possible, else CP the seq.
        return (
            "layers", "batch",
            "cache_seq" if kv_shardable else "cache_seq_tp",
            "kv_heads", "head_dim",
        )
    if key == "pos":
        return ("batch", None)
    if key == "length":
        return ("batch",)
    if key == "rnn_h":
        return ("layers", "batch", "rnn")
    if key == "conv_buf":
        return ("layers", "batch", None, "rnn")
    if key in ("m_C",):
        return ("layers", "batch", "heads", "head_dim", None)
    if key in ("m_n", "s_c", "s_n", "s_h"):
        return ("layers", "batch", "heads", "head_dim")
    if key in ("m_m", "s_m"):
        return ("layers", "batch", "heads")
    # fallback: batch-shard dim 1 if rank >= 2
    return tuple(
        "batch" if i == 1 else ("layers" if i == 0 else None) for i in range(len(shape))
    )


def build_cache_specs(
    cache_shape_tree: Tree,
    mesh: Mesh,
    scfg: ShardingConfig,
    n_kv_heads: int,
) -> Tree:
    """Shardings for a serve cache. If the KV-head count divides the TP axis
    the KV heads shard (TP); otherwise the cache *sequence* dim shards over
    the TP axis (context parallelism) when ``shard_cache_seq``."""
    tp = _mesh_size(mesh, scfg.tp_axis)
    kv_shardable = n_kv_heads % tp == 0 if tp > 1 else False
    rules = scfg.rules()
    rules = dict(rules)
    rules["cache_seq"] = None
    rules["cache_seq_tp"] = scfg.tp_axis if scfg.shard_cache_seq else None
    if not kv_shardable:
        rules["kv_heads"] = None

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape_tree)
    out = []
    for path, aval in flat:
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        axes = _cache_axes_for_key(keys, aval.shape, kv_shardable)
        out.append(
            NamedSharding(
                mesh,
                _spec_for(aval.shape, axes, rules, mesh, False, scfg.dp_axes),
            )
        )
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# Model-input ShapeDtypeStructs + shardings per shape cell                    #
# --------------------------------------------------------------------------- #
def input_specs_for(
    cfg,
    cell,
    mesh: Mesh,
    scfg: ShardingConfig,
) -> Tuple[Dict[str, jax.ShapeDtypeStruct], Dict[str, NamedSharding]]:
    """ShapeDtypeStruct stand-ins + shardings for every model input of a
    shape cell (tokens/labels for train; tokens for serve; stub modality
    embeddings for vlm/audio). No device allocation happens here."""
    import jax.numpy as jnp

    b, s = cell.global_batch, cell.seq_len
    f = jax.ShapeDtypeStruct
    rules = scfg.rules()

    def sh(shape, axes):
        return NamedSharding(
            mesh, _spec_for(shape, axes, rules, mesh, False, scfg.dp_axes)
        )

    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    shards: Dict[str, NamedSharding] = {}
    if cell.kind == "train":
        specs["tokens"] = f((b, s), jnp.int32)
        specs["labels"] = f((b, s), jnp.int32)
        shards["tokens"] = sh((b, s), ("batch", "seq"))
        shards["labels"] = sh((b, s), ("batch", "seq"))
        if cfg.family == "vlm":
            p = cfg.num_patch_tokens
            specs["patch_embeds"] = f((b, p, cfg.d_model), jnp.bfloat16)
            shards["patch_embeds"] = sh((b, p, cfg.d_model), ("batch", None, "embed"))
        if cfg.family == "audio":
            specs["frames"] = f((b, s, cfg.d_model), jnp.bfloat16)
            shards["frames"] = sh((b, s, cfg.d_model), ("batch", "seq", "embed"))
    elif cell.kind == "prefill":
        specs["tokens"] = f((b, s), jnp.int32)
        shards["tokens"] = sh((b, s), ("batch", "seq"))
        if cfg.family == "vlm":
            p = cfg.num_patch_tokens
            specs["patch_embeds"] = f((b, p, cfg.d_model), jnp.bfloat16)
            shards["patch_embeds"] = sh((b, p, cfg.d_model), ("batch", None, "embed"))
        if cfg.family == "audio":
            specs["frames"] = f((b, s, cfg.d_model), jnp.bfloat16)
            shards["frames"] = sh((b, s, cfg.d_model), ("batch", "seq", "embed"))
    elif cell.kind == "decode":
        specs["tokens"] = f((b,), jnp.int32)
        shards["tokens"] = sh((b,), ("batch",))
    else:
        raise ValueError(cell.kind)
    return specs, shards
