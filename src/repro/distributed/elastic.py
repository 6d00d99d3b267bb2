"""Elastic scaling & failure recovery.

The recovery contract at fleet scale:

  1. every state tree is checkpointed unsharded + self-describing
     (``repro.checkpoint``), so restore is mesh-shape independent;
  2. on node failure, the controller rebuilds the largest healthy mesh that
     preserves the ``model`` axis width (TP width is baked into kernels'
     efficiency; DP width is the elastic dimension), re-derives shardings
     from the same logical rules, and restores;
  3. the data pipeline resumes from the checkpointed cursor; the scheduler
     (paper layer) re-enqueues in-flight requests — its state is tiny
     (queues + remain_token) and rides in checkpoint metadata.

``remesh_plan`` computes the new mesh; ``reshard_restore`` does 1+2. The
round-trip is exercised on fake devices in tests/test_distributed.py.
Straggler mitigation at the request level is the paper's Algorithm 1 (work
stealing); at the step level the engine re-buckets slow prefills (see
serving.engine).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..checkpoint import restore_checkpoint
from ..launch.mesh import make_mesh
from .sharding import ShardingConfig, build_param_specs

Tree = Any


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    old_shape: Tuple[int, ...]
    new_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    lost_devices: int

    @property
    def healthy_fraction(self) -> float:
        return float(np.prod(self.new_shape)) / float(np.prod(self.old_shape))


def remesh_plan(
    old_shape: Tuple[int, ...],
    axis_names: Tuple[str, ...],
    n_healthy: int,
    model_axis: str = "model",
) -> RemeshPlan:
    """Largest mesh ≤ n_healthy devices that keeps the model-axis width.

    The DP axes shrink to the largest power-of-two product that fits; the
    TP axis is preserved (weights' shard layout and per-chip working set
    stay identical, so restart needs no retuning).
    """
    sizes = dict(zip(axis_names, old_shape))
    tp = sizes.get(model_axis, 1)
    if n_healthy < tp:
        raise ValueError(
            f"cannot preserve model axis {tp} with only {n_healthy} devices"
        )
    dp_budget = n_healthy // tp
    # distribute the dp budget over the non-model axes, largest-first
    dp_axes = [a for a in axis_names if a != model_axis]
    new_sizes = dict(sizes)
    # shrink to powers of two that fit
    total_dp = 1
    for a in dp_axes:
        total_dp *= sizes[a]
    scale = 1
    while total_dp // scale > dp_budget:
        scale *= 2
    remaining = scale
    for a in reversed(dp_axes):  # shrink innermost dp axis first
        while remaining > 1 and new_sizes[a] > 1:
            new_sizes[a] //= 2
            remaining //= 2
    new_shape = tuple(new_sizes[a] for a in axis_names)
    return RemeshPlan(
        old_shape=tuple(old_shape),
        new_shape=new_shape,
        axis_names=tuple(axis_names),
        lost_devices=int(np.prod(old_shape)) - n_healthy,
    )


def build_mesh(plan: RemeshPlan):
    """Materialize the plan's mesh, dropping axes that shrank to 1 if they
    are leading pod axes (a 1-pod mesh is just (data, model))."""
    shape, names = [], []
    for s, a in zip(plan.new_shape, plan.axis_names):
        if s == 1 and a == "pod":
            continue
        shape.append(s)
        names.append(a)
    return make_mesh(tuple(shape), tuple(names))


def reshard_restore(
    checkpoint_dir,
    abstract_tree: Tree,
    logical_axes: Tree,
    mesh,
    scfg: Optional[ShardingConfig] = None,
    step: Optional[int] = None,
) -> Tuple[Tree, Dict[str, Any]]:
    """Restore a checkpoint onto a (possibly different) mesh."""
    scfg = scfg or ShardingConfig(
        dp_axes=tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    )
    specs = build_param_specs(abstract_tree, logical_axes, mesh, scfg)
    return restore_checkpoint(checkpoint_dir, step, abstract_tree, specs)
