"""Sharding rule tables for params, inputs, and caches.

Strategy (single-pod mesh ``(data=16, model=16)``; multi-pod adds a leading
``pod=2`` axis used for data parallelism and ZeRO-style optimizer-state
sharding):

* **Weights: FSDP-style 2D sharding.** Every >=2-D parameter leaf greedily
  assigns the ``model`` axis to its largest divisible dim, then the ``data``
  axis to the largest remaining divisible dim. 1-D leaves shard over
  ``model`` when divisible, else replicate. This is uniform across all ten
  architectures — heads/experts/d_ff usually land on ``model``, d_model or
  vocab on ``data`` — and lets 400B-class weights fit per-device HBM.
* **Batch-bearing activations** shard batch over ``(pod, data)`` when
  divisible, falling back to ``data`` then replication.
* **Decode caches**: batch over ``data``; KV heads over ``model`` when
  divisible, else head_dim; for global_batch=1 long-context decode the
  *sequence* axis takes ``data`` instead (sequence-sharded KV cache).
* **Optimizer state (mu/nu)** inherits the param spec, plus — multi-pod —
  the ``pod`` axis on the largest still-unsharded divisible dim (ZeRO-1
  across pods).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


# --------------------------------------------------------------------- helpers
def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes usable for batch data parallelism, biggest grouping first."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def choose_spec(
    shape: Sequence[int],
    mesh: Mesh,
    axes_priority: Sequence[Any] = ("model", "data"),
    taken: Optional[Dict[int, Any]] = None,
) -> P:
    """Greedy divisible assignment: each axis (in priority order) goes to the
    largest not-yet-sharded dim it divides evenly."""
    assign: Dict[int, Any] = dict(taken or {})
    for axis in axes_priority:
        if axis not in mesh.axis_names and not isinstance(axis, tuple):
            continue
        size = _axis_size(mesh, axis)
        best, best_dim = None, 0
        for d, n in enumerate(shape):
            if d in assign:
                continue
            if n % size == 0 and n // size > 0 and n > best_dim:
                best, best_dim = d, n
        if best is not None:
            assign[best] = axis
    return P(*[assign.get(d) for d in range(len(shape))])


def _batch_spec(mesh: Mesh, batch: int) -> Optional[Any]:
    """Pick the widest divisible data-parallel grouping for a batch dim."""
    dp = data_axes(mesh)
    for cand in (dp, dp[-1:] if dp else ()):
        if not cand:
            continue
        axes = cand if len(cand) > 1 else cand[0]
        if batch % _axis_size(mesh, axes) == 0:
            return axes
    return None


# --------------------------------------------------------------------- params
def param_specs(params_shape: Pytree, mesh: Mesh, policy: str = "fsdp") -> Pytree:
    """Weight sharding specs for a parameter pytree of ShapeDtypeStructs.

    policy:
      "fsdp"      2D (model, data) — minimal memory, pays weight all-gathers
                  every step. Right for huge models / big per-step compute.
      "tp"        model-axis only, replicated across data — zero weight
                  gathers (activation all-reduces instead). Right for
                  latency-critical decode when W/16 fits HBM.
      "replicate" no weight sharding at all — zero weight collectives.
                  Right for small models (the paper's multi-tenant regime).
      "auto"      per-model choice by replicated-weight footprint:
                  <= 4 GiB -> replicate; <= 4 GiB model-sharded -> tp;
                  else fsdp.
    """
    if policy == "auto":
        total = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(params_shape)
        )
        if total <= 4 * 2**30:
            policy = "replicate"
        elif total / mesh.shape.get("model", 1) <= 4 * 2**30:
            policy = "tp"
        else:
            policy = "fsdp"

    axes = {
        "fsdp": ("model", "data"),
        "tp": ("model",),
        "replicate": (),
    }[policy]

    def rule(leaf) -> P:
        shape = leaf.shape
        if not axes:
            return P(*([None] * len(shape)))
        if len(shape) <= 1:
            return choose_spec(shape, mesh, axes[:1])
        if len(shape) == 2:
            return choose_spec(shape, mesh, axes)
        # stacked leaves (reps/experts leading): never shard the stack axis
        # of scanned units; DO shard expert axis. Heuristic: axis 0 is
        # protected, remaining dims get model/data greedily.
        return choose_spec(shape, mesh, axes, taken={0: None})

    return jax.tree.map(rule, params_shape)


def opt_state_specs(params_shape: Pytree, mesh: Mesh, policy: str = "fsdp") -> Pytree:
    """mu/nu: param spec + pod axis on the largest remaining dim (ZeRO-1)."""
    base = param_specs(params_shape, mesh, policy)
    if "pod" not in mesh.axis_names:
        return base

    def widen(leaf, spec: P) -> P:
        shape = leaf.shape
        taken = {d: a for d, a in enumerate(spec) if a is not None}
        if len(shape) >= 3:
            taken.setdefault(0, None)
        return choose_spec(shape, mesh, ("pod",), taken=taken)

    return jax.tree.map(widen, params_shape, base)


# --------------------------------------------------------------------- caches
def cache_specs(cache_shape: Pytree, mesh: Mesh, batch: int) -> Pytree:
    """Specs for the decode-cache pytree (see models.transformer layout).

    Leaf layouts (unit caches carry a leading reps axis, rem caches don't):
        k/v      (B, Hkv, D, S)   attention KV
        conv     (B, W, C)        mamba conv state
        ssm      (B, H, P, N)     mamba SSM state
        wkv      (B, H, N, N)     rwkv state
        shift_*  (B, D)           rwkv token-shift state
    """
    bspec = _batch_spec(mesh, batch)

    def rule(path, leaf) -> P:
        shape = leaf.shape
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        is_unit = any(
            isinstance(e, jax.tree_util.DictKey) and str(e.key) == "unit" for e in path
        )
        off = 1 if is_unit else 0  # skip the reps axis
        spec: list = [None] * len(shape)

        def set_if_div(dim: int, axis) -> bool:
            size = _axis_size(mesh, axis)
            if shape[dim] % size == 0 and spec[dim] is None:
                spec[dim] = axis
                return True
            return False

        if name in ("k", "v"):
            # (B, Hkv, D, S). NEVER shard head_dim D: contracting a
            # model-sharded D turns every decode score tensor into a
            # (B,H,S)-sized all-reduce per layer (measured 16.8 MB x L —
            # the dominant collective in the decode baseline). When KV
            # heads don't divide the model axis, shard the SEQUENCE dim
            # instead: softmax/value contractions then reduce to
            # (B,H,D)-sized partials only.
            b, h, d, s = off, off + 1, off + 2, off + 3
            if bspec is not None and shape[b] % _axis_size(mesh, bspec) == 0:
                spec[b] = bspec
                set_if_div(h, "model") or set_if_div(s, "model")
            else:
                # batch=1 long-context: sequence-sharded cache
                set_if_div(s, "data")
                set_if_div(h, "model") or set_if_div(s, "model")
        elif name is not None and name.startswith("conv"):
            b, w, c = off, off + 1, off + 2
            if bspec is not None and shape[b] % _axis_size(mesh, bspec) == 0:
                spec[b] = bspec
            set_if_div(c, "model")
        elif name in ("ssm", "wkv"):
            b, h = off, off + 1
            if bspec is not None and shape[b] % _axis_size(mesh, bspec) == 0:
                spec[b] = bspec
            set_if_div(h, "model")
        elif name is not None and name.startswith("shift"):
            b, d = off, off + 1
            if bspec is not None and shape[b] % _axis_size(mesh, bspec) == 0:
                spec[b] = bspec
            set_if_div(d, "model")
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


# --------------------------------------------------------------------- inputs
def input_specs_shardings(
    mesh: Mesh, batch: int, kind: str
) -> Dict[str, P]:
    """Specs for token-level step inputs."""
    bspec = _batch_spec(mesh, batch)
    return {
        "tokens": P(bspec, None),
        "labels": P(bspec, None),
        "token": P(bspec),
        "lengths": P(bspec),
        "prefix_embeds": P(bspec, None, None),
    }


def to_shardings(specs: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
