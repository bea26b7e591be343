"""Multi-tenant model store: stacked weight pytrees + eviction.

The space-time scheduler's model-level form: R tenants of the same
architecture (different weights — "These models have different weights and
inputs, as is likely in a multi-tenancy setting") are stored STACKED along
a leading tenant axis, so one vmap'd program serves all tenants — every
matmul becomes a batched super-kernel, and on a pod the tenant axis shards
over the `data` mesh axis.

Contrast with per-process replication (paper Fig 5): stacked storage holds
exactly R copies of the weights and zero framework duplication, which is
what let the paper's explicit-streams variant scale to 60+ ResNet-50s
while MPS hit the 16 GB wall at 18.

A model tree's top-level ``"unit"`` key holds the layer stack that the
model scans (``[reps, ...]`` per tenant); stacked, those leaves are
layer-major, ``[reps, R, ...]``, so a scan over the layers reads each
layer's R tenants where they lie instead of relaying the whole stack on
every call. Every other leaf is tenant-major, ``[R, ...]``.
``tenant_axes`` names the axis; everything that stacks, indexes or
counts tenants goes through it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Set

import jax
import jax.numpy as jnp

Params = Any


#: the top-level key of a model tree whose leaves are stacked layer-major
LAYER_MAJOR_KEY = "unit"


def tenant_axes(tree: Params) -> Any:
    """Each leaf's tenant axis in the stacked form of ``tree``, as a tree
    prefix (usable as ``jax.vmap``'s ``in_axes``/``out_axes``): 1 under a
    top-level ``"unit"`` key, 0 everywhere else."""
    if isinstance(tree, dict) and LAYER_MAJOR_KEY in tree:
        return {k: int(k == LAYER_MAJOR_KEY) for k in tree}
    return 0


def map_tenant_axis(f: Callable[..., Any], tree: Params, *rest: Params) -> Params:
    """``jax.tree.map(f, tree, *rest)`` with the stacked leaf's tenant
    axis (``tenant_axes(tree)``) as ``f``'s first argument."""
    return jax.tree.map(
        lambda ax, *subs: jax.tree.map(functools.partial(f, ax), *subs),
        tenant_axes(tree), tree, *rest)


def stack_params(params_list: List[Params]) -> Params:
    """Stack R tenants' pytrees along each leaf's tenant axis."""
    return map_tenant_axis(lambda ax, *xs: jnp.stack(xs, axis=ax),
                           *params_list)


def init_stacked(init_fn: Callable[[jax.Array], Params], key: jax.Array,
                 r: int, device=None) -> Params:
    """R tenants' weights built directly in stacked form: tenant ``t``
    draws from ``fold_in(key, t)``. No per-tenant tree ever exists, so
    the device holds one copy of R tenants' weights (stacking a list
    needs room for both), and the vmapped draw writes each leaf on its
    tenant axis. ``device`` places (and commits) the result."""
    keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(jnp.arange(r))
    sharding = (jax.sharding.SingleDeviceSharding(device)
                if device is not None else None)
    out_axes = tenant_axes(jax.eval_shape(init_fn, key))
    return jax.jit(jax.vmap(init_fn, out_axes=out_axes),
                   out_shardings=sharding)(keys)


def take_tenant(stacked: Params, t) -> Params:
    """Tenant ``t``'s tree out of a stacked one (``t`` may be traced)."""
    return map_tenant_axis(
        lambda ax, x: jax.lax.dynamic_index_in_dim(x, t, ax, keepdims=False),
        stacked)


def unstack_params(stacked: Params, r: int) -> List[Params]:
    return [take_tenant(stacked, i) for i in range(r)]


def tenant_counts(stacked: Params) -> Set[int]:
    """The sizes of the stacked leaves' tenant axes (one, if consistent)."""
    return set(jax.tree.leaves(
        map_tenant_axis(lambda ax, x: x.shape[ax], stacked)))


def broadcast_tenants(tree: Params, r: int) -> Params:
    """One tenant's ``tree`` repeated R times on each leaf's tenant axis."""
    return map_tenant_axis(
        lambda ax, x: jnp.broadcast_to(jnp.expand_dims(x, ax),
                                       x.shape[:ax] + (r,) + x.shape[ax:]),
        tree)


def tenant_bytes(params: Params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


@dataclasses.dataclass
class TenantSlot:
    tenant_id: int
    active: bool = True
    evictions: int = 0


class TenantManager:
    """Registry of co-located tenants and their stacked weights."""

    def __init__(self) -> None:
        self._slots: Dict[int, TenantSlot] = {}
        self._params: Dict[int, Params] = {}
        self._stacked: Optional[Params] = None
        self._stack_order: List[int] = []
        self._dirty = True

    # ------------------------------------------------------------- membership
    def register(self, tenant_id: int, params: Params) -> None:
        if tenant_id in self._slots:
            raise ValueError(f"tenant {tenant_id} already registered")
        self._slots[tenant_id] = TenantSlot(tenant_id)
        self._params[tenant_id] = params
        self._dirty = True

    def evict(self, tenant_id: int) -> None:
        """Straggler eviction: drop the tenant from the merged cohort.

        The tenant is marked inactive (its weights stay resident so it can
        be re-admitted to a fresh slot, as the paper's evict-and-restart
        policy does) and the stacked cohort is rebuilt without it.
        """
        slot = self._slots[tenant_id]
        slot.active = False
        slot.evictions += 1
        self._dirty = True

    def readmit(self, tenant_id: int) -> None:
        self._slots[tenant_id].active = True
        self._dirty = True

    @property
    def active_ids(self) -> List[int]:
        return sorted(tid for tid, s in self._slots.items() if s.active)

    # ------------------------------------------------------------- stacking
    def stacked(self) -> Params:
        """Stacked weights of the ACTIVE cohort, rebuilt lazily on change."""
        if self._dirty:
            ids = self.active_ids
            if not ids:
                raise ValueError("no active tenants")
            self._stacked = stack_params([self._params[i] for i in ids])
            self._stack_order = ids
            self._dirty = False
        return self._stacked

    @property
    def stack_order(self) -> List[int]:
        self.stacked()
        return list(self._stack_order)

    def memory_bytes(self) -> int:
        """Total resident weight bytes (stacked cohort)."""
        return sum(tenant_bytes(self._params[i]) for i in self._slots)
