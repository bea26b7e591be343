"""Slot-based multi-tenant cache management.

Each tenant owns ``num_slots`` sequence slots inside the stacked cache
pytree (leading axes: [tenant, ..., batch=slot, ...]). The manager tracks
slot occupancy and per-slot live lengths; freeing a slot only zeroes its
length, and the slot's cache keeps what its last request left.

Two kinds of state live in a slot. Keys and values are read only up to
the slot's length (the decode kernels mask by it), so stale positions
past it are never read. A recurrent layer's state (Mamba2 conv tails
and SSM state, RWKV state) is masked by nothing: it is read whole at
every step, and the decode step runs every slot, free ones included.
What keeps a request's state out of the next one is admission: the
engine installs the prefilled cache of the new request over every leaf
of the slot (``MultiTenantEngine._scatter_slot``) before the slot
decodes again.

Keys and values are stored positions-last, ``(..., slot, Hkv, D, S)``
(``models.attention``), and the decode step reads them where they lie
and writes each slot's new token at position ``length % S`` after the
layer scan. The engine donates the K/V stacks to that step, so the
arrays it held before a step are deleted by it: read ``engine.caches``
anew after each step, never a reference kept from before.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class SlotState:
    request_id: Optional[int] = None
    length: int = 0

    @property
    def free(self) -> bool:
        return self.request_id is None


class SlotManager:
    def __init__(self, num_tenants: int, slots_per_tenant: int):
        self.slots: Dict[Tuple[int, int], SlotState] = {
            (t, s): SlotState()
            for t in range(num_tenants)
            for s in range(slots_per_tenant)
        }
        self.num_tenants = num_tenants
        self.slots_per_tenant = slots_per_tenant

    def acquire(self, tenant: int, request_id: int) -> Optional[int]:
        for s in range(self.slots_per_tenant):
            st = self.slots[(tenant, s)]
            if st.free:
                st.request_id = request_id
                st.length = 0
                return s
        return None

    def release(self, tenant: int, slot: int) -> None:
        """Free the slot. Its cache is left as it is: the next request
        must have its whole state installed on admission before the slot
        is decoded for it (recurrent state is not masked by length)."""
        self.slots[(tenant, slot)] = SlotState()

    def set_length(self, tenant: int, slot: int, length: int) -> None:
        self.slots[(tenant, slot)].length = length

    def lengths(self, tenant: int) -> List[int]:
        return [self.slots[(tenant, s)].length for s in range(self.slots_per_tenant)]

    def active(self, tenant: int) -> List[int]:
        return [
            s for s in range(self.slots_per_tenant) if not self.slots[(tenant, s)].free
        ]

    def utilization(self) -> float:
        busy = sum(0 if s.free else 1 for s in self.slots.values())
        return busy / len(self.slots)
