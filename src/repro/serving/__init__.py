"""Multi-tenant inference serving runtime.

The model-level embodiment of space-time scheduling: R tenants of one
architecture run as ONE vmapped program over stacked weights/caches
(every layer's GEMMs become inter-model batched super-kernels), with a
slot-based continuous batcher feeding the decode loop. The engine
schedules nothing: in a fleet, the pump's space-time scheduler
(``core.scheduler``) decides when and where a cohort runs, and the engine runs its prefills
and decode steps to completion, recording each tenant's latency.

``repro.serving.fleet`` puts N engines behind the sim routers (the live
half of the fleet story). The engine (and therefore jax) is imported
LAZILY: building live specs, running the deterministic fake-engine fleet,
and the sim↔live parity suite all stay jax-free — only touching
``MultiTenantEngine`` / ``EngineConfig`` pays the import.
"""

from repro.serving.request import InferenceRequest, RequestState  # noqa: F401

_ENGINE_EXPORTS = ("EngineConfig", "MultiTenantEngine")


def __getattr__(name):
    if name in _ENGINE_EXPORTS:
        from repro.serving import engine

        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_ENGINE_EXPORTS))
