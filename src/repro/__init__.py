"""repro: cone-beam CT back projection on the TPU, from kernels to serving."""

__version__ = "0.1.0"

# The blessed public surface (defined in repro/api.py).  Forwarded
# lazily via PEP 562 so ``import repro`` stays cheap — the serving /
# dispatch / tune stacks only load when one of these names is touched.
# ``tests/test_api.py`` asserts this list matches ``repro.api.__all__``.
__all__ = [
    "Geometry",
    "filter_projections",
    "reconstruct",
    "sharded_reconstruct",
    "reconstruct_shards",
    "Dispatcher",
    "ExecutionPlan",
    "get_dispatcher",
    "set_dispatcher",
    "TunedConfig",
    "autotune",
    "ProjectionChunk",
    "ReconstructionEngine",
    "ScanState",
    "CTFrontDoor",
    "ScanTicket",
    "Backpressure",
    "ScanAborted",
    "AdmissionPolicy",
    "FIFOPolicy",
    "SRSFPolicy",
    "DeadlinePolicy",
    "FairSharePolicy",
    "PolicyContext",
    "POLICIES",
]


def __getattr__(name):
    if name in __all__:
        from . import api

        return getattr(api, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
