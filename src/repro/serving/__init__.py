"""Serving: the CT front door over the streaming reconstruction engine."""

from .ct_frontdoor import (AdmissionPolicy, Backpressure,  # noqa: F401
                           CTFrontDoor, DeadlinePolicy, FairSharePolicy,
                           FIFOPolicy, POLICIES, PolicyContext,
                           ScanAborted, ScanTicket, SRSFPolicy)

__all__ = [
    "AdmissionPolicy", "Backpressure", "CTFrontDoor", "DeadlinePolicy",
    "FairSharePolicy", "FIFOPolicy", "POLICIES", "PolicyContext",
    "ScanAborted", "ScanTicket", "SRSFPolicy",
]
