"""Streamed reconstruction: continuous batching of CT scans over a fixed
pool of volume slots (DESIGN.md §8)."""

from .engine import (ProjectionChunk, ReconstructionEngine,  # noqa: F401
                     ScanState)

__all__ = ["ProjectionChunk", "ReconstructionEngine", "ScanState"]
