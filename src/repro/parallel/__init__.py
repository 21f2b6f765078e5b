"""Mesh/collective helpers shared by the core algorithms and the service."""

from repro.parallel.collectives import replicate

__all__ = ["replicate"]
