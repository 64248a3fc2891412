"""Metrics substrate: step logging + chain-ensemble health."""
from .log import MetricLogger
from .ensemble import chain_divergence, ensemble_health, robust_z

__all__ = ["MetricLogger", "chain_divergence", "ensemble_health",
           "robust_z"]
