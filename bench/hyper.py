"""The program's configuration object from a configuration file: the
model's hyperparameters only.  Every field that picks a path (kernels
or not, blocks, fused sweeps, buckets, the draw) stays at the program's
default, so each cell measures the route users get."""
from __future__ import annotations

from bench.reference import HP

HYPER = HP._fields + ("label_type",)


def slda_config(conf: dict):
    from repro.core import SLDAConfig
    return SLDAConfig(**{k: conf[k] for k in HYPER})
