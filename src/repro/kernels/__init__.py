"""Pallas TPU kernels for the framework's compute hot-spots.

  slda_gibbs      — the paper's hot loop: document-blocked collapsed-Gibbs
                    sweep, topic dim on lanes, doc block on sublanes
  slda_predict    — fused multi-sweep test-time sampler: all prediction
                    sweeps in one launch, counter-hash in-kernel PRNG;
                    chain-batched grid (M, blocks) feeding ONE shared
                    corpus to all M chains (no M-way replication)
  slda_train      — fused multi-sweep TRAINING launch: k sweeps per
                    launch with an in-kernel block-local delayed-count
                    refresh of the topic-word table (VMEM scratch,
                    per-row ±1 adds); chain-batched grid
                    (M, blocks) runs all M chains in one launch
  access          — the in-kernel reads and writes above, in forms the
                    TPU compiler accepts (SMEM word ids + dynamic row
                    loads, lane-select columns)
  flash_attention — blocked causal attention with native GQA index maps
  ssd_scan        — Mamba-2 chunked state-space scan (state in VMEM scratch)
  rmsnorm         — fused row-blocked RMSNorm

Use through `repro.kernels.ops` (padding + CPU-interpret dispatch); oracles
in `repro.kernels.ref`.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
