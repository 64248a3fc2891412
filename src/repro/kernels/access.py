"""In-kernel access patterns of the sLDA kernels that lower on the TPU.

The token loops were first written against the interpreter, where
``tokens_ref[:, n]``, ``jnp.take(table, w, axis=0)`` and
``jnp.take(eta, z)`` are plain XLA ops.  Mosaic refuses all three: a
dynamic index on the lane (minor) axis must be a provable multiple of
128, and its gather covers only the 2-D in-vreg form.  These helpers
express the same reads and writes with ops the chip compiles, and every
one returns exactly the value of the op it replaces (DESIGN.md
§Predict-kernel, "Row access on the chip"):

  * ``column`` / ``set_column`` — the token position ``n`` is a lane
    index of the ``[DB, N]`` doc-block tiles, so a column is a lane
    select plus a lane reduction over one nonzero term (exact), and a
    column store is a full-tile select;
  * ``gather_rows`` — word ids come from a copy of the token tile in
    SMEM, and each document's ``[1, T]`` table row is a dynamic sublane
    load from the VMEM table into a ``[DB, T]`` staging buffer: a copy,
    so counts and probabilities come back bit-exact;
  * ``add_rows`` — the scatter-add form of the same loop, which the
    train kernel's block-local refresh uses (integer-valued ±1 adds);
  * ``pick`` — ``row[z]`` per document as a select over the topic lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def check_compiled_mode(sampler_mode: str, interpret: bool):
    """The sparse two-stage draw gathers along the topic lanes
    (`take_along_axis`), which the TPU compiler refuses; it runs only
    interpreted or on the jnp route."""
    if sampler_mode == "sparse" and not interpret:
        raise NotImplementedError(
            "sampler_mode='sparse' has no compiled TPU kernel; use "
            "sampler_mode='dense' or the jnp route (use_pallas=False)")


def column(block, n):
    """``block[:, n]`` of a ``[DB, N]`` tile for a traced ``n``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == n, block, jnp.zeros_like(block)),
                   axis=1)


def set_column(ref, n, values):
    """``ref[:, n] = values`` for a traced ``n``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 1)
    ref[...] = jnp.where(lane == n, values[:, None], ref[...])


def gather_rows(table_ref, ids_ref, n, rows_ref):
    """``rows_ref[d] = table_ref[ids_ref[d, n]]`` for every document d of
    the block; returns the ``[DB, T]`` rows.  ``ids_ref`` lives in SMEM."""
    def one(d, carry):
        rows_ref[pl.ds(d, 1), :] = table_ref[pl.ds(ids_ref[d, n], 1), :]
        return carry
    jax.lax.fori_loop(0, rows_ref.shape[0], one, 0)
    return rows_ref[...]


def add_rows(table_ref, ids_ref, n, delta_ref):
    """``table_ref[ids_ref[d, n]] += delta_ref[d]`` for every d, in
    document order (repeated word ids accumulate)."""
    def one(d, carry):
        r = pl.ds(ids_ref[d, n], 1)
        table_ref[r, :] = table_ref[r, :] + delta_ref[pl.ds(d, 1), :]
        return carry
    jax.lax.fori_loop(0, delta_ref.shape[0], one, 0)


def pick(row, onehot):
    """``row[z_d]`` for each document, given ``onehot = (iota == z[:, None])``
    — one nonzero term per lane row, so the sum is exact."""
    return jnp.sum(jnp.where(onehot, row[None, :], 0.0), axis=1)
