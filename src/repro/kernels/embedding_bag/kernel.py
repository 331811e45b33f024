"""EmbeddingBag (gather + segment-sum) as a Pallas TPU kernel.

The DLRM/DeepFM lookup hot path: the table lives in HBM (10⁶–10⁹ rows never
fit VMEM); lookup indices arrive as *scalar-prefetch* operands so the
BlockSpec index_map itself does the row indirection — each grid step DMAs
exactly the (1, D) table row it needs (TPU's analogue of FBGEMM TBE's
gather pipeline) and accumulates into the output bag row held in VMEM.

Requirements (enforced by ops.py):
* ``segment_ids`` sorted ascending — consecutive grid steps that share a bag
  revisit the same output block, which Pallas keeps resident in VMEM; the
  first visit zero-initializes (``pl.when`` on a segment boundary).
* bags with zero lookups are masked to zero by the wrapper (their output
  block is never visited).

Grid: (n_lookups,). Sequential by construction (output revisiting).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, seg_ref, table_row_ref, out_ref):
    i = pl.program_id(0)
    is_first = jnp.logical_or(i == 0, seg_ref[jnp.maximum(i - 1, 0)] != seg_ref[i])

    @pl.when(is_first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += table_row_ref[...].astype(out_ref.dtype)


def embedding_bag_pallas(
    table: jnp.ndarray,  # (V, D)
    indices: jnp.ndarray,  # (n_lookups,) int32, bag-sorted
    segment_ids: jnp.ndarray,  # (n_lookups,) int32 ascending
    n_bags: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    (n_lookups,) = indices.shape
    v, d = table.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # indices, segment_ids
        grid=(n_lookups,),
        in_specs=[
            # the row indirection: block (1, D) at row idx_ref[i]
            pl.BlockSpec((1, d), lambda i, idx_ref, seg_ref: (idx_ref[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, idx_ref, seg_ref: (seg_ref[i], 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="embedding_bag",
    )(indices.astype(jnp.int32), segment_ids.astype(jnp.int32), table)
