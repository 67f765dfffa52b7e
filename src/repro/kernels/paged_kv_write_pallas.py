"""Pallas TPU in-place paged KV row write: block-table scatter via DMA.

The serve engine's decode step appends one K/V row per active slot into
the pooled block cache.  Expressing that append as a jnp scatter on a
scan-carried pool makes XLA rewrite the *entire* ``[L, KV, NB, BS, lanes]``
pool every step — per-step cost grows linearly in ``num_blocks`` even
though exactly one row per layer changes (ROADMAP: a 128-block pool
measured ~2.7x slower than 16-block at equal work).  This kernel is the
write-side mirror of ``kernels/paged_attention_pallas.py``'s gather:

* the pool rides in (and out) as an **aliased HBM operand**
  (``input_output_aliases`` + ``memory_space=ANY``): the output *is* the
  input buffer, so nothing outside the touched rows moves;
* per-slot page ids / in-page offsets arrive as *scalar prefetch*
  (``pltpu.PrefetchScalarGridSpec``), so the destination of each row is
  known before the body runs — the scatter happens in the DMA engine
  (``pltpu.make_async_copy`` VMEM -> HBM), not in compute;
* grid = (batch,): slot b DMAs its ``[KV, 1, 1, lanes]`` K and V rows
  into ``pages[layer, :, page_idx[b], offset[b], :]``; inactive slots
  skip the copy entirely with ``pl.when`` (the aliased buffer keeps its
  old rows — "drop" semantics for free, and zero traffic for dead
  slots).  Rows arrive ``[B, KV, D]`` and are zero-padded to the pool's
  ``lanes`` (a multiple of 128): the TPU's DMA engine refuses a slice
  narrower than the 128-lane tile, so a 64-wide row cannot be written.

Every row has its own destination (distinct requests own distinct pages,
and one request's rows distinct positions), so the per-slot DMAs never
collide; a ``[B, T]`` chunk is written as ``B * T`` slots in one call.
``layer`` is static: the hoisted layer loop of the paged steps
(``transformer.decode_step_paged*``) emits one dispatch per layer
against the stacked pool.

Forward-only; the pure-jnp oracle is
``repro.kernels.ref.ref_paged_kv_write`` (whose per-slot
``dynamic_update_slice`` structure XLA also updates in place — the
CPU/reference path gets the same flat-in-``num_blocks`` cost).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import pad_lanes


def _kv_write_kernel(
    page_idx_ref,   # scalar prefetch [B] int32 (in-range for active slots)
    offset_ref,     # scalar prefetch [B] int32 row offset within the page
    active_ref,     # scalar prefetch [B] int32 (0 = drop the write)
    k_rows_ref,     # [1, KV, 1, 1, D] VMEM — slot b's new K row
    v_rows_ref,     # [1, KV, 1, 1, D] VMEM
    k_in_ref,       # [L, KV, NB, BS, lanes] ANY/HBM (aliased with k_out_ref)
    v_in_ref,       # [L, KV, NB, BS, lanes] ANY/HBM (aliased with v_out_ref)
    k_out_ref,      # same buffer as k_in_ref
    v_out_ref,      # same buffer as v_in_ref
    k_sem,          # DMA semaphore
    v_sem,          # DMA semaphore
    *,
    layer: int,
):
    del k_in_ref, v_in_ref  # aliased: the out refs are the same buffers
    b = pl.program_id(0)

    @pl.when(active_ref[b] != 0)
    def _write():
        page = page_idx_ref[b]
        off = offset_ref[b]
        copy_k = pltpu.make_async_copy(
            k_rows_ref.at[0],
            k_out_ref.at[layer, :, pl.ds(page, 1), pl.ds(off, 1), :],
            k_sem,
        )
        copy_v = pltpu.make_async_copy(
            v_rows_ref.at[0],
            v_out_ref.at[layer, :, pl.ds(page, 1), pl.ds(off, 1), :],
            v_sem,
        )
        copy_k.start()
        copy_v.start()
        copy_k.wait()
        copy_v.wait()


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def paged_kv_write(
    k_pages: jax.Array,   # [L, KV, NB, BS, lanes] pooled key blocks
    v_pages: jax.Array,   # [L, KV, NB, BS, lanes] pooled value blocks
    k_rows: jax.Array,    # [B, KV, D] new key rows (one per slot)
    v_rows: jax.Array,    # [B, KV, D] new value rows
    page_idx: jax.Array,  # [B] int32 destination page per slot
    offset: jax.Array,    # [B] int32 destination row within the page
    active: jax.Array,    # [B] bool/int; False slots write nothing
    *,
    layer: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one decode step's K/V rows into layer ``layer`` in place.

    Returns the (aliased) pools; the caller must treat its input pools as
    consumed, exactly like a donated buffer.  ``page_idx`` of an inactive
    slot may be any value (the copy is skipped before the id is read).
    """
    b, kv, _ = k_rows.shape
    assert k_pages.ndim == 5, k_pages.shape
    d = k_pages.shape[4]      # the pool's (lane-padded) row width
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kv, 1, 1, d),
                         lambda b_, pi, of, ac: (b_, 0, 0, 0, 0)),
            pl.BlockSpec((1, kv, 1, 1, d),
                         lambda b_, pi, of, ac: (b_, 0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, layer=layer),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Operand indices count the scalar-prefetch args: the pools are
        # operands 5/6 and alias outputs 0/1 — the in-place contract.
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(page_idx.astype(jnp.int32), offset.astype(jnp.int32),
      active.astype(jnp.int32),
      pad_lanes(k_rows, d).reshape(b, kv, 1, 1, d).astype(k_pages.dtype),
      pad_lanes(v_rows, d).reshape(b, kv, 1, 1, d).astype(v_pages.dtype),
      k_pages, v_pages)
