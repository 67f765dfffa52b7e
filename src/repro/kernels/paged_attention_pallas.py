"""Pallas TPU paged attention: block-table K/V gather in-kernel.

The serve engine's KV cache is a pool of fixed-size blocks; each request
owns an ordered *block table* mapping logical positions to pages.  Dense
attention would need the pool compacted per step — this kernel instead
gathers pages through the table inside the kernel, so a step touches
exactly the pages its requests own:

* grid = (batch, kv_heads, max_blocks); the block axis is innermost
  (sequential) so the online-softmax accumulator lives in VMEM scratch
  across page iterations, as in the flash kernel.
* the block tables and per-slot ``(row_start, row_len)`` ride in as
  *scalar prefetch* (``pltpu.PrefetchScalarGridSpec``): the k/v
  BlockSpec index maps read ``tables[b, j]`` to pick the HBM page to
  stream, which is the whole trick — the gather happens in the DMA
  engine, not in compute.
* the kernel reads the engine's whole pool, ``[L, KV, NB, BS, lanes]``
  (kv-head major, rows lane-padded, see
  ``models.transformer.init_paged_cache``), in place: the layer rides
  in as a fourth scalar-prefetch operand and the k/v index maps pick
  ``(layer, g, tables[b, j])``, so no per-layer slice of the pool is
  ever materialised (a ``pallas_call`` operand is a buffer of its own,
  so ``pool[layer]`` would copy the layer's every page each call).  The
  layer is traced, not static: all layers share one trace and one
  lowering of the kernel at each call shape.
* one grid step streams a single ``[BS, lanes]`` tile; the query block
  is one kv head's whole group, ``[T * G, lanes]`` (row ``r`` is query
  ``r // G``), so every block's trailing dims are its array's own, as
  the TPU compiler requires of blocks below its (8, 128) tile.
* ragged sequences: query ``t < row_len[b]`` of request ``b`` sits at
  absolute position ``row_start[b] + t`` and attends causally over its
  own prefix; rows ``t >= row_len[b]`` are padding and come back exactly
  zero.  Pages entirely past the context (or outside a sliding window)
  are skipped with ``pl.when`` — a request with 3 live pages in a
  64-page table does 3 page-iterations of work.

Pad slots of a table must hold an *in-range* page id (the allocator pads
with 0): the index map runs for skipped iterations too.

One kernel, three entry points:

* :func:`paged_attention_varlen` — up to ``Tmax`` query rows per slot.
  Decode (``row_len == 1``), speculative verify (``row_len == k``) and
  chunked prefill tiles (ragged ``row_len``) are its call shapes.
* :func:`paged_attention` — one query token per request, at position
  ``context_lens[b] - 1`` (the plain decode step).
* :func:`paged_attention_multi` — the fixed-``T`` shape (every active
  slot supplies exactly ``T`` rows ending at ``context_lens[b]``).

Forward-only; the pure-jnp oracles are
``repro.kernels.ref.ref_paged_attention`` and
``ref.ref_paged_attention_varlen``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import pad_lanes

NEG_INF = -1e30


def _paged_varlen_kernel(
    tables_ref,   # scalar prefetch [B, M] int32
    start_ref,    # scalar prefetch [B] int32 (abs position of query row 0)
    len_ref,      # scalar prefetch [B] int32 (live query rows, 0 = inactive)
    layer_ref,    # scalar prefetch [1] int32 (read by the k/v index maps)
    q_ref,        # [1, 1, T*G, D] one kv group's query heads, t-major
    k_ref,        # [BS, D] one page of one layer's kv head
    v_ref,        # [BS, D]
    o_ref,        # [1, 1, T*G, D]
    m_ref,        # scratch [T*G, 1]
    l_ref,        # scratch [T*G, 1]
    acc_ref,      # scratch [T*G, D]
    *,
    block_size: int,
    num_blocks_max: int,
    group: int,
    window: Optional[int],
    scale: float,
):
    del layer_ref
    b = pl.program_id(0)
    j = pl.program_id(2)
    base = start_ref[b]           # absolute position of query 0
    n = len_ref[b]                # live rows; padding rows t >= n
    ctx = base + n                # rows live once the chunk is written
    rows = q_ref.shape[2]         # T * G: row r is query t = r // G

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = j * block_size
    live = jnp.logical_and(k_start < ctx, n > 0)
    if window is not None:
        # The *oldest* query (position `base`) has the leftmost window;
        # a page fully left of it is dead for every query in the chunk.
        live = jnp.logical_and(
            live, base - (k_start + block_size - 1) < window
        )

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [R, D]
        k = k_ref[...].astype(jnp.float32)                   # [BS, D]
        v = v_ref[...].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [R, BS]

        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        qpos = base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0) // group
        mask = kpos <= qpos                                  # causal
        if window is not None:
            mask = jnp.logical_and(mask, (qpos - kpos) < window)
        # Padding rows (t >= n) get a fully-masked score row; their m
        # saturates at NEG_INF and the accumulator fills with garbage
        # that _finalize zeroes out.
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[...]                                  # [R, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                          # [R, BS]
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == num_blocks_max - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)               # [R, 1]
        row_live = jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // group < n
        out = jnp.where(row_live, acc_ref[...] / denom, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"),
)
def paged_attention_varlen(
    q: jax.Array,             # [B, T, H, D] ragged query chunks, right-padded
    k_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D] the whole pool
    v_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads must be in-range)
    row_start: jax.Array,     # [B] int32 abs position of query row 0
    row_len: jax.Array,       # [B] int32 live rows per slot (0 = inactive)
    *,
    layer: jax.Array,         # int32 scalar: the pool layer to read
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged multi-token attention over a paged KV pool.

    Query ``t < row_len[b]`` of request ``b`` sits at absolute position
    ``row_start[b] + t`` and attends causally over positions ``<=`` its
    own; rows ``t >= row_len[b]`` are padding and yield exactly zero, as
    does a slot with ``row_len[b] == 0``.  Decode (``row_len == 1``),
    speculative verify (``row_len == k``) and chunked prefill tiles are
    all this one kernel called with different ``(row_start, row_len)``
    tables.  Keys and values are read from layer ``layer`` of the pool,
    in place."""
    b, t, h, d = q.shape
    _, kv, _, block_size, lanes = k_pages.shape
    m = block_tables.shape[1]
    assert h % kv == 0, (h, kv)
    group = h // kv
    rows = t * group
    scale = d ** -0.5
    # [B, T, KV*G, D] -> [B, KV, T*G, lanes]: one kv head's query group
    # is a block whose trailing dims are the array's own, which the TPU
    # compiler requires of a block narrower than (8, 128).  The zero
    # pad lanes (pool rows are lane-padded alike) add exact zeros.
    qg = q.reshape(b, t, kv, group, d).transpose(0, 2, 1, 3, 4)
    qg = pad_lanes(qg.reshape(b, kv, rows, d), lanes)

    # One page of one layer's kv head: the leading three dims are
    # squeezed, the trailing two are the pool's own.
    page = (pl.Squeezed(), pl.Squeezed(), pl.Squeezed(), block_size, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kv, m),
        in_specs=[
            pl.BlockSpec(
                (1, 1, rows, lanes),
                lambda b_, g_, j, tbl, rs, rl, ly: (b_, g_, 0, 0)),
            pl.BlockSpec(
                page,
                lambda b_, g_, j, tbl, rs, rl, ly: (
                    ly[0], g_, tbl[b_, j], 0, 0),
            ),
            pl.BlockSpec(
                page,
                lambda b_, g_, j, tbl, rs, rl, ly: (
                    ly[0], g_, tbl[b_, j], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, lanes),
            lambda b_, g_, j, tbl, rs, rl, ly: (b_, g_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_varlen_kernel, block_size=block_size, num_blocks_max=m,
            group=group, window=window, scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, lanes), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), row_start.astype(jnp.int32),
      row_len.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qg, k_pages, v_pages)
    out = out[..., :d].reshape(b, kv, t, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, h, d)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"),
)
def paged_attention(
    q: jax.Array,             # [B, H, D]
    k_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D] the whole pool
    v_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads must be in-range)
    context_lens: jax.Array,  # [B] int32
    *,
    layer: jax.Array,         # int32 scalar: the pool layer to read
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention over a paged KV pool: the
    ``row_len == 1`` shape of :func:`paged_attention_varlen`, with the
    query at position ``context_lens[b] - 1``.  A slot with
    ``context_lens[b] == 0`` is inactive and yields exactly zero."""
    context_lens = context_lens.astype(jnp.int32)
    active = context_lens > 0
    out = paged_attention_varlen(
        q[:, None], k_pages, v_pages, block_tables,
        jnp.where(active, context_lens - 1, 0), active.astype(jnp.int32),
        layer=layer, window=window, interpret=interpret)
    return out[:, 0]


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"),
)
def paged_attention_multi(
    q: jax.Array,             # [B, T, H, D] consecutive query tokens
    k_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D] the whole pool
    v_pages: jax.Array,       # [L, KV, NB, BS, lanes >= D]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads must be in-range)
    context_lens: jax.Array,  # [B] int32 rows live *including* the T chunk
    *,
    layer: jax.Array,         # int32 scalar: the pool layer to read
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fixed-``T`` shape of :func:`paged_attention_varlen`: query ``t``
    of request ``b`` sits at absolute position ``context_lens[b] - T +
    t`` and attends causally over positions ``<=`` its own.  A slot with
    ``context_lens[b] == 0`` is inactive and yields exactly zero."""
    t = q.shape[1]
    context_lens = context_lens.astype(jnp.int32)
    active = context_lens > 0
    row_start = jnp.where(active, context_lens - t, 0)
    row_len = jnp.where(active, t, 0)
    return paged_attention_varlen(
        q, k_pages, v_pages, block_tables, row_start, row_len,
        layer=layer, window=window, interpret=interpret)
