"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` is the semantic ground truth the kernels must reproduce;
tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle.  These are
also the CPU/autodiff fallback paths used by the models when the Pallas
route is disabled.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# V-trace (paper Eqs. 14-15) — same math as repro.core.vtrace, re-exported
# here so the kernel package is self-contained for its tests.
# ---------------------------------------------------------------------------


def ref_vtrace(
    log_ratios: jax.Array,      # [B, T]
    values: jax.Array,          # [B, T]
    bootstrap_value: jax.Array,  # [B]
    rewards: jax.Array,         # [B, T]
    discounts: jax.Array,       # [B, T]
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    lam: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (vs, advantages)."""
    from repro.core.vtrace import vtrace

    out = vtrace(
        log_ratios=log_ratios, values=values,
        bootstrap_value=bootstrap_value, rewards=rewards,
        discounts=discounts, rho_bar=rho_bar, c_bar=c_bar, lam=lam,
    )
    return out.vs, out.advantages


# ---------------------------------------------------------------------------
# Flash attention (causal / sliding-window, GQA)
# ---------------------------------------------------------------------------


def ref_attention(
    q: jax.Array,   # [B, S, H, D]
    k: jax.Array,   # [B, S, KV, D]
    v: jax.Array,   # [B, S, KV, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,  # None = global
) -> jax.Array:
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = d ** -0.5
    qg = q.reshape(b, s, kv, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg * scale, k,
                        preferred_element_type=jnp.float32)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask = qi >= ki
    if window is not None:
        mask = jnp.logical_and(mask, (qi - ki) < window)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, s, h, d)


# ---------------------------------------------------------------------------
# Paged decode attention (serve engine's block-table KV cache)
# ---------------------------------------------------------------------------


def ref_paged_attention(
    q: jax.Array,             # [B, H, D] one query token per request
    k_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled key blocks
    v_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled value blocks
    block_tables: jax.Array,  # [B, M] int32 page ids (pad slots may be any
                              # in-range id; they are masked by context_lens)
    context_lens: jax.Array,  # [B] int32 valid tokens per request (0 = slot
                              # inactive -> zero output)
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Gather K/V through per-request block tables and attend.

    The logical sequence of request b is the concatenation of its table's
    blocks; token t lives in block t // BS at offset t % BS.  Only the
    first ``context_lens[b]`` positions are real (ragged sequences), and
    the newest token (the query's own K/V row) is expected to already be
    written at position ``context_lens[b] - 1``.
    """
    kv = k_pages.shape[0]
    b, h, d = q.shape
    g = h // kv
    scale = d ** -0.5
    # [KV, B, M, BS, lanes] -> [KV, B, S, D] with S = M * BS
    keys = k_pages[:, block_tables, :, :d].reshape(kv, b, -1, d)
    vals = v_pages[:, block_tables, :, :d].reshape(kv, b, -1, d)
    qg = q.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,kbsd->bkgs", qg * scale, keys,
                        preferred_element_type=jnp.float32)
    pos = jnp.arange(keys.shape[2], dtype=jnp.int32)[None, :]     # [1, S]
    valid = pos < context_lens[:, None]
    if window is not None:
        q_pos = context_lens[:, None] - 1
        valid = jnp.logical_and(valid, (q_pos - pos) < window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,kbsd->bkgd", probs, vals)
    # Inactive slots (context_len 0) have no valid keys; zero them rather
    # than returning the softmax-of-NEG_INF uniform average.
    out = jnp.where(context_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, d)


def ref_paged_attention_varlen(
    q: jax.Array,             # [B, T, H, D] ragged query chunks, right-padded
    k_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled key blocks
    v_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled value blocks
    block_tables: jax.Array,  # [B, M] int32 page ids
    row_start: jax.Array,     # [B] int32 abs position of query row 0
    row_len: jax.Array,       # [B] int32 live rows per slot (0 = inactive)
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Ragged multi-token paged attention ground truth.

    Query ``t < row_len[b]`` of request ``b`` sits at absolute position
    ``row_start[b] + t`` and attends causally over positions ``<=`` its
    own (its K/V row — and those of the earlier rows in the chunk — are
    expected to already be written).  Padding rows ``t >= row_len[b]``
    and fully inactive slots (``row_len[b] == 0``) yield exactly zero.
    Decode, speculative verify and chunked prefill tiles are all this
    one shape with different ``(row_start, row_len)`` tables.
    """
    kv = k_pages.shape[0]
    b, t, h, d = q.shape
    g = h // kv
    scale = d ** -0.5
    row_start = row_start.astype(jnp.int32)
    row_len = row_len.astype(jnp.int32)
    keys = k_pages[:, block_tables, :, :d].reshape(kv, b, -1, d)
    vals = v_pages[:, block_tables, :, :d].reshape(kv, b, -1, d)
    qg = q.reshape(b, t, kv, g, d)
    scores = jnp.einsum("btkgd,kbsd->bkgts", qg * scale, keys,
                        preferred_element_type=jnp.float32)
    pos = jnp.arange(keys.shape[2], dtype=jnp.int32)[None, None, :]
    qpos = (row_start[:, None]
            + jnp.arange(t, dtype=jnp.int32)[None, :])[:, :, None]
    valid = pos <= qpos                                   # [B, T, S]
    if window is not None:
        valid = jnp.logical_and(valid, (qpos - pos) < window)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,kbsd->btkgd", probs, vals)
    row_live = (jnp.arange(t, dtype=jnp.int32)[None, :]
                < row_len[:, None])                       # [B, T]
    out = jnp.where(row_live[:, :, None, None, None], out, 0.0)
    return out.reshape(b, t, h, d)


def ref_paged_attention_multi(
    q: jax.Array,             # [B, T, H, D] consecutive query tokens
    k_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled key blocks
    v_pages: jax.Array,       # [KV, NB, BS, lanes >= D] pooled value blocks
    block_tables: jax.Array,  # [B, M] int32 page ids
    context_lens: jax.Array,  # [B] int32 rows live *including* the T chunk
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-token (speculative-verify) paged attention ground truth.

    The fixed-``T`` shape of :func:`ref_paged_attention_varlen`: query
    ``t`` of request ``b`` sits at absolute position ``context_lens[b]
    - T + t``.  ``T = 1`` reduces exactly to
    :func:`ref_paged_attention`.
    """
    t = q.shape[1]
    context_lens = context_lens.astype(jnp.int32)
    active = context_lens > 0
    row_start = jnp.where(active, context_lens - t, 0)
    row_len = jnp.where(active, t, 0)
    return ref_paged_attention_varlen(
        q, k_pages, v_pages, block_tables, row_start, row_len,
        window=window)


# ---------------------------------------------------------------------------
# Paged KV row write (serve engine's in-place pool append)
# ---------------------------------------------------------------------------


def pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of ``x`` to ``width``.

    The paged pool stores each K/V row lane-padded (see
    ``models.transformer.init_paged_cache``); rows are padded on their
    way in and queries alike, so the pad lanes only ever add exact
    zeros to a dot product."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def masked_inplace_update(
    arr: jax.Array,
    new: jax.Array,
    start: Tuple[jax.Array, ...],
    valid,   # bool scalar or broadcastable-to-`new` mask
) -> jax.Array:
    """dynamic_update_slice of ``new`` at ``start``, keeping old values
    where ``valid`` is False.

    This read-select-writeback idiom is the load-bearing in-place
    pattern of the paged pool: XLA updates a DUS on a dead operand in
    place (also inside scan bodies), so callers pay O(slice), not
    O(array).  Shared by the decode-row oracle below and the prefill
    tile writer (``models.transformer.write_prefill_to_pages``) so the
    invariant lives in one place.
    """
    old = jax.lax.dynamic_slice(arr, start, new.shape)
    return jax.lax.dynamic_update_slice(
        arr, jnp.where(valid, new, old), start)


def ref_paged_kv_write(
    k_pages: jax.Array,   # [L, KV, NB, BS, lanes] pooled key blocks
    v_pages: jax.Array,   # [L, KV, NB, BS, lanes] pooled value blocks
    k_rows: jax.Array,    # [B, KV, D] new key rows (one per slot)
    v_rows: jax.Array,    # [B, KV, D] new value rows
    page_idx: jax.Array,  # [B] int32 destination page per slot
    offset: jax.Array,    # [B] int32 destination row within the page
    active: jax.Array,    # [B] bool; False slots write nothing
    *,
    layer: int,
) -> Tuple[jax.Array, jax.Array]:
    """Write slot b's K/V row at ``[layer, :, page_idx[b], offset[b], :]``.

    Semantic ground truth for ``paged_kv_write_pallas``.  Deliberately a
    per-slot ``dynamic_update_slice`` chain rather than one vector
    scatter: XLA updates DUS-on-a-dead-operand in place (also inside
    scan bodies), so the reference serve path pays O(rows written) per
    step instead of O(pool) — the same flatness in ``num_blocks`` the
    Pallas kernel gets from DMA + buffer aliasing.  Inactive slots keep
    the old row (read-select-writeback), mirroring the kernel's skipped
    copy; distinct slots never share a destination (allocator invariant),
    so the chain order is immaterial.
    """
    b, kv, _ = k_rows.shape
    d = k_pages.shape[4]
    k_rows = pad_lanes(k_rows, d).astype(k_pages.dtype)
    v_rows = pad_lanes(v_rows, d).astype(v_pages.dtype)
    safe_page = jnp.where(active, page_idx, 0).astype(jnp.int32)
    offset = offset.astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for i in range(b):
        start = (jnp.asarray(layer, jnp.int32), zero, safe_page[i],
                 offset[i], zero)
        k_pages = masked_inplace_update(
            k_pages, k_rows[i].reshape(1, kv, 1, 1, d), start, active[i])
        v_pages = masked_inplace_update(
            v_pages, v_rows[i].reshape(1, kv, 1, 1, d), start, active[i])
    return k_pages, v_pages


# ---------------------------------------------------------------------------
# WKV6 linear-attention recurrence (rwkv6 time-mix)
# ---------------------------------------------------------------------------


def ref_wkv6(
    r: jax.Array,   # [B, S, H, K]
    k: jax.Array,   # [B, S, H, K]
    v: jax.Array,   # [B, S, H, V]
    w: jax.Array,   # [B, S, H, K]   decay in (0, 1)
    u: jax.Array,   # [H, K]         bonus
    state: Optional[jax.Array] = None,  # [B, H, K, V]
) -> Tuple[jax.Array, jax.Array]:
    from repro.models.rwkv6 import wkv6_scan

    return wkv6_scan(r, k, v, w, u, state)


# ---------------------------------------------------------------------------
# Fused per-token log-prob (the RLVR hot-spot)
# ---------------------------------------------------------------------------


def ref_logprobs_from_logits(
    logits: jax.Array,   # [N, V] (callers flatten [B, S, V])
    targets: jax.Array,  # [N] int32
) -> jax.Array:
    """log softmax gathered at targets, fp32 accumulation."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    tgt = jnp.take_along_axis(logits32, targets[:, None], axis=1)[:, 0]
    return tgt - lse


def ref_entropy_from_logits(logits: jax.Array) -> jax.Array:
    """Per-row softmax entropy, fp32."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.exp(lp) * lp, axis=-1)


# ---------------------------------------------------------------------------
# Selective-SSM (Mamba/S6) scan — hymba's SSM branch
# ---------------------------------------------------------------------------


def ref_ssm_scan(
    u: jax.Array,     # [B, S, I]
    dt: jax.Array,    # [B, S, I]
    b_t: jax.Array,   # [B, S, N]
    c_t: jax.Array,   # [B, S, N]
    a: jax.Array,     # [I, N]
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    from repro.models.ssm import _ssm_scan

    return _ssm_scan(u, dt, b_t, c_t, a, h0)
