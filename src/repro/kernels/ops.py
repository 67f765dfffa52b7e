"""jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

Selection policy (``KernelMode``):

* ``reference``         — pure-jnp oracles (CPU, autodiff, dry-run).
* ``pallas_interpret``  — Pallas kernels executed by the interpreter
                          (CPU validation of the TPU kernel bodies).
* ``pallas``            — compiled Pallas (real TPU).

The default comes from the platform: compiled ``pallas`` on a TPU and
``reference`` everywhere else, so a chip never runs an oracle or the
interpreter unless a caller asks for it with ``mode=``.  The wrappers keep
one signature regardless of backend so the models/trainers never branch.

**Mesh-sharded serve** (``mesh=`` on the paged ops): the paged KV pool
shards its ``NB`` (page) axis over the mesh's ``data`` axis, and every
request's pages live on exactly ONE shard (placement is host-side, in
``repro.serve``).  The sharded dispatchers wrap the same kernel bodies
in ``shard_map``:

* ``paged_attention`` / ``paged_attention_multi`` /
  ``paged_attention_varlen`` — block tables carry
  *shard-local* page ids; each device runs the kernel over its local
  pool with non-local slots masked to ``context_len 0`` (both the
  Pallas kernel and the oracle produce exact zeros there), then a
  ``psum`` over the data axis recombines the batch.  Since every slot
  is non-zero on exactly one shard, the sum is exact — the sharded path
  is bit-identical to the single-device one.
* ``paged_kv_write`` — each device applies the row scatter with the
  active mask restricted to its own slots; out_specs keep the pool
  sharded, and the in-place aliasing (Pallas ``input_output_aliases``
  / XLA DUS-on-dead-operand) survives because each shard updates only
  its local buffer.

``mesh=None`` (or a data axis of size 1) is the single-device special
case of the same code path, not a sibling implementation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as ref_mod
from repro.kernels.flash_attention_pallas import flash_attention
from repro.kernels.fused_logprob_pallas import logprobs_pallas
from repro.kernels.paged_attention_pallas import paged_attention as \
    paged_attention_pallas
from repro.kernels.paged_attention_pallas import paged_attention_multi as \
    paged_attention_multi_pallas
from repro.kernels.paged_attention_pallas import paged_attention_varlen as \
    paged_attention_varlen_pallas
from repro.kernels.paged_kv_write_pallas import paged_kv_write as \
    paged_kv_write_pallas
from repro.kernels.ssm_scan_pallas import ssm_scan_pallas
from repro.kernels.vtrace_pallas import vtrace_pallas
from repro.kernels.wkv6_pallas import wkv6_pallas

_VALID = ("reference", "pallas_interpret", "pallas")


def kernel_mode() -> str:
    """The platform's kernel path: compiled Pallas on TPU, else oracles."""
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _pallas_kwargs(mode: Optional[str]) -> Optional[dict]:
    mode = mode or kernel_mode()
    if mode not in _VALID:
        raise ValueError(f"kernel mode {mode!r}; want one of {_VALID}")
    if mode == "reference":
        return None
    return {"interpret": mode == "pallas_interpret"}


def mesh_data_size(mesh, axis_name: str = "data") -> int:
    """Size of the mesh's serve-sharding axis (1 = unsharded/no mesh)."""
    if mesh is None or axis_name not in mesh.shape:
        return 1
    return int(mesh.shape[axis_name])


def _sharded(mesh, axis_name: str) -> bool:
    return mesh_data_size(mesh, axis_name) > 1


def vtrace(
    log_ratios, values, bootstrap_value, rewards, discounts,
    *, rho_bar: float = 1.0, c_bar: float = 1.0, lam: float = 1.0,
    mode: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_vtrace(
            log_ratios, values, bootstrap_value, rewards, discounts,
            rho_bar=rho_bar, c_bar=c_bar, lam=lam)
    return vtrace_pallas(
        log_ratios, values, bootstrap_value, rewards, discounts,
        rho_bar=rho_bar, c_bar=c_bar, lam=lam, **kw)


def attention(
    q, k, v, *, window: Optional[int] = None, causal: bool = True,
    mode: Optional[str] = None,
):
    kw = _pallas_kwargs(mode)
    if kw is None or not causal:
        return ref_mod.ref_attention(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, window=window, **kw)


def _paged_attention_local(
    q, k_pages, v_pages, block_tables, context_lens, *, layer, window, mode,
):
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_paged_attention(
            q, k_pages[layer], v_pages[layer], block_tables, context_lens,
            window=window)
    return paged_attention_pallas(
        q, k_pages, v_pages, block_tables, context_lens,
        layer=layer, window=window, **kw)


def paged_attention(
    q, k_pages, v_pages, block_tables, context_lens,
    *, layer, window: Optional[int] = None, mode: Optional[str] = None,
    mesh=None, slot_shard=None, axis_name: str = "data",
):
    """Decode attention over layer ``layer`` of a block-table paged KV
    pool (``[L, KV, NB, BS, lanes]``, read in place; q ``[B, H, D]``).

    With a ``mesh``, ``k_pages``/``v_pages`` are NB-sharded over
    ``axis_name``, ``block_tables`` hold shard-local page ids, and
    ``slot_shard[b]`` names the shard owning slot ``b``'s pages: each
    device attends over its local pool with foreign slots masked to
    context 0 (exact zero output) and a ``psum`` recombines the batch.
    """
    layer = jnp.asarray(layer, jnp.int32)
    if not _sharded(mesh, axis_name):
        return _paged_attention_local(
            q, k_pages, v_pages, block_tables, context_lens,
            layer=layer, window=window, mode=mode)

    def body(q, kp, vp, tbl, lens, ly, ss):
        idx = jax.lax.axis_index(axis_name)
        local_lens = jnp.where(ss == idx, lens, 0).astype(jnp.int32)
        out = _paged_attention_local(
            q, kp, vp, tbl, local_lens, layer=ly, window=window, mode=mode)
        return jax.lax.psum(out, axis_name)

    pool = P(None, None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), pool, pool, P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q, k_pages, v_pages, block_tables, context_lens, layer,
      slot_shard.astype(jnp.int32))


def _paged_attention_multi_local(
    q, k_pages, v_pages, block_tables, context_lens, *, layer, window, mode,
):
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_paged_attention_multi(
            q, k_pages[layer], v_pages[layer], block_tables, context_lens,
            window=window)
    return paged_attention_multi_pallas(
        q, k_pages, v_pages, block_tables, context_lens,
        layer=layer, window=window, **kw)


def paged_attention_multi(
    q, k_pages, v_pages, block_tables, context_lens,
    *, layer, window: Optional[int] = None, mode: Optional[str] = None,
    mesh=None, slot_shard=None, axis_name: str = "data",
):
    """Multi-token verify attention over layer ``layer`` of the paged
    pool ([B, T, H, D]): query ``t`` sits at absolute position
    ``context_lens - T + t`` and attends causally — T drafted tokens
    scored in one dispatch.  Pool layout and mesh semantics match
    :func:`paged_attention` (local tables + psum)."""
    layer = jnp.asarray(layer, jnp.int32)
    if not _sharded(mesh, axis_name):
        return _paged_attention_multi_local(
            q, k_pages, v_pages, block_tables, context_lens,
            layer=layer, window=window, mode=mode)

    def body(q, kp, vp, tbl, lens, ly, ss):
        idx = jax.lax.axis_index(axis_name)
        local_lens = jnp.where(ss == idx, lens, 0).astype(jnp.int32)
        out = _paged_attention_multi_local(
            q, kp, vp, tbl, local_lens, layer=ly, window=window, mode=mode)
        return jax.lax.psum(out, axis_name)

    pool = P(None, None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), pool, pool, P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q, k_pages, v_pages, block_tables, context_lens, layer,
      slot_shard.astype(jnp.int32))


def _paged_attention_varlen_local(
    q, k_pages, v_pages, block_tables, row_start, row_len,
    *, layer, window, mode,
):
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_paged_attention_varlen(
            q, k_pages[layer], v_pages[layer], block_tables, row_start,
            row_len, window=window)
    return paged_attention_varlen_pallas(
        q, k_pages, v_pages, block_tables, row_start, row_len,
        layer=layer, window=window, **kw)


def paged_attention_varlen(
    q, k_pages, v_pages, block_tables, row_start, row_len,
    *, layer, window: Optional[int] = None, mode: Optional[str] = None,
    mesh=None, slot_shard=None, axis_name: str = "data",
):
    """Ragged multi-token attention over layer ``layer`` of the paged
    pool ([B, T, H, D]): query ``t < row_len[b]`` sits at absolute
    position ``row_start[b] + t`` and attends causally; padding rows and
    ``row_len == 0`` slots come back exactly zero.  Decode, speculative
    verify and chunked prefill tiles are call shapes of this one kernel.
    Pool layout and mesh semantics match :func:`paged_attention` —
    foreign slots are masked to ``row_len 0`` (exact zero) and a
    ``psum`` recombines the batch."""
    layer = jnp.asarray(layer, jnp.int32)
    if not _sharded(mesh, axis_name):
        return _paged_attention_varlen_local(
            q, k_pages, v_pages, block_tables, row_start, row_len,
            layer=layer, window=window, mode=mode)

    def body(q, kp, vp, tbl, rs, rl, ly, ss):
        idx = jax.lax.axis_index(axis_name)
        local_len = jnp.where(ss == idx, rl, 0).astype(jnp.int32)
        out = _paged_attention_varlen_local(
            q, kp, vp, tbl, rs, local_len, layer=ly, window=window,
            mode=mode)
        return jax.lax.psum(out, axis_name)

    pool = P(None, None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), pool, pool, P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q, k_pages, v_pages, block_tables, row_start, row_len, layer,
      slot_shard.astype(jnp.int32))


def _paged_kv_write_local(
    k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
    *, layer, mode,
):
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_paged_kv_write(
            k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
            layer=layer)
    return paged_kv_write_pallas(
        k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
        layer=layer, **kw)


def paged_kv_write(
    k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
    *, layer: int, mode: Optional[str] = None,
    mesh=None, slot_shard=None, axis_name: str = "data",
) -> Tuple[jax.Array, jax.Array]:
    """In-place scatter of one decode step's K/V rows into the pool.

    Returns the updated ``(k_pages, v_pages)``; both paths update the
    buffer in place when the caller's pools are donated/dead (the Pallas
    route via ``input_output_aliases``, the reference route via XLA's
    in-place dynamic_update_slice), so per-step cost is O(rows), not
    O(pool).

    With a ``mesh`` the pools are NB-sharded over ``axis_name``,
    ``page_idx`` is shard-local, and each device narrows ``active`` to
    its own slots (``slot_shard``), so a slot's row lands only on its
    home shard; out_specs keep the pool sharded and the per-shard
    buffers update in place exactly as on one device.
    """
    if not _sharded(mesh, axis_name):
        return _paged_kv_write_local(
            k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
            layer=layer, mode=mode)

    def body(kp, vp, kr, vr, pidx, off, act, ss):
        idx = jax.lax.axis_index(axis_name)
        local_act = jnp.logical_and(act, ss == idx)
        return _paged_kv_write_local(
            kp, vp, kr, vr, pidx, off, local_act, layer=layer, mode=mode)

    pool = P(None, None, axis_name, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pool, pool, P(), P(), P(), P(), P(), P()),
        out_specs=(pool, pool), check_vma=False,
    )(k_pages, v_pages, k_rows, v_rows, page_idx, offset, active,
      slot_shard.astype(jnp.int32))


def wkv6(
    r, k, v, w, u, state=None, *, mode: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_wkv6(r, k, v, w, u, state)
    return wkv6_pallas(r, k, v, w, u, state, **kw)


def ssm_scan(
    u, dt, b_t, c_t, a, h0=None, *, mode: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    kw = _pallas_kwargs(mode)
    if kw is None:
        return ref_mod.ref_ssm_scan(u, dt, b_t, c_t, a, h0)
    return ssm_scan_pallas(u, dt, b_t, c_t, a, h0, **kw)


def logprobs_from_logits(
    logits: jax.Array,    # [..., V]
    targets: jax.Array,   # [...]
    *, mode: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logp, entropy), shapes = targets.shape, fp32."""
    lead = logits.shape[:-1]
    vocab = logits.shape[-1]
    flat_logits = logits.reshape(-1, vocab)
    flat_targets = targets.reshape(-1)
    kw = _pallas_kwargs(mode)
    if kw is None:
        logp = ref_mod.ref_logprobs_from_logits(flat_logits, flat_targets)
        ent = ref_mod.ref_entropy_from_logits(flat_logits)
    else:
        logp, ent = logprobs_pallas(flat_logits, flat_targets, **kw)
    return logp.reshape(lead), ent.reshape(lead)
