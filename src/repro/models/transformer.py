"""Decoder-only policy backbone: dense / MoE / hybrid / VLM / attn-free.

One assembly covers eight of the ten assigned architectures (whisper's
encoder-decoder lives in ``repro.models.encdec``; the Gaussian MLP policy
for classic RL in ``repro.models.mlp_policy``).

Design points:

* **scan over layers** — layer parameters are stacked with a leading
  ``[L]`` axis and the block is a single ``jax.lax.scan`` body, keeping
  HLO size O(1) in depth (48-61-layer archs compile quickly and the
  dry-run stays tractable).
* **heterogeneous layers without unrolling** — per-layer differences
  (gemma3's 5 local : 1 global window pattern, hymba's 3 global layers)
  are expressed as a traced ``[L]`` window array (jnp.inf = global), so
  the mask math is data-dependent and the scan body stays uniform.
* **KV cache as scan ys/xs** — caches are ``[L, ...]`` stacked pytrees
  threaded through the same scan.
* **value head** — per-token critic for VACO/PPO RLVR (Alg. 1's V_phi).

The forward returns per-token logits; per-token log-probs for the RL
losses are computed by ``repro.kernels.ops.logprobs_from_logits`` (fused
Pallas path or jnp reference).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_rope,
    dense_apply,
    dense_init,
    embedding_apply,
    embedding_attend,
    embedding_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    softcap,
)


class ModelOutput(NamedTuple):
    logits: jax.Array            # [B, S, V]
    value: Optional[jax.Array]   # [B, S] or None
    cache: Any                   # updated cache pytree (or None)
    aux_loss: jax.Array          # router load-balance etc.


def scan_layers(body, carry, xs, unroll: bool = False,
                remat: bool = False):
    """jax.lax.scan over stacked layers, or a Python unroll.

    ``remat=True`` wraps the body in jax.checkpoint (per-layer activation
    rematerialization) — the standard training memory policy: backward
    recomputes each layer instead of storing its internals, bounding
    activation memory to the inter-layer residual stream.

    The unrolled form exists for the dry-run's cost extrapolation: XLA's
    cost_analysis counts a while-loop body once regardless of trip count,
    so exact per-layer FLOP/byte/collective numbers come from compiling
    shallow *unrolled* variants (launch/dryrun.py).
    """
    if remat:
        body = jax.checkpoint(body)
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys_all = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys_all.append(y)
    if not ys_all or not jax.tree.leaves(ys_all[0]):
        return carry, ys_all[0] if ys_all else None
    ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys_all)
    return carry, ys


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "norm2": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.attn_free:
        p["rwkv"] = rwkv_mod.rwkv6_init(ks[0], cfg.d_model, cfg.d_ff, dtype)
        return p
    p["attn"] = attn.attn_init(
        ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        qkv_bias=cfg.qkv_bias, dtype=dtype,
    )
    if cfg.hybrid_attn_ssm:
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg.d_model, cfg.ssm, dtype)
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(
            ks[2], cfg.d_model, cfg.moe, cfg.activation, dtype
        )
    else:
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.activation,
                            dtype)
    return p


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    k_emb, k_layers, k_head, k_val, k_vis = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(layer_keys)
    p: Dict[str, Any] = {
        "embed": embedding_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.value_head:
        p["value_head"] = dense_init(k_val, cfg.d_model, 1, dtype, bias=True)
    if cfg.vision_prefix_len > 0:
        # Projector from the (stubbed) vision tower embedding dim.
        p["vision_proj"] = dense_init(k_vis, vision_stub_dim(cfg),
                                      cfg.d_model, dtype)
    return p


def vision_stub_dim(cfg: ModelConfig) -> int:
    """Embedding dim of the stubbed modality frontend (SigLIP-so400m)."""
    return 1152


def layer_windows(cfg: ModelConfig, decode_cache_len: Optional[int] = None
                  ) -> jax.Array:
    """[L] float32 window sizes; jnp.inf marks global layers."""
    ws = []
    for l in range(cfg.n_layers):
        w = cfg.window_for_layer(l)
        ws.append(jnp.inf if w is None else float(w))
    return jnp.asarray(ws, jnp.float32)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> Dict:
    """Allocate the decode cache for `batch` streams of up to `max_len`."""
    c: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    L = cfg.n_layers
    if cfg.attn_free:
        h = cfg.d_model // rwkv_mod.HEAD_DIM
        c["wkv"] = jnp.zeros((L, batch, h, rwkv_mod.HEAD_DIM,
                              rwkv_mod.HEAD_DIM), jnp.float32)
        c["shift_tm"] = jnp.zeros((L, batch, 1, cfg.d_model), dtype)
        c["shift_cm"] = jnp.zeros((L, batch, 1, cfg.d_model), dtype)
        return c
    c["k"] = jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype)
    c["v"] = jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype)
    if cfg.hybrid_attn_ssm:
        inner = cfg.ssm.expand * cfg.d_model
        c["ssm"] = jnp.zeros((L, batch, inner, cfg.ssm.state_dim),
                             jnp.float32)
        c["conv"] = jnp.zeros((L, batch, cfg.ssm.conv_width - 1, inner),
                              dtype)
    return c


def paged_arch_unsupported(cfg: ModelConfig) -> Optional[str]:
    """Why this config cannot run the paged decode path (None = it can).

    The paged KV pool covers the standard attention archs — including
    gemma3-style per-layer sliding windows, which the paged kernels
    mask natively (the hoisted layer loop passes each layer's static
    window).  Recurrent state (rwkv/ssm) has no per-position rows to
    page; prefix-LM/VLM prefixes are still serve/ follow-ons.
    """
    if cfg.attn_free:
        return "attn-free (rwkv) archs keep recurrent state, not KV rows"
    if cfg.hybrid_attn_ssm:
        return "hybrid attn+ssm archs carry unpaged ssm/conv state"
    if cfg.encoder_layers > 0:
        return "encoder-decoder cross-attention cache is not paged"
    if cfg.vision_prefix_len > 0:
        return "vision prefix rows are not paged"
    return None


POOL_LANES = 128


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.float32) -> Dict:
    """Allocate the pooled block KV cache shared by all requests.

    Layout ``[L, KV, NB, BS, lanes]`` (kv-head major within a layer) so
    the paged-attention kernel streams one ``[BS, lanes]`` tile per page
    visit.  ``lanes`` is ``head_dim`` rounded up to the TPU's 128-lane
    width: the chip stores a narrower trailing dim padded to 128 anyway,
    and its DMA engine moves only lane-aligned rows, which the in-place
    row write needs.  Rows are zero-padded on the way in.  Ownership of
    pages lives host-side in ``repro.serve.paged_cache``.
    """
    reason = paged_arch_unsupported(cfg)
    if reason is not None:
        raise ValueError(f"{cfg.name}: paged decode unsupported: {reason}")
    shape = (cfg.n_layers, cfg.n_kv_heads, num_blocks, block_size,
             -(-cfg.head_dim // POOL_LANES) * POOL_LANES)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def _paged_layer_tail(cfg: ModelConfig, lp: Dict, x: jax.Array,
                      attn_out: jax.Array) -> jax.Array:
    """Shared post-attention half of a paged decode layer ([B, S, ...])."""
    b = x.shape[0]
    attn_out = attn_out.reshape(b, -1, cfg.n_heads * cfg.head_dim)
    x = x + dense_apply(lp["attn"]["wo"], attn_out)
    h = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
    if cfg.moe is not None:
        mlp_out, _ = moe_mod.moe_apply(
            lp["moe"], h, cfg.moe, cfg.activation, group_size=h.shape[0],
        )
    else:
        mlp_out = mlp_apply(lp["mlp"], h, cfg.activation)
    return x + mlp_out


def _paged_qkv(cfg: ModelConfig, lp: Dict, x: jax.Array,
               positions: jax.Array) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """Projections + rope for one paged decode layer ([B, S, ...]);
    ``positions`` is [B, S] absolute rope positions."""
    h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
    q = attn._split_heads(dense_apply(lp["attn"]["wq"], h), cfg.n_heads)
    k_new = attn._split_heads(
        dense_apply(lp["attn"]["wk"], h), cfg.n_kv_heads)
    v_new = attn._split_heads(
        dense_apply(lp["attn"]["wv"], h), cfg.n_kv_heads)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)
    return q, k_new, v_new


def _write_rows(k_pages, v_pages, k_new, v_new, page_idx, offset, write_ok,
                *, layer, kernel_mode, mesh, slot_shard):
    """Write a ``[B, T]`` chunk of K/V rows into layer ``layer`` with one
    row-write dispatch: the chunk is flattened to ``B * T`` slots (row
    ``(b, t)`` keeps slot ``b``'s home shard under a mesh)."""
    from repro.kernels import ops as kops

    b, t = page_idx.shape
    if slot_shard is not None:
        slot_shard = jnp.repeat(slot_shard, t)
    return kops.paged_kv_write(
        k_pages, v_pages,
        k_new.reshape((b * t,) + k_new.shape[2:]),
        v_new.reshape((b * t,) + v_new.shape[2:]),
        page_idx.reshape(-1), offset.reshape(-1), write_ok.reshape(-1),
        layer=layer, mode=kernel_mode, mesh=mesh, slot_shard=slot_shard)


def _paged_head_full(params: Dict, cfg: ModelConfig, x: jax.Array
                     ) -> ModelOutput:
    """Final norm + readout over every query position ([B, S, V])."""
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = embedding_attend(params["embed"], x)
    else:
        logits = dense_apply(params["lm_head"], x)
    logits = softcap(logits, cfg.logit_softcap)
    value = None
    if cfg.value_head:
        value = dense_apply(params["value_head"], x)[..., 0]
    return ModelOutput(
        logits=logits, value=value,
        cache=None, aux_loss=jnp.zeros((), jnp.float32),
    )


def _paged_head(params: Dict, cfg: ModelConfig, x: jax.Array
                ) -> ModelOutput:
    out = _paged_head_full(params, cfg, x)
    return out._replace(
        logits=out.logits[:, 0],
        value=None if out.value is None else out.value[:, 0],
    )


def decode_step_paged(
    params: Dict,
    cfg: ModelConfig,
    token: jax.Array,         # [B] current token ids (one per slot)
    pages: Dict,              # {"k_pages","v_pages"} [L, KV, NB, BS, Dh]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads in-range)
    pos: jax.Array,           # [B] int32 tokens already cached per slot
    active: jax.Array,        # [B] bool; inactive slots write/read nothing
    *,
    kernel_mode: Optional[str] = None,
    mesh=None,
    slot_shard: Optional[jax.Array] = None,  # [B] int32 home shard per slot
) -> Tuple[ModelOutput, Dict]:
    """One decode step for a batch of *independent ragged* requests.

    Unlike :func:`decode_step`, slots need not be in lockstep: each slot
    writes its new K/V row at its own ``pos`` through its own block
    table, and attends over exactly its ``pos + 1`` live positions.  The
    incoming token's row is written first (so it attends to itself),
    matching the dense path's validity rule ``kv_pos <= position``.

    The layer loop is *hoisted* (a Python unroll, HLO O(L)) rather than
    a ``lax.scan`` so the pool never rides a scan as a carried value:
    each layer's row append is an in-place-able op
    (``kernels.ops.paged_kv_write`` — aliased Pallas DMA scatter, or its
    dynamic-update-slice oracle), and each layer's attention reads the
    whole pool in place at a traced layer index
    (``kernels.ops.paged_attention``), so no layer's pages are ever
    sliced out of the pool.  That keeps per-step cost O(rows written
    and pages read), independent of ``num_blocks``.  The data
    dependency through each layer's tail orders layer ``l``'s read
    before layer ``l + 1``'s in-place write.  The scan-carried
    formulation made XLA rewrite the whole ``[L, KV, NB, BS, Dh]`` pool
    every step (~2.7x slower at 128 vs 16 blocks at equal work); it is
    kept as :func:`decode_step_paged_carried` as the equivalence oracle
    for this path.  Serve archs run reduced depths, so the O(L) HLO is
    cheap; the O(1)-HLO training forward is untouched.

    The hoisted loop also gives each layer its *static* sliding window
    (``cfg.window_for_layer``), so gemma3-style local:global patterns
    run the paged path natively — the kernels mask reads outside the
    window; rows behind it are never read, which is what lets the
    scheduler's window reclamation (all-windowed archs) release whole
    pages behind the widest window mid-flight.

    With ``mesh``/``slot_shard`` the pool is NB-sharded over the mesh's
    ``data`` axis and block tables carry shard-local page ids; the
    kernels dispatch through ``shard_map`` (see ``kernels.ops``) and
    this function's math is bit-identical to the single-device case.
    """
    from repro.kernels import ops as kops

    block_size = pages["k_pages"].shape[3]
    x = embedding_apply(params["embed"], token[:, None])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    safe_pos = jnp.maximum(pos, 0)
    page_idx = jnp.take_along_axis(
        block_tables, (safe_pos // block_size)[:, None], axis=1)[:, 0]
    offset = safe_pos % block_size
    context_lens = jnp.where(active, safe_pos + 1, 0).astype(jnp.int32)

    k_pages, v_pages = pages["k_pages"], pages["v_pages"]
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k_new, v_new = _paged_qkv(cfg, lp, x, safe_pos[:, None])
        k_pages, v_pages = kops.paged_kv_write(
            k_pages, v_pages, k_new[:, 0], v_new[:, 0],
            page_idx, offset, active, layer=layer, mode=kernel_mode,
            mesh=mesh, slot_shard=slot_shard,
        )
        attn_out = kops.paged_attention(
            q[:, 0], k_pages, v_pages, block_tables, context_lens,
            layer=layer, window=cfg.window_for_layer(layer),
            mode=kernel_mode, mesh=mesh, slot_shard=slot_shard,
        )
        x = _paged_layer_tail(cfg, lp, x, attn_out)

    out = _paged_head(params, cfg, x)
    return out, {"k_pages": k_pages, "v_pages": v_pages}


def decode_step_paged_multi(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, T] consecutive tokens per slot
    pages: Dict,              # {"k_pages","v_pages"} [L, KV, NB, BS, Dh]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads in-range)
    pos: jax.Array,           # [B] int32 tokens already cached per slot
    active: jax.Array,        # [B] bool; inactive slots write/read nothing
    write_cap: jax.Array,     # [B] int32 rows this slot owns pages for
    *,
    kernel_mode: Optional[str] = None,
    mesh=None,
    slot_shard: Optional[jax.Array] = None,  # [B] int32 home shard per slot
) -> Tuple[ModelOutput, Dict]:
    """Score ``T`` consecutive tokens per slot in one dispatch (the
    speculative-decode verifier).

    Token ``t`` of slot ``b`` sits at absolute position ``pos[b] + t``:
    its K/V row is written first (at that position, through the slot's
    block table) and it attends causally over its own prefix — exactly
    ``T`` sequential :func:`decode_step_paged` calls fused into one
    launch, with the attention read done by the multi-query paged
    kernel (``kernels.ops.paged_attention_multi``) instead of ``T``
    single-query ones.  ``T = 1`` is the plain decode step.

    ``write_cap[b]`` bounds the rows slot ``b`` may write (its allocated
    pages): positions ``>= write_cap`` *drop* their K/V write instead of
    landing in the table's in-range pad pages (page 0 belongs to someone
    else).  Logits at such positions are garbage — callers never emit
    from them (the scheduler allocates pages for every row that can
    influence an emitted token; only past-end-of-budget draft positions
    are ever uncovered).

    Rollback after partial acceptance is *pure position arithmetic*: the
    caller rewinds ``pos`` to the accepted prefix and the rejected rows
    are simply overwritten by the next chunk — no page copies, no
    retraction of emitted tokens, preemption-safe (a preempted request
    re-prefills prompt + emitted tokens exactly as before).
    """
    from repro.kernels import ops as kops

    b, t = tokens.shape
    block_size = pages["k_pages"].shape[3]
    x = embedding_apply(params["embed"], tokens)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    safe_pos = jnp.maximum(pos, 0)
    positions = safe_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    page_idx = jnp.take_along_axis(
        block_tables, positions // block_size, axis=1)       # [B, T]
    offset = positions % block_size
    write_ok = jnp.logical_and(
        active[:, None], positions < write_cap[:, None])     # [B, T]
    context_lens = jnp.where(active, safe_pos + t, 0).astype(jnp.int32)

    k_pages, v_pages = pages["k_pages"], pages["v_pages"]
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k_new, v_new = _paged_qkv(cfg, lp, x, positions)
        k_pages, v_pages = _write_rows(
            k_pages, v_pages, k_new, v_new, page_idx, offset, write_ok,
            layer=layer, kernel_mode=kernel_mode, mesh=mesh,
            slot_shard=slot_shard)
        attn_out = kops.paged_attention_multi(
            q, k_pages, v_pages, block_tables, context_lens,
            layer=layer, window=cfg.window_for_layer(layer),
            mode=kernel_mode, mesh=mesh, slot_shard=slot_shard,
        )
        x = _paged_layer_tail(cfg, lp, x, attn_out)

    out = _paged_head_full(params, cfg, x)
    return out, {"k_pages": k_pages, "v_pages": v_pages}


def decode_step_paged_varlen(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, T] ragged token chunks, right-padded
    pages: Dict,              # {"k_pages","v_pages"} [L, KV, NB, BS, Dh]
    block_tables: jax.Array,  # [B, M] int32 page ids (pads in-range)
    row_start: jax.Array,     # [B] int32 rows already cached per slot
    row_len: jax.Array,       # [B] int32 live tokens per slot (0 = idle)
    write_cap: jax.Array,     # [B] int32 rows this slot owns pages for
    *,
    kernel_mode: Optional[str] = None,
    mesh=None,
    slot_shard: Optional[jax.Array] = None,  # [B] int32 home shard per slot
) -> Tuple[ModelOutput, Dict]:
    """Score a *ragged* chunk of consecutive tokens per slot in one
    dispatch — the varlen generalization of :func:`decode_step_paged_multi`
    that unifies chunked prefill, decode and speculative verify.

    Token ``t < row_len[b]`` of slot ``b`` sits at absolute position
    ``row_start[b] + t``: its K/V row is written (through the slot's
    block table, dropped past ``write_cap``) and it attends causally
    over its own prefix via the varlen paged kernel.  Padding rows
    (``t >= row_len[b]``) write nothing and their logits are garbage —
    callers only read rows ``< row_len``.  ``row_len == 1`` everywhere
    is the plain decode step; ``row_len == T`` everywhere is the
    verifier; mixed values interleave prefill tiles with decode rows in
    one launch.  Layer-loop hoisting, in-place page writes, per-layer
    windows and mesh semantics are identical to the multi path.
    """
    from repro.kernels import ops as kops

    b, t = tokens.shape
    block_size = pages["k_pages"].shape[3]
    x = embedding_apply(params["embed"], tokens)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    safe_start = jnp.maximum(row_start, 0)
    row_len = row_len.astype(jnp.int32)
    positions = safe_start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    page_idx = jnp.take_along_axis(
        block_tables, positions // block_size, axis=1)       # [B, T]
    offset = positions % block_size
    live = jnp.arange(t, dtype=jnp.int32)[None, :] < row_len[:, None]
    write_ok = jnp.logical_and(
        live, positions < write_cap[:, None])                # [B, T]

    k_pages, v_pages = pages["k_pages"], pages["v_pages"]
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k_new, v_new = _paged_qkv(cfg, lp, x, positions)
        k_pages, v_pages = _write_rows(
            k_pages, v_pages, k_new, v_new, page_idx, offset, write_ok,
            layer=layer, kernel_mode=kernel_mode, mesh=mesh,
            slot_shard=slot_shard)
        attn_out = kops.paged_attention_varlen(
            q, k_pages, v_pages, block_tables, safe_start, row_len,
            layer=layer, window=cfg.window_for_layer(layer),
            mode=kernel_mode, mesh=mesh, slot_shard=slot_shard,
        )
        x = _paged_layer_tail(cfg, lp, x, attn_out)

    out = _paged_head_full(params, cfg, x)
    return out, {"k_pages": k_pages, "v_pages": v_pages}


def decode_step_paged_carried(
    params: Dict,
    cfg: ModelConfig,
    token: jax.Array,
    pages: Dict,
    block_tables: jax.Array,
    pos: jax.Array,
    active: jax.Array,
    *,
    kernel_mode: Optional[str] = None,
    mesh=None,
    slot_shard: Optional[jax.Array] = None,
) -> Tuple[ModelOutput, Dict]:
    """Legacy paged decode step: pool carried through the layer scan.

    Semantically identical to :func:`decode_step_paged` — tests assert
    greedy *token* equality bit-for-bit and ulp-level logit/pool
    closeness (scan-fused vs standalone ops round the last bit
    differently) — but O(pool) per step: the pages ride the scan as
    xs/ys, so every step re-materializes the full ``[L, ...]`` pool.
    Kept as the oracle for the aliased path; not used by the engine.
    Uniform-scan body: no per-layer windows (use the hoisted path for
    sliding-window archs) and no mesh dispatch.
    """
    from repro.kernels import ops as kops
    from repro.kernels.ref import pad_lanes

    if cfg.sliding_window is not None:
        raise ValueError(
            "decode_step_paged_carried has a uniform scan body and "
            "cannot carry per-layer sliding windows; use "
            "decode_step_paged")
    if mesh is not None and kops.mesh_data_size(mesh) > 1:
        raise ValueError(
            "decode_step_paged_carried is a single-device test oracle; "
            "mesh dispatch lives on decode_step_paged")

    num_blocks = pages["k_pages"].shape[2]
    block_size = pages["k_pages"].shape[3]
    x = embedding_apply(params["embed"], token[:, None])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    safe_pos = jnp.maximum(pos, 0)
    # Out-of-pool page index + scatter mode="drop" turns inactive slots'
    # writes into no-ops without branching.
    page_idx = jnp.take_along_axis(
        block_tables, (safe_pos // block_size)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(active, page_idx, num_blocks)
    offset = safe_pos % block_size
    context_lens = jnp.where(active, safe_pos + 1, 0).astype(jnp.int32)

    def layer_step(x, xs):
        lp, k_pages, v_pages = xs
        q, k_new, v_new = _paged_qkv(cfg, lp, x, safe_pos[:, None])
        # [B, 1, KV, Dh] -> [KV, B, Dh] rows, scattered per slot.
        lanes = k_pages.shape[-1]
        k_rows = pad_lanes(k_new[:, 0], lanes).transpose(1, 0, 2)
        v_rows = pad_lanes(v_new[:, 0], lanes).transpose(1, 0, 2)
        k_pages = k_pages.at[:, page_idx, offset, :].set(
            k_rows.astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[:, page_idx, offset, :].set(
            v_rows.astype(v_pages.dtype), mode="drop")
        # The kernel reads a whole pool by layer: this scan step's
        # per-layer pool is a pool of one layer.
        attn_out = kops.paged_attention(
            q[:, 0], k_pages[None], v_pages[None], block_tables,
            context_lens, layer=0, mode=kernel_mode,
        )
        x = _paged_layer_tail(cfg, lp, x, attn_out)
        return x, {"k_pages": k_pages, "v_pages": v_pages}

    x, new_pages = scan_layers(
        layer_step, x,
        (params["layers"], pages["k_pages"], pages["v_pages"]),
    )
    out = _paged_head(params, cfg, x)
    return out, new_pages


def write_prefill_to_pages(
    cache_k: jax.Array,       # [L, 1, P, KV, Dh] dense prefill rows
    cache_v: jax.Array,
    pages: Dict,
    blocks: jax.Array,        # [M] int32 page ids owned by this request
    prompt_len: jax.Array,    # scalar int32: rows >= prompt_len are dropped
) -> Dict:
    """Scatter one request's prefill K/V rows into its allocated pages.

    Structured as one ``dynamic_update_slice`` per table slot (a static
    count of page-sized tiles) rather than a row scatter: with the pool
    donated, XLA updates the tiles in place, so a prefill costs O(rows
    written), not O(pool).  Tiles past ``prompt_len`` — and the pad
    slots of ``blocks`` (page 0) — write their *old* contents back
    (read-select-writeback), i.e. drop semantics without touching the
    rest of the pool.
    """
    k_pages, v_pages = pages["k_pages"], pages["v_pages"]
    block_size = k_pages.shape[3]
    p = cache_k.shape[2]
    n_tiles = -(-p // block_size)
    pad = n_tiles * block_size - p
    from repro.kernels.ref import masked_inplace_update, pad_lanes

    # [L, 1, P, KV, Dh] -> [L, KV, P(+pad), lanes]
    lanes = k_pages.shape[4]
    k_rows = pad_lanes(cache_k[:, 0].transpose(0, 2, 1, 3), lanes)
    v_rows = pad_lanes(cache_v[:, 0].transpose(0, 2, 1, 3), lanes)
    k_rows = k_rows.astype(k_pages.dtype)
    v_rows = v_rows.astype(v_pages.dtype)
    if pad:
        k_rows = jnp.pad(k_rows, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_rows = jnp.pad(v_rows, ((0, 0), (0, 0), (0, pad), (0, 0)))

    zero = jnp.zeros((), jnp.int32)
    for j in range(n_tiles):
        rows = j * block_size + jnp.arange(block_size, dtype=jnp.int32)
        valid = (rows < prompt_len)[None, None, None, :, None]
        start = (zero, zero, blocks[j].astype(jnp.int32), zero, zero)
        new_k = k_rows[:, :, None, j * block_size:(j + 1) * block_size, :]
        new_v = v_rows[:, :, None, j * block_size:(j + 1) * block_size, :]
        k_pages = masked_inplace_update(k_pages, new_k, start, valid)
        v_pages = masked_inplace_update(v_pages, new_v, start, valid)
    return {"k_pages": k_pages, "v_pages": v_pages}


def write_prefill_batch_to_pages(
    cache_k: jax.Array,       # [L, N, P, KV, Dh] dense prefill rows
    cache_v: jax.Array,
    pages: Dict,
    blocks: jax.Array,        # [N, M] int32 page ids (shard-local w/ mesh)
    prompt_lens: jax.Array,   # [N] int32 rows to write per request
    home_shard: Optional[jax.Array] = None,   # [N] int32 (mesh only)
    *,
    mesh=None,
    axis_name: str = "data",
) -> Dict:
    """Scatter a *group* of prefilled requests into their pages.

    The single-device path is exactly ``N`` calls to
    :func:`write_prefill_to_pages` (the bit-pinned baseline).  With a
    ``mesh`` the pool is NB-sharded over ``axis_name`` and each request
    writes only on its ``home_shard``: inside ``shard_map`` foreign
    requests get ``prompt_len 0`` (every tile's validity mask is then
    all-False, i.e. read-select-writeback keeps the local pool rows
    untouched), so the per-shard buffers still update in place.
    """
    n = cache_k.shape[1]

    def write_all(kc, vc, pages, blocks, plens):
        for i in range(n):
            pages = write_prefill_to_pages(
                jax.lax.slice_in_dim(kc, i, i + 1, axis=1),
                jax.lax.slice_in_dim(vc, i, i + 1, axis=1),
                pages, blocks[i], plens[i])
        return pages

    from repro.kernels.ops import _sharded

    if not _sharded(mesh, axis_name):
        return write_all(cache_k, cache_v, pages, blocks, prompt_lens)

    from jax.sharding import PartitionSpec as P

    def body(kc, vc, k_pages, v_pages, blocks, plens, home):
        idx = jax.lax.axis_index(axis_name)
        local_plens = jnp.where(home == idx, plens, 0).astype(jnp.int32)
        out = write_all(kc, vc, {"k_pages": k_pages, "v_pages": v_pages},
                        blocks, local_plens)
        return out["k_pages"], out["v_pages"]

    pool = P(None, None, axis_name, None, None)
    k_pages, v_pages = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), pool, pool, P(), P(), P()),
        out_specs=(pool, pool), check_vma=False,
    )(cache_k, cache_v, pages["k_pages"], pages["v_pages"],
      blocks, prompt_lens, home_shard.astype(jnp.int32))
    return {"k_pages": k_pages, "v_pages": v_pages}


def copy_page_rows(
    pages: Dict,
    src: jax.Array,           # [N] int32 source page ids (shard-local)
    dst: jax.Array,           # [N] int32 destination page ids
    rows: jax.Array,          # [N] int32 leading rows to copy per pair
    home_shard: Optional[jax.Array] = None,   # [N] int32 (mesh only)
    *,
    mesh=None,
    axis_name: str = "data",
) -> Dict:
    """Copy the leading ``rows[i]`` K/V rows of page ``src[i]`` into page
    ``dst[i]`` across every layer and kv head — the prefix cache's
    copy-on-write step, run before a divergent suffix appends into a
    partially-matched shared page.

    Same in-place discipline as the prefill writers: one
    ``dynamic_slice`` read of the source tile plus one masked
    read-select-writeback ``dynamic_update_slice`` per pair, so with the
    pool donated the copy costs O(rows copied), not O(pool).  Rows past
    ``rows[i]`` keep the destination's old contents.  Under a ``mesh``
    both pages live on the pair's ``home_shard`` (page sharing is
    shard-local); foreign shards mask ``rows`` to 0 and write nothing.
    """
    from repro.kernels.ref import masked_inplace_update

    n = src.shape[0]

    def copy_all(k_pages, v_pages, src, dst, rows):
        bs = k_pages.shape[3]
        zero = jnp.zeros((), jnp.int32)
        sizes = (k_pages.shape[0], k_pages.shape[1], 1, bs,
                 k_pages.shape[4])
        for i in range(n):
            valid = (jnp.arange(bs, dtype=jnp.int32)
                     < rows[i])[None, None, None, :, None]
            at_src = (zero, zero, src[i].astype(jnp.int32), zero, zero)
            at_dst = (zero, zero, dst[i].astype(jnp.int32), zero, zero)
            k_tile = jax.lax.dynamic_slice(k_pages, at_src, sizes)
            v_tile = jax.lax.dynamic_slice(v_pages, at_src, sizes)
            k_pages = masked_inplace_update(k_pages, k_tile, at_dst, valid)
            v_pages = masked_inplace_update(v_pages, v_tile, at_dst, valid)
        return k_pages, v_pages

    from repro.kernels.ops import _sharded

    if not _sharded(mesh, axis_name):
        k_pages, v_pages = copy_all(
            pages["k_pages"], pages["v_pages"], src, dst, rows)
        return {"k_pages": k_pages, "v_pages": v_pages}

    from jax.sharding import PartitionSpec as P

    def body(k_pages, v_pages, src, dst, rows, home):
        idx = jax.lax.axis_index(axis_name)
        local_rows = jnp.where(home == idx, rows, 0).astype(jnp.int32)
        return copy_all(k_pages, v_pages, src, dst, local_rows)

    pool = P(None, None, axis_name, None, None)
    k_pages, v_pages = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pool, pool, P(), P(), P(), P()),
        out_specs=(pool, pool), check_vma=False,
    )(pages["k_pages"], pages["v_pages"], src, dst, rows,
      home_shard.astype(jnp.int32))
    return {"k_pages": k_pages, "v_pages": v_pages}


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    prefix_embeds: Optional[jax.Array],
) -> Tuple[jax.Array, int]:
    x = embedding_apply(params["embed"], tokens)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # gemma-style scaling
    prefix_len = 0
    if cfg.vision_prefix_len > 0:
        assert prefix_embeds is not None, (
            f"{cfg.name}: vision/audio prefix embeddings required"
        )
        proj = dense_apply(params["vision_proj"], prefix_embeds)
        x = jnp.concatenate([proj.astype(x.dtype), x], axis=1)
        prefix_len = cfg.vision_prefix_len
    return x, prefix_len


def forward(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,                       # [B, S]
    *,
    prefix_embeds: Optional[jax.Array] = None,  # [B, P, vision_dim]
    kv_valid: Optional[jax.Array] = None,       # [B, S(+P)] padding mask
    return_cache: bool = False,
    cache_len: Optional[int] = None,            # cache capacity for prefill
    unroll_layers: bool = False,
    remat: bool = False,
) -> ModelOutput:
    x, prefix_len = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    windows = layer_windows(cfg)
    prefix = prefix_len if cfg.prefix_lm else 0

    aux0 = jnp.zeros((), jnp.float32)

    def body(carry, xs):
        x, aux = carry
        lp, window = xs
        ys = {}
        if cfg.attn_free:
            h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
            out, (wkv_state, shift_tm) = rwkv_mod.rwkv6_time_mix(
                lp["rwkv"], h
            )
            x = x + out
            h = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
            out, shift_cm = rwkv_mod.rwkv6_channel_mix(lp["rwkv"], h)
            x = x + out
            if return_cache:
                ys = {"wkv": wkv_state, "shift_tm": shift_tm,
                      "shift_cm": shift_cm}
            return (x, aux), ys

        h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
        attn_out, (k, v) = attn.attn_forward(
            lp["attn"], h, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            window=window, kv_valid=kv_valid, prefix_len=prefix,
        )
        if cfg.hybrid_attn_ssm:
            ssm_out, (ssm_state, conv_state) = ssm_mod.ssm_forward(
                lp["ssm"], h, cfg.ssm
            )
            mix = 0.5 * (attn_out + ssm_out)   # hymba: mean-fused heads
            x = x + mix
            if return_cache:
                ys = {"ssm": ssm_state, "conv": conv_state}
        else:
            x = x + attn_out
        if return_cache:
            pad = cache_len if cache_len is not None else s
            kc = jnp.zeros((b, pad) + k.shape[2:], k.dtype)
            vc = jnp.zeros((b, pad) + v.shape[2:], v.dtype)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
            ys = dict(ys, k=kc, v=vc)

        h = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            mlp_out, moe_aux = moe_mod.moe_apply(
                lp["moe"], h, cfg.moe, cfg.activation,
                group_size=cfg.moe.group_size,
            )
            aux = aux + moe_aux
        else:
            mlp_out = mlp_apply(lp["mlp"], h, cfg.activation)
        x = x + mlp_out
        return (x, aux), ys

    (x, aux), cache_ys = scan_layers(
        body, (x, aux0), (params["layers"], windows), unroll_layers, remat
    )

    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = embedding_attend(params["embed"], x)
    else:
        logits = dense_apply(params["lm_head"], x)
    logits = softcap(logits, cfg.logit_softcap)

    value = None
    if cfg.value_head:
        value = dense_apply(params["value_head"], x)[..., 0]

    cache = None
    if return_cache:
        cache = dict(cache_ys)
        cache["pos"] = jnp.full((b,), s, jnp.int32)
    # Strip the prefix positions from the heads (policy over text tokens).
    if prefix_len > 0:
        logits = logits[:, prefix_len:]
        if value is not None:
            value = value[:, prefix_len:]
    return ModelOutput(logits=logits, value=value, cache=cache, aux_loss=aux)


# ---------------------------------------------------------------------------
# Decode (single-token serve step)
# ---------------------------------------------------------------------------


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    token: jax.Array,       # [B] current token ids
    cache: Dict,
    unroll_layers: bool = False,
) -> Tuple[ModelOutput, Dict]:
    """One autoregressive step against the cache. Returns logits [B, V]."""
    x = embedding_apply(params["embed"], token[:, None])
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    pos = cache["pos"]
    windows = layer_windows(cfg)
    prefix = cfg.vision_prefix_len if cfg.prefix_lm else 0

    if cfg.attn_free:
        def body(x, xs):
            lp, wkv, sh_tm, sh_cm = xs
            h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
            out, (wkv, sh_tm) = rwkv_mod.rwkv6_time_mix(
                lp["rwkv"], h, state=(wkv, sh_tm)
            )
            x = x + out
            h = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
            out, sh_cm = rwkv_mod.rwkv6_channel_mix(lp["rwkv"], h, sh_cm)
            x = x + out
            return x, {"wkv": wkv, "shift_tm": sh_tm, "shift_cm": sh_cm}

        x, new = scan_layers(
            body, x,
            (params["layers"], cache["wkv"], cache["shift_tm"],
             cache["shift_cm"]),
            unroll_layers,
        )
        new_cache = dict(new, pos=pos + 1)
    else:
        def layer_step(x, xs, window_slice=None):
            if cfg.hybrid_attn_ssm:
                lp, window, ck, cv, ssm_state, conv_state = xs
            else:
                lp, window, ck, cv = xs
            ys = {}
            h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
            attn_out, (ck, cv) = attn.attn_decode(
                lp["attn"], h, pos, ck, cv,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                window=window, prefix_len=prefix,
                window_slice=window_slice,
            )
            ys["k"], ys["v"] = ck, cv
            if cfg.hybrid_attn_ssm:
                ssm_out, (ssm_state, conv_state) = ssm_mod.ssm_forward(
                    lp["ssm"], h, cfg.ssm, state=(ssm_state, conv_state)
                )
                ys["ssm"], ys["conv"] = ssm_state, conv_state
                x = x + 0.5 * (attn_out + ssm_out)
            else:
                x = x + attn_out
            h = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                # Same grouped dispatch as training (group = the decode
                # batch) so expert parallelism lowers to the identical
                # all-to-all pattern in serve_step.
                mlp_out, _ = moe_mod.moe_apply(
                    lp["moe"], h, cfg.moe, cfg.activation,
                    group_size=h.shape[0],
                )
            else:
                mlp_out = mlp_apply(lp["mlp"], h, cfg.activation)
            x = x + mlp_out
            return x, ys

        if cfg.hybrid_attn_ssm:
            xs = (params["layers"], windows, cache["k"], cache["v"],
                  cache["ssm"], cache["conv"])
        else:
            xs = (params["layers"], windows, cache["k"], cache["v"])

        if unroll_layers and cfg.sliding_window is not None:
            # Unrolled decode with STATIC per-layer windows: local layers
            # read only a window-sized dynamic slice of the cache (§Perf
            # hillclimb #3b — cache-read bytes on local layers drop by
            # ~window/Smax, e.g. 32x for gemma3 decode_32k).
            ys_all = []
            for i in range(cfg.n_layers):
                xs_i = jax.tree.map(lambda a: a[i], xs)
                x, ys = layer_step(
                    x, xs_i, window_slice=cfg.window_for_layer(i))
                ys_all.append(ys)
            new = jax.tree.map(lambda *z: jnp.stack(z), *ys_all)
        else:
            x, new = scan_layers(
                lambda c, xs_i: layer_step(c, xs_i), x, xs, unroll_layers)
        new_cache = dict(new, pos=pos + 1)

    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = embedding_attend(params["embed"], x)
    else:
        logits = dense_apply(params["lm_head"], x)
    logits = softcap(logits, cfg.logit_softcap)
    value = None
    if cfg.value_head:
        value = dense_apply(params["value_head"], x)[..., 0]
    out = ModelOutput(
        logits=logits[:, 0], value=None if value is None else value[:, 0],
        cache=None, aux_loss=jnp.zeros((), jnp.float32),
    )
    return out, new_cache
