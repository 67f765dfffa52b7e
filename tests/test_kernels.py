"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _layered_pool import layer_cases, layered
from repro.kernels import ref
from repro.kernels.flash_attention_pallas import flash_attention
from repro.kernels.fused_logprob_pallas import logprobs_pallas
from repro.kernels.paged_attention_pallas import (
    paged_attention,
    paged_attention_multi,
    paged_attention_varlen,
)
from repro.kernels.vtrace_pallas import vtrace_pallas
from repro.kernels.wkv6_pallas import wkv6_pallas
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# vtrace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,t", [(1, 5), (4, 13), (8, 64), (13, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vtrace_kernel_sweep(b, t, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, b * t), 5)
    lr = (0.5 * jax.random.normal(ks[0], (b, t))).astype(dtype)
    v = jax.random.normal(ks[1], (b, t)).astype(dtype)
    bv = jax.random.normal(ks[2], (b,)).astype(dtype)
    r = jax.random.normal(ks[3], (b, t)).astype(dtype)
    d = 0.99 * (1 - jax.random.bernoulli(ks[4], 0.1, (b, t)).astype(
        jnp.float32)).astype(dtype)
    vs, adv = vtrace_pallas(lr, v, bv, r, d, interpret=True)
    vs_r, adv_r = ref.ref_vtrace(
        lr.astype(jnp.float32), v.astype(jnp.float32),
        bv.astype(jnp.float32), r.astype(jnp.float32),
        d.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(vs, vs_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(adv, adv_r, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,h,kv,d,window",
    [(64, 4, 2, 32, None), (100, 4, 1, 16, None), (128, 8, 8, 64, 32),
     (96, 4, 2, 32, 16), (65, 2, 2, 8, 7)],
)
def test_flash_attention_sweep(s, h, kv, d, window):
    ks = jax.random.split(jax.random.fold_in(KEY, s + h + d), 3)
    q = jax.random.normal(ks[0], (2, s, h, d))
    k = jax.random.normal(ks[1], (2, s, kv, d))
    v = jax.random.normal(ks[2], (2, s, kv, d))
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    want = ref.ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 32)).astype(dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.ref_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# paged decode attention (serve engine)
# ---------------------------------------------------------------------------


def _ragged_tables(rng, b, num_blocks, max_blocks, block_size,
                   full_lens=False):
    """Shuffled distinct page assignments + ragged context lengths."""
    perm = rng.permutation(num_blocks)
    tables = np.zeros((b, max_blocks), np.int32)
    lens = np.zeros((b,), np.int32)
    nxt = 0
    for i in range(b):
        n_pages = int(rng.integers(1, max_blocks + 1))
        if nxt + n_pages > num_blocks:
            n_pages = num_blocks - nxt
        tables[i, :n_pages] = perm[nxt:nxt + n_pages]
        nxt += n_pages
        hi = n_pages * block_size
        lens[i] = hi if full_lens else int(rng.integers(1, hi + 1))
    return tables, lens


@pytest.mark.parametrize(
    "b,h,kv,d,bs,window,layers,layer",
    layer_cases(
        [(4, 4, 2, 16, 8, None), (3, 4, 4, 32, 4, None),
         (2, 8, 2, 16, 8, 5), (5, 2, 1, 8, 16, None),
         (4, 4, 2, 16, 8, 12)],
        (4, 4, 2, 16, 8, None)),
)
def test_paged_attention_ragged_sweep(b, h, kv, d, bs, window, layers,
                                      layer):
    """Pallas kernel vs jnp oracle on shuffled, ragged block tables;
    the kernel reads layer ``layer`` of the whole pool in place, the
    oracle that layer alone."""
    rng = np.random.default_rng(b * 31 + h)
    num_blocks, max_blocks = 24, 4
    ks = jax.random.split(jax.random.fold_in(KEY, b * h * d), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (kv, num_blocks, bs, d))
    vp = jax.random.normal(ks[2], (kv, num_blocks, bs, d))
    tables, lens = _ragged_tables(rng, b, num_blocks, max_blocks, bs)
    out = paged_attention(q, layered(kp, layers, layer),
                          layered(vp, layers, layer), jnp.asarray(tables),
                          jnp.asarray(lens), layer=layer, window=window,
                          interpret=True)
    want = ref.ref_paged_attention(q, kp, vp, jnp.asarray(tables),
                                   jnp.asarray(lens), window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_inactive_slot_zero_output():
    """context_len 0 (an empty serve slot) must yield exactly zero."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 16))
    kp = jax.random.normal(ks[1], (2, 8, 4, 16))
    vp = jax.random.normal(ks[2], (2, 8, 4, 16))
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([0, 6], jnp.int32)
    for fn in (
        lambda: paged_attention(q, kp[None], vp[None], tables, lens,
                                layer=0, interpret=True),
        lambda: ref.ref_paged_attention(q, kp, vp, tables, lens),
    ):
        out = np.asarray(fn())
        np.testing.assert_array_equal(out[0], 0.0)
        assert np.abs(out[1]).max() > 0


def test_paged_attention_matches_dense_attention():
    """A contiguous single-request table == plain causal attention on
    the last query position (the dense/paged equivalence the serve
    engine relies on)."""
    s, h, kv, d, bs = 12, 4, 2, 16, 4
    ks = jax.random.split(KEY, 3)
    q_full = jax.random.normal(ks[0], (1, s, h, d))
    k_full = jax.random.normal(ks[1], (1, s, kv, d))
    v_full = jax.random.normal(ks[2], (1, s, kv, d))
    want = ref.ref_attention(q_full, k_full, v_full, causal=True)[0, -1]
    # pack rows 0..s-1 into contiguous pages
    kp = jnp.zeros((kv, 4, bs, d))
    vp = jnp.zeros((kv, 4, bs, d))
    kp = kp.at[:, :3].set(
        k_full[0].transpose(1, 0, 2).reshape(kv, 3, bs, d))
    vp = vp.at[:, :3].set(
        v_full[0].transpose(1, 0, 2).reshape(kv, 3, bs, d))
    tables = jnp.asarray([[0, 1, 2]], jnp.int32)
    lens = jnp.asarray([s], jnp.int32)
    got = ref.ref_paged_attention(q_full[:, -1], kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention, varlen (one kernel family: prefill/decode/verify)
# ---------------------------------------------------------------------------


def _varlen_rows(rng, lens, t):
    """Random per-slot ``(row_start, row_len)`` inside each context.

    Mixes the three call shapes the serve engine issues: decode rows
    (``row_len == 1``), ragged tiles (``1 < row_len <= t``) and dead
    slots (``row_len == 0``) — plus a ``row_start`` anywhere in the
    written context, as chunked prefill resumes mid-prompt."""
    b = len(lens)
    row_start = np.zeros((b,), np.int32)
    row_len = np.zeros((b,), np.int32)
    for i in range(b):
        kind = i % 3
        if kind == 0 and lens[i] >= 1:          # decode shape
            row_len[i] = 1
        elif kind == 1:                          # dead slot
            row_len[i] = 0
        else:                                    # ragged tile
            row_len[i] = int(rng.integers(1, min(t, lens[i]) + 1))
        row_start[i] = int(rng.integers(0, lens[i] - row_len[i] + 1))
    return row_start, row_len


@pytest.mark.parametrize(
    "b,t,h,kv,d,bs,window,layers,layer",
    layer_cases(
        [(4, 4, 4, 2, 16, 8, None), (3, 8, 4, 4, 32, 4, None),
         (5, 3, 2, 1, 8, 16, None), (2, 6, 8, 2, 16, 8, 5),
         (4, 5, 4, 2, 16, 8, 12), (6, 2, 2, 2, 8, 4, None)],
        (4, 5, 4, 2, 16, 8, 12)),
)
def test_paged_attention_varlen_ragged_sweep(b, t, h, kv, d, bs, window,
                                             layers, layer):
    """Varlen Pallas kernel (interpret) vs the jnp oracle on shuffled
    tables with mixed decode/tile/dead rows at ragged offsets; the kernel
    reads layer ``layer`` of the whole pool, the oracle that layer."""
    rng = np.random.default_rng(b * 131 + t * 7 + h)
    num_blocks, max_blocks = 24, 4
    ks = jax.random.split(jax.random.fold_in(KEY, b * t * h + d), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kp = jax.random.normal(ks[1], (kv, num_blocks, bs, d))
    vp = jax.random.normal(ks[2], (kv, num_blocks, bs, d))
    tables, lens = _ragged_tables(rng, b, num_blocks, max_blocks, bs)
    row_start, row_len = _varlen_rows(rng, lens, t)
    got = paged_attention_varlen(
        q, layered(kp, layers, layer), layered(vp, layers, layer),
        jnp.asarray(tables), jnp.asarray(row_start), jnp.asarray(row_len),
        layer=layer, window=window, interpret=True)
    want = ref.ref_paged_attention_varlen(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(row_start),
        jnp.asarray(row_len), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # Padding rows and dead slots must be exactly zero, not just close.
    got_np = np.asarray(got)
    for i in range(b):
        np.testing.assert_array_equal(got_np[i, row_len[i]:], 0.0)


def test_paged_attention_varlen_subsumes_decode_and_verify():
    """The three serve call shapes are one kernel: ``row_len == 1``
    reproduces single-token decode and full-tail ``row_len == k``
    reproduces the speculative-verify (multi) shape, numerically
    identical to the dedicated entry points."""
    rng = np.random.default_rng(7)
    b, t, h, kv, d, bs = 4, 4, 4, 2, 16, 8
    num_blocks, max_blocks = 24, 4
    ks = jax.random.split(jax.random.fold_in(KEY, 977), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kp = jax.random.normal(ks[1], (kv, num_blocks, bs, d))
    vp = jax.random.normal(ks[2], (kv, num_blocks, bs, d))
    tables, lens = _ragged_tables(rng, b, num_blocks, max_blocks, bs)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    kp, vp = kp[None], vp[None]

    # decode: the varlen row (row_start = ctx-1, row_len = 1) vs the
    # single-token kernel on the same contexts.
    dec = paged_attention_varlen(
        q[:, :1], kp, vp, tables, lens - 1, jnp.ones((b,), jnp.int32),
        layer=0, interpret=True)
    want_dec = paged_attention(q[:, 0], kp, vp, tables, lens, layer=0,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(want_dec),
                               rtol=2e-5, atol=2e-5)

    # verify: the fixed-T wrapper is literally the varlen kernel with
    # (ctx-T, T) rows — including its treatment of inactive slots.
    lens_inact = lens.at[1].set(0)
    multi = paged_attention_multi(q, kp, vp, tables, lens_inact, layer=0,
                                  interpret=True)
    active = lens_inact > 0
    var = paged_attention_varlen(
        q, kp, vp, tables,
        jnp.where(active, lens_inact - t, 0),
        jnp.where(active, t, 0), layer=0, interpret=True)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(var),
                               rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(multi[1]), 0.0)


def test_ops_varlen_dispatch_modes_agree():
    """reference and pallas_interpret modes of the ops-layer varlen
    entry agree on ragged mixed-shape rows, at a middle layer of a
    three-layer pool."""
    rng = np.random.default_rng(13)
    b, t, h, kv, d, bs = 5, 3, 4, 2, 16, 4
    num_blocks, max_blocks = 16, 4
    ks = jax.random.split(jax.random.fold_in(KEY, 1933), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kp = jax.random.normal(ks[1], (3, kv, num_blocks, bs, d))
    vp = jax.random.normal(ks[2], (3, kv, num_blocks, bs, d))
    tables, lens = _ragged_tables(rng, b, num_blocks, max_blocks, bs)
    row_start, row_len = _varlen_rows(rng, lens, t)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(row_start),
            jnp.asarray(row_len))
    a = ops.paged_attention_varlen(*args, layer=1, mode="reference")
    bI = ops.paged_attention_varlen(*args, layer=1, mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(bI),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,h,kd,vd,chunk",
    [(32, 2, 16, 16, 8), (50, 3, 32, 32, 16), (64, 2, 64, 64, 64),
     (17, 1, 8, 8, 4)],
)
def test_wkv6_sweep(s, h, kd, vd, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + kd), 6)
    r = jax.random.normal(ks[0], (2, s, h, kd))
    k = jax.random.normal(ks[1], (2, s, h, kd))
    v = jax.random.normal(ks[2], (2, s, h, vd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (2, s, h, kd))) * 0.8 + 0.1
    u = 0.3 * jax.random.normal(ks[4], (h, kd))
    s0 = jax.random.normal(ks[5], (2, h, kd, vd))
    y, sf = wkv6_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, sr = ref.ref_wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               rtol=3e-4, atol=3e-4)


def test_wkv6_extreme_decay_stable():
    """Aggressive decays (w -> 0) must not overflow the chunked form —
    the TPU adaptation's exponent differences are all <= 0."""
    s, h, kd, vd = 32, 1, 16, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (1, s, h, kd))
    k = jax.random.normal(ks[1], (1, s, h, kd))
    v = jax.random.normal(ks[2], (1, s, h, vd))
    w = jnp.full((1, s, h, kd), 1e-6)  # near-total forgetting
    u = jnp.zeros((h, kd))
    y, sf = wkv6_pallas(r, k, v, w, u, chunk=16, interpret=True)
    yr, sr = ref.ref_wkv6(r, k, v, w, u)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused logprob
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,vocab,bn,bv",
    [(16, 54, 8, 32), (7, 1000, 8, 256), (64, 2048, 8, 512),
     (3, 131, 4, 64)],
)
def test_logprob_kernel_sweep(n, vocab, bn, bv):
    ks = jax.random.split(jax.random.fold_in(KEY, n * vocab), 2)
    logits = 4.0 * jax.random.normal(ks[0], (n, vocab))
    targets = jax.random.randint(ks[1], (n,), 0, vocab)
    logp, ent = logprobs_pallas(logits, targets, block_n=bn, block_v=bv,
                                interpret=True)
    logp_r = ref.ref_logprobs_from_logits(logits, targets)
    ent_r = ref.ref_entropy_from_logits(logits)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(logp_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_r),
                               rtol=1e-4, atol=1e-4)


def test_logprob_kernel_bf16_logits():
    ks = jax.random.split(KEY, 2)
    logits = (4.0 * jax.random.normal(ks[0], (32, 512))).astype(jnp.bfloat16)
    targets = jax.random.randint(ks[1], (32,), 0, 512)
    logp, _ = logprobs_pallas(logits, targets, interpret=True)
    logp_r = ref.ref_logprobs_from_logits(logits, targets)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(logp_r),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------


def test_ops_dispatch_modes_agree():
    ks = jax.random.split(KEY, 5)
    lr = 0.3 * jax.random.normal(ks[0], (4, 16))
    v = jax.random.normal(ks[1], (4, 16))
    bv = jax.random.normal(ks[2], (4,))
    r = jax.random.normal(ks[3], (4, 16))
    d = jnp.full((4, 16), 0.99)
    a = ops.vtrace(lr, v, bv, r, d, mode="reference")
    b = ops.vtrace(lr, v, bv, r, d, mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=1e-5, atol=1e-5)

    logits = jax.random.normal(ks[4], (2, 8, 64))
    tgts = jax.random.randint(ks[0], (2, 8), 0, 64)
    la, ea = ops.logprobs_from_logits(logits, tgts, mode="reference")
    lb, eb = ops.logprobs_from_logits(logits, tgts, mode="pallas_interpret")
    assert la.shape == (2, 8)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ea), np.asarray(eb),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# selective-SSM scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,i,n,blk",
    [(16, 32, 8, 16), (33, 100, 16, 64), (64, 128, 16, 64), (7, 8, 4, 8)],
)
def test_ssm_scan_sweep(s, i, n, blk):
    from repro.kernels.ssm_scan_pallas import ssm_scan_pallas

    ks = jax.random.split(jax.random.fold_in(KEY, s * i), 6)
    u = jax.random.normal(ks[0], (2, s, i))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, i)))
    bt = jax.random.normal(ks[2], (2, s, n))
    ct = jax.random.normal(ks[3], (2, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (i, n)))
    h0 = jax.random.normal(ks[5], (2, i, n))
    y, hT = ssm_scan_pallas(u, dt, bt, ct, a, h0, block_i=blk,
                            interpret=True)
    y_r, h_r = ref.ref_ssm_scan(u, dt, bt, ct, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_r),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_ops_dispatch():
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (1, 8, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 8, 16)))
    bt = jax.random.normal(ks[2], (1, 8, 4))
    ct = jax.random.normal(ks[3], (1, 8, 4))
    a = -jnp.exp(jax.random.normal(ks[4], (16, 4)))
    y1, h1 = ops.ssm_scan(u, dt, bt, ct, a, mode="reference")
    y2, h2 = ops.ssm_scan(u, dt, bt, ct, a, mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
