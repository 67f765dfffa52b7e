"""Pallas TPU kernel for the selective-SSM (Mamba/S6) recurrence.

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + dt_t ⊙ u_t ⊙ B_t
    y_t = h_t · C_t   (+ D-skip handled by the caller)

Parallel over (batch x channel blocks), sequential over time — the state
[I_BLK, N] lives in VMEM scratch for the whole trajectory, so each step
is a handful of VPU vector ops with zero HBM round-trips for the state
(the XLA scan reference spills the [B, I, N] carry between steps).

Channel blocks of 64 x state 16 keep the per-program working set
(inputs for all S timesteps + state) around 2-4 MiB for S=4096.

Validated in interpret mode against the jnp scan in repro.models.ssm.
Forward-only: training uses the autodiff-able reference; the kernel
serves the actor-side (no-grad) paths and is the TPU adaptation of the
CUDA selective-scan in the Mamba reference implementation.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(
    u_ref,    # [1, S, IB]
    dt_ref,   # [1, S, IB]
    b_ref,    # [1, S, N]
    c_ref,    # [1, S, N]
    a_ref,    # [IB, N]
    h0_ref,   # [1, IB, N]
    y_ref,    # [1, S, IB] out
    hT_ref,   # [1, IB, N] out
    h_scratch,  # [IB, N] fp32
    *,
    s_len: int,
):
    h_scratch[...] = h0_ref[0].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)          # [IB, N]

    def step(t, _):
        row = (0, pl.ds(t, 1), slice(None))
        u_t = u_ref[row][0]
        dt_t = dt_ref[row][0]
        b_t = b_ref[row][0]
        c_t = c_ref[row][0]
        u_t = u_t.astype(jnp.float32)
        dt_t = dt_t.astype(jnp.float32)
        b_t = b_t.astype(jnp.float32)
        c_t = c_t.astype(jnp.float32)

        h = h_scratch[...]
        decay = jnp.exp(dt_t[:, None] * a)                   # [IB, N]
        h = decay * h + (dt_t * u_t)[:, None] * b_t[None, :]
        h_scratch[...] = h
        y_t = jnp.sum(h * c_t[None, :], axis=1)              # [IB]
        y_ref[0, pl.ds(t, 1), :] = y_t[None, :].astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, s_len, step, ())
    hT_ref[0] = h_scratch[...].astype(hT_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_i", "interpret")
)
def ssm_scan_pallas(
    u: jax.Array,     # [B, S, I]
    dt: jax.Array,    # [B, S, I]
    b_t: jax.Array,   # [B, S, N]
    c_t: jax.Array,   # [B, S, N]
    a: jax.Array,     # [I, N] (negative reals)
    h0: Optional[jax.Array] = None,   # [B, I, N]
    *,
    block_i: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,I], h_final [B,I,N])."""
    bsz, s, inner = u.shape
    n = a.shape[1]
    if h0 is None:
        h0 = jnp.zeros((bsz, inner, n), jnp.float32)
    block_i = min(block_i, inner)
    pad_i = (-inner) % block_i
    if pad_i:
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad_i)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_i)))
        a = jnp.pad(a, ((0, pad_i), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_i), (0, 0)))
    ip = inner + pad_i
    num_i = ip // block_i

    chan_spec = pl.BlockSpec((1, s, block_i), lambda b_, i: (b_, 0, i))
    state_in_spec = pl.BlockSpec((1, s, n), lambda b_, i: (b_, 0, 0))
    a_spec = pl.BlockSpec((block_i, n), lambda b_, i: (i, 0))
    h_spec = pl.BlockSpec((1, block_i, n), lambda b_, i: (b_, i, 0))

    y, hT = pl.pallas_call(
        functools.partial(_ssm_kernel, s_len=s),
        grid=(bsz, num_i),
        in_specs=[chan_spec, chan_spec, state_in_spec, state_in_spec,
                  a_spec, h_spec],
        out_specs=[chan_spec, h_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, ip), u.dtype),
            jax.ShapeDtypeStruct((bsz, ip, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_i, n), jnp.float32)],
        interpret=interpret,
    )(u, dt, b_t, c_t, a, h0)
    return y[..., :inner], hT[:, :inner]
