"""Chunked RWKV-6 WKV recurrence kernel for TPU.

TPU-native adaptation: the GPU RWKV kernels run one thread per channel with a
serial token loop. On TPU we instead use the *chunked matrix form* so the MXU
does the heavy lifting:

  per chunk of Lc tokens (state S [N,N] carried in VMEM scratch across the
  sequential chunk grid axis):
    cw       = tril(1) @ log w  (inclusive cumsum)  # [Lc,N], all <= 0
    y_inter  = (r * exp(cw_prev)) @ S               # MXU [Lc,N]x[N,N]
    a[j,i,n] = exp(cw_prev[j,n] - cw[i,n])  (i<j)   # VPU, bounded <= 1
    s[j,i]   = sum_n r[j,n] a[j,i,n] k[i,n]         # VPU reduce
    y_intra  = tril(s) @ v                          # MXU [Lc,Lc]x[Lc,N]
    y_diag   = (sum_n r*u*k) * v
    S'       = diag(exp(cw_L)) S + (k*exp(cw_L-cw))^T v   # MXU

Every exponential argument is <= 0 — exact, overflow-free fp32 (no decay
clamping); every matmul runs at f32 precision (``Precision.HIGHEST``).
The code sticks to what the TPU lowering offers: no cumsum, no dynamic
slices, and 2-D row/column shapes for per-row quantities. VMEM per (b,h)
program: 4*Lc*N inputs + Lc^2*N for `a` + [N,N] state
≈ (4*64*64 + 64*64*64 + 64*64)*4B ≈ 1.1 MB at Lc=N=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 matmul at full precision ([M,K] x [K,N] unless dims say otherwise)
_mm = functools.partial(jax.lax.dot_general,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
_MK_KN = (((1,), (0,)), ((), ()))
_KM_KN = (((0,), (0,)), ((), ()))


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_ref, *, chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)                      # [Lc,N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                      # [1,N]
    S0 = state_ref[...]                                   # [N,N]
    Lc = r.shape[0]

    rows = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    cw = _mm((rows >= cols).astype(jnp.float32), lw, _MK_KN)  # [Lc,N] <= 0
    cw_prev = cw - lw
    y_inter = _mm(r * jnp.exp(cw_prev), S0, _MK_KN)
    diff = cw_prev[:, None, :] - cw[None, :, :]           # [Lc,Lc,N]
    diff = jnp.minimum(diff, 0.0)
    a = jnp.exp(diff)
    tri = (rows > cols).astype(jnp.float32)
    s = jnp.sum(r[:, None, :] * a * k[None, :, :], axis=-1) * tri  # [Lc,Lc]
    y_intra = _mm(s, v, _MK_KN)
    coef = jnp.sum(r * u * k, axis=-1, keepdims=True)     # [Lc,1]
    y = y_inter + y_intra + coef * v
    y_ref[0] = y.astype(y_ref.dtype)

    cw_last = cw[Lc - 1:Lc]                               # [1,N]
    # the chunk's total decay as a column: cw_col[n, m] = cw_last[n]
    cw_col = _mm(lw, jnp.ones_like(lw), _KM_KN)           # [N,N]
    kd = k * jnp.exp(cw_last - cw)                        # [Lc,N]
    state_ref[...] = jnp.exp(cw_col) * S0 + _mm(kd, v, _KM_KN)

    @pl.when(c == chunks - 1)
    def _finalize():
        sout_ref[0] = state_ref[...]


def wkv_kernel(r, k, v, logw, u, state0, *, chunk: int = 64,
               interpret: bool = False):
    """r,k,v,logw: [BH, S, N]; u: [BH, N]; state0: [BH, N, N] fp32.
    (``u`` enters the call as [BH, 1, N], so its block tail is the
    array's own.)

    Returns (y [BH,S,N] fp32, state [BH,N,N] fp32).
    """
    BH, S, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    chunks = S // chunk
    grid = (BH, chunks)

    kernel = functools.partial(_wkv_kernel, chunks=chunks)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, N), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(BH, 1, N), state0)
