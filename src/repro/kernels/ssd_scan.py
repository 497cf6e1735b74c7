"""Mamba-2 SSD chunk-scan TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the sequence is cut
into chunks; within a chunk everything is dense matmuls (MXU-friendly), and
the inter-chunk recurrence is a scalar-decay state update carried in VMEM
scratch across the innermost (sequential) grid axis — the Pallas analogue of
``lax.scan`` with the state never leaving VMEM.

Grid: (B, H, NC).  Per step the kernel consumes one (chunk x head) tile:
  x  (Q, P)   head inputs           dt (Q, 1)  post-softplus step sizes
  B  (Q, N)   input projections     C  (Q, N)  output projections
  A  ()       per-head decay (negative scalar), via scalar prefetch
and produces y (Q, P), carrying h (P, N) f32 state in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(A_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                *, chunk, n_chunks):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = A_ref[hi]                                        # scalar, negative
    x = x_ref[0, 0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)             # (Q, 1)
    bmat = b_ref[0, 0, 0].astype(jnp.float32)            # (Q, N)
    cmat = c_ref[0, 0, 0].astype(jnp.float32)            # (Q, N)

    # Within-chunk cumulative log decays as masked sums over the chunk
    # (Mosaic has no cumsum), in both orientations the math below needs:
    # lc[t] = sum_{s<=t} l_s as (Q, 1) and the same as lc_row (1, Q).  dt moves
    # from sublanes to lanes by a diagonal-masked reduction, which is exact.
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dt_row = jnp.sum(jnp.where(ti == si, dt, 0.0), axis=0, keepdims=True)
    l_col = dt * a                                       # (Q, 1) log decays
    l_row = dt_row * a                                   # (1, Q)
    lc = jnp.sum(jnp.where(si <= ti, l_row, 0.0), axis=1, keepdims=True)
    lc_row = jnp.sum(jnp.where(ti <= si, l_col, 0.0), axis=0, keepdims=True)
    ltot = jnp.sum(l_col, axis=0, keepdims=True)         # (1, 1)

    # intra-chunk: y[t] = sum_{s<=t} (C_t.B_s) exp(lc_t - lc_s) dt_s x_s
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    decay = jnp.exp(lc - lc_row)
    m = jnp.where(ti >= si, cb * decay, 0.0) * dt_row
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y[t] += C_t . (exp(lc_t) * h_prev)
    h_prev = h_scr[...]                                  # (P, N)
    y = y + jnp.exp(lc) * jax.lax.dot_general(
        cmat, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(ltot) * h_prev + sum_s exp(ltot - lc_s) dt_s x_s B_s^T
    w = (jnp.exp(ltot - lc) * dt) * x                    # (Q, P)
    h_new = jnp.exp(ltot) * h_prev + jax.lax.dot_general(
        w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (P, N)
    h_scr[...] = h_new
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(x, dt, A, B_mat, C_mat, chunk, *, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B/C: (B,S,G,N).

    Returns y (B,S,H,P) f32, h_final (B,H,P,N) f32.  (D-skip and gating are
    applied by the caller; see ``repro.models.ssm``.)
    """
    b, s, h, p = x.shape
    g, n = B_mat.shape[2], B_mat.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    hpg = h // g

    # head-major chunked layouts
    xr = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtr = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk, 1)
    br = B_mat.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)
    cr = C_mat.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    grid = (b, h, nc)

    y, hT = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, 1, chunk, p),
                             lambda bi, hi, ci, *r: (bi, hi, ci, 0, 0)),
                pl.BlockSpec((1, 1, 1, chunk, 1),
                             lambda bi, hi, ci, *r: (bi, hi, ci, 0, 0)),
                pl.BlockSpec((1, 1, 1, chunk, n),
                             lambda bi, hi, ci, *r, hpg=hpg: (bi, hi // hpg, ci, 0, 0)),
                pl.BlockSpec((1, 1, 1, chunk, n),
                             lambda bi, hi, ci, *r, hpg=hpg: (bi, hi // hpg, ci, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, chunk, p),
                             lambda bi, hi, ci, *r: (bi, hi, ci, 0, 0)),
                pl.BlockSpec((1, 1, p, n),
                             lambda bi, hi, ci, *r: (bi, hi, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(A, xr, dtr, br, cr)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, hT
