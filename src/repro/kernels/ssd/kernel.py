"""Mamba-2 SSD intra-chunk kernel (Pallas TPU).

The intra-chunk term of the SSD duality is, per (batch, chunk, head):

    scores = C B^T                (q x n @ n x q  -> MXU)
    L      = tril(exp(acs_t - acs_u))
    y      = (scores * L) @ x     (q x q @ q x p  -> MXU)

which is three MXU ops + a VPU mask per grid cell -- exactly the shape of work
the TPU wants, replacing the CUDA selective-scan's warp shuffles.  Grid:
(batch * n_chunks, heads); all operands for one (chunk, head) fit easily in
VMEM (chunk<=256, state n<=128, head dim p<=64 => < 1 MB).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, acol_ref, arow_ref, b_ref, c_ref, o_ref):
    x = x_ref[0, 0].astype(jnp.float32)           # (q, p)
    B = b_ref[0, 0].astype(jnp.float32)           # (q, n)
    C = c_ref[0, 0].astype(jnp.float32)           # (q, n)
    q = x.shape[0]
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (q, q)
    t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    u = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # acs arrives as a column (q, 1) and a row (1, q): the kernel never
    # transposes a vector
    decay = jnp.exp(acol_ref[0, 0] - arow_ref[0, 0])
    L = jnp.where(t >= u, decay, 0.0)
    y = jnp.dot(scores * L, x, preferred_element_type=jnp.float32)   # (q, p)
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_intra_fwd(xb, acs, Bh, Ch, *, interpret: bool = False):
    """Intra-chunk SSD.

    xb:  (bc, q, h, p) fp32   (batch*chunks flattened)
    acs: (bc, q, h)    fp32   cumulative log-decay within chunk
    Bh:  (bc, q, h, n) fp32
    Ch:  (bc, q, h, n) fp32
    Returns y_intra: (bc, q, h, p) fp32.

    The kernel runs head-major, (bc, h, q, *): every block is then a whole
    (q, *) tile, as the TPU block rule asks.
    """
    bc, q, h, p = xb.shape
    n = Bh.shape[-1]
    heads_first = lambda a: jnp.moveaxis(a, 2, 1)
    acs_hq = jnp.moveaxis(acs.astype(jnp.float32), 2, 1)     # (bc, h, q)

    def spec(rows, cols):
        return pl.BlockSpec((1, 1, rows, cols), lambda b, hh: (b, hh, 0, 0))

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(bc, h),
        in_specs=[spec(q, p), spec(q, 1), spec(1, q), spec(q, n), spec(q, n)],
        out_specs=spec(q, p),
        out_shape=jax.ShapeDtypeStruct((bc, h, q, p), jnp.float32),
        interpret=interpret,
    )(heads_first(xb), acs_hq[..., None], acs_hq[:, :, None, :],
      heads_first(Bh), heads_first(Ch))
    return jnp.moveaxis(y, 1, 2)
