"""Pallas TPU kernel for the Mamba-2 SSD (state-space duality) scan.

The SSD insight: a selective-SSM over a chunk decomposes into (a) an
intra-chunk *quadratic* term — structurally a masked attention matmul,
ideal for the MXU — and (b) an inter-chunk rank-N recurrent state carry.
On TPU we map:

* grid = (batch, heads, chunks) with the chunk axis last (sequential),
  so the [P, N] recurrent state lives in VMEM scratch across chunks —
  the chunked scan never round-trips the state through HBM;
* the intra-chunk [Q, Q] decay-masked score matrix and the [Q, P]/[P, N]
  products are MXU matmuls (Q = 128/256 aligned);
* B/C group mapping (G groups shared across H heads) handled in index
  maps, mirroring GQA folding;
* the wrapper lays every operand out head-major (``[B, H, L, ...]``),
  so each block is a whole ``[chunk, ...]`` tile of one head: the TPU
  lowering refuses a size-1 head axis in a block's last two dims.

Inputs are pre-discretised (x already dt-scaled, ``a`` = per-step log
decay <= 0) so every exp() in the kernel is of a non-positive number —
numerically safe without max-subtraction.

Oracle: ``ref.ssd_naive`` (quadratic form) / ``ref.ssd_chunked``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    x_ref,                   # [1, 1, Q, P]
    acol_ref,                # [1, 1, Q, 1] in-chunk cumsum of a
    arow_ref,                # [1, 1, 1, Q] the same, as a row
    b_ref,                   # [1, 1, Q, N]
    c_ref,                   # [1, 1, Q, N]
    y_ref,                   # [1, 1, Q, P]
    state_ref,               # VMEM scratch [P, N] f32
    *,
    chunk: int,
):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                       # [Q, P]
    a_col = acol_ref[0, 0]                                    # [Q, 1], <= 0, decreasing
    a_row = arow_ref[0, 0]                                    # [1, Q]
    b = b_ref[0, 0].astype(jnp.float32)                       # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)                       # [Q, N]

    # intra-chunk decay mask: L[i, j] = exp(a_cs[i] - a_cs[j]) for i >= j
    li = a_col - a_row
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(row >= col, jnp.exp(li), 0.0)            # [Q, Q]

    # (a) intra-chunk quadratic term (MXU): (C B^T * L) X
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * lmat                                                  # [Q, Q]
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                          # [Q, P]

    # (b) inter-chunk: contribution of the carried state
    c_in = c * jnp.exp(a_col)                                  # [Q, N]
    y = y + jax.lax.dot_general(
        c_in, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # [Q, N] x [P, N]^T -> [Q, P]

    # state update: h' = e^{sum a} h + sum_i e^{a_cs[-1]-a_cs[i]} x_i b_i^T
    a_last = a_col[chunk - 1:, :]                              # [1, 1]
    xw = x * jnp.exp(a_last - a_col)                           # [Q, P]
    # (the [1, 1] decay is widened along lanes first: the lowering
    # broadcasts along one of sublanes and lanes at a time)
    decay = jnp.exp(jnp.broadcast_to(a_last, (1, state_ref.shape[1])))
    state_ref[...] = state_ref[...] * decay + jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                          # [P, N]

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(
    x: jax.Array,            # [B, L, H, P]   dt-scaled inputs
    a: jax.Array,            # [B, L, H]      per-step log decay (<= 0)
    b: jax.Array,            # [B, L, G, N]
    c: jax.Array,            # [B, L, G, N]
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert H % G == 0, (H, G)
    rep = H // G
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    # head-major operands. The in-chunk cumulative decay is summed here
    # (the TPU lowering has no cumsum) and passed as a column and a row
    x, b, c = (t.transpose(0, 2, 1, 3) for t in (x, b, c))
    a_cs = jnp.cumsum(a.astype(jnp.float32).reshape(B, nc, chunk, H), axis=2)
    a_cs = a_cs.reshape(B, L, H).transpose(0, 2, 1)           # [B, H, L]

    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bt, h, n: (bt, h, n, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bt, h, n: (bt, h, n, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bt, h, n: (bt, h, 0, n)),
            pl.BlockSpec((1, 1, chunk, N), lambda bt, h, n: (bt, h // rep, n, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bt, h, n: (bt, h // rep, n, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda bt, h, n: (bt, h, n, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, a_cs[..., None], a_cs[:, :, None, :], b, c)
    return y.transpose(0, 2, 1, 3)
