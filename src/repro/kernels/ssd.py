"""SSD (Mamba-2 state-space duality) kernels: jnp chunked reference +
Pallas TPU intra-chunk kernel.

The SSD dual form is the paper's tiling idea applied along the *time*
axis: the sequence is chunked, intra-chunk terms are dense
(decay-masked) matmuls and inter-chunk terms are a rank-N state
recurrence. Chunking is mathematically exact — the chunk size is a
pure performance knob, which is what makes the op autotunable (the
execution chunk swept by `tuning.tune_ssd` can differ from the model's
configured chunk; only float rounding changes).

Two implementations share one contract
    (x (B,L,H,P), a (B,L,H), b (B,L,G,N), c (B,L,G,N), chunk,
     init_state (B,H,P,N) or None) -> (y (B,L,H,P) in x.dtype,
                                       final_state (B,H,P,N) f32)
and carry the inter-chunk state in f32 regardless of input dtype
(cast at the boundary), so bf16 runs agree across backends:

* `ssd_chunked` — the jnp composition (the xla backend and the VJP's
  unfused target). Everything is computed in f32.
* `ssd_pallas`  — intra-chunk work in the Pallas kernel below; the
  decay mask, the C·Bᵀ score matrix and the chunk-local output live in
  VMEM — exactly the tensors that dominate HBM traffic in the XLA
  lowering (EXPERIMENTS §SSD traffic accounting).

Per grid cell (Q = chunk, BP = head_dim tile, N = d_state), f32:
    cs    = cumsum(a)                      (Q,)
    L     = exp((cs_i - cs_j)[j <= i])     (Q, Q)   decay mask
    S     = (C Bᵀ) ⊙ L                     (Q, Q)   MXU matmul
    y     = S x                            (Q, BP)  MXU matmul
    state = (B ⊙ exp(cs_Q - cs))ᵀ x        (N, BP)  chunk state out

The inter-chunk recurrence (rank-N, tiny) and the state→output term
stay in jnp (they are O(L·N·P), not the bottleneck). The log-space
decay argument is masked *before* the exp (as `_segsum` does): the
upper triangle of cs_i - cs_j is positive and overflows to inf for
strong decays, which would NaN gradients through a post-exp `where`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, row_to_column


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """a: (..., Q) -> (..., Q, Q) with S[i,j] = sum_{j<m<=i} a[..., m],
    -inf above the diagonal (log-space decay mask)."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    s = cs[..., :, None] - cs[..., None, :]
    ii = jnp.arange(q)[:, None]
    jj = jnp.arange(q)[None, :]
    return jnp.where(jj <= ii, s, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,      # (B, L, H, P) — already dt-scaled
    a: jnp.ndarray,      # (B, L, H)    — dt * A (negative log-decay)
    b_: jnp.ndarray,     # (B, L, G, N)
    c_: jnp.ndarray,     # (B, L, G, N)
    chunk: int,
    init_state: Optional[jnp.ndarray] = None,   # (B, H, P, N)
):
    """Chunked jnp reference. Returns (y in x.dtype, final_state f32);
    all interior math — including the carried inter-chunk state — is
    f32, so bf16 inputs follow the same accumulation discipline as the
    Pallas kernel (f64 inputs keep f64 accumulation, like matmul_ref)."""
    bsz, l, h, p = x.shape
    g, n = b_.shape[-2:]
    rep = h // g
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    acc = jnp.float64 if x.dtype == jnp.float64 else jnp.float32

    xc = x.astype(acc).reshape(bsz, nc, chunk, h, p)
    ac = a.astype(acc).reshape(bsz, nc, chunk, h) \
        .transpose(0, 1, 3, 2)                                # (B,nc,H,Q)
    bc = jnp.repeat(
        b_.astype(acc).reshape(bsz, nc, chunk, g, n), rep, axis=3)
    cc = jnp.repeat(
        c_.astype(acc).reshape(bsz, nc, chunk, g, n), rep, axis=3)

    # 1. intra-chunk (dense blocked matmul with decay mask)
    ldec = jnp.exp(_segsum(ac))                               # (B,nc,H,Q,Q)
    cb = jnp.einsum("bcqhn,bcshn->bchqs", cc, bc)
    y_diag = jnp.einsum("bchqs,bcshp->bcqhp", cb * ldec, xc)

    # 2. per-chunk states
    a_cum = jnp.cumsum(ac, axis=-1)                           # (B,nc,H,Q)
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)           # (B,nc,H,Q)
    states = jnp.einsum("bcqhn,bchq,bcqhp->bchpn",
                        bc, decay_to_end, xc)                 # (B,nc,H,P,N)

    # 3. inter-chunk recurrence (f32 state, seeded by init_state)
    chunk_decay = jnp.exp(a_cum[..., -1])                     # (B,nc,H)
    s0 = (jnp.zeros((bsz, h, p, n), acc)
          if init_state is None else init_state.astype(acc))

    def step(s, inp):
        st, dec = inp
        return s * dec[..., None, None] + st, s               # emit state *before*

    (s_final, prev_states) = jax.lax.scan(
        step, s0,
        (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)                  # (B,nc,H,P,N)

    # 4. state -> output within each chunk
    state_decay = jnp.exp(a_cum)                              # (B,nc,H,Q)
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp",
                       cc, prev_states, state_decay)
    y = (y_diag + y_off).reshape(bsz, l, h, p).astype(x.dtype)
    return y, s_final


def _ssd_chunk_kernel(x_ref, cs_ref, b_ref, c_ref, y_ref, state_ref):
    q = x_ref.shape[2]
    x = x_ref[0, 0].astype(jnp.float32)       # (Q, BP)
    cs = cs_ref[0, 0]                         # (1, Q) f32 cumsum(a) row
    b = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    cs_col = row_to_column(cs)                # the same values, (Q, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # mask the log-space argument BEFORE exp: the upper triangle of
    # cs_i - cs_j is positive and overflows for strong decays, and a
    # post-exp where() would propagate NaN through the VJP.
    ldec = jnp.exp(jnp.where(jj <= ii, cs_col - cs, -jnp.inf))

    scores = jax.lax.dot_general(                     # C Bᵀ: (Q, Q)
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(                          # (S ⊙ L) x: (Q, BP)
        scores * ldec, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    decay_end = jnp.exp(cs_col[q - 1:] - cs_col)      # (Q, 1)
    state = jax.lax.dot_general(                      # Bᵀ diag(d) x: (N, BP)
        b * decay_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state.astype(state_ref.dtype)


def ssd_intra_chunk(
    x: jnp.ndarray,    # (BH, nc, Q, P) — dt-scaled inputs
    a: jnp.ndarray,    # (BH, nc, Q)    — dt*A log decays
    b: jnp.ndarray,    # (BH, nc, Q, N)
    c: jnp.ndarray,    # (BH, nc, Q, N)
    *,
    block_p: Optional[int] = None,
    interpret: bool = False,
):
    """Returns (y_diag (BH, nc, Q, P), states (BH, nc, N, P)).

    `block_p` tiles the head dim: each (bh, chunk, p-tile) grid cell
    recomputes the (Q, Q) decay mask and score matrix for its slice —
    smaller working set per cell at the price of redundant score
    compute; the autotuner decides (tuning/space.py::ssd_candidates).
    """
    bh, nc, q, p = x.shape
    n = b.shape[-1]
    bp = block_p or p
    if p % bp:
        bp = p
    grid = (bh, nc, p // bp)
    # The in-chunk cumulative decay is an XLA prefix sum: Mosaic has no
    # cumsum, and the kernel needs nothing of `a` but its running sum.
    cs = jnp.cumsum(a.astype(jnp.float32), axis=-1)
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, bp), lambda i, j, k: (i, j, 0, k)),
            # cumsum(a) as (BH, nc, 1, Q): a lane-dense (1, Q) block
            # satisfies the TPU (8, 128) rule on the last two dims
            pl.BlockSpec((1, 1, 1, q), lambda i, j, k: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j, k: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, bp), lambda i, j, k: (i, j, 0, k)),
            pl.BlockSpec((1, 1, n, bp), lambda i, j, k: (i, j, 0, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, n, p), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_intra_chunk",
        compiler_params=compiler_params("parallel", "parallel", "parallel"),
    )(x, cs.reshape(bh, nc, 1, q), b, c)


def ssd_pallas(
    x: jnp.ndarray,    # (B, L, H, P) — dt-scaled
    a: jnp.ndarray,    # (B, L, H)
    b_: jnp.ndarray,   # (B, L, G, N)
    c_: jnp.ndarray,   # (B, L, G, N)
    chunk: int,
    init_state: Optional[jnp.ndarray] = None,   # (B, H, P, N)
    *,
    block_p: Optional[int] = None,
    interpret: bool = False,
):
    """Drop-in for `ssd_chunked` (same contract, incl. `init_state`
    seeding the inter-chunk scan) with the intra-chunk work in the
    Pallas kernel. `chunk` here is the *execution* chunk — any divisor
    of L computes the same function."""
    bsz, l, h, p = x.shape
    g, n = b_.shape[-2:]
    rep = h // g
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    # (B, L, H, *) -> (B*H, nc, Q, *)
    xk = x.transpose(0, 2, 1, 3).reshape(bsz * h, nc, chunk, p)
    ak = a.transpose(0, 2, 1).reshape(bsz * h, nc, chunk)
    bk = jnp.repeat(b_, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(bsz * h, nc, chunk, n)
    ck = jnp.repeat(c_, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(bsz * h, nc, chunk, n)

    y_diag, states = ssd_intra_chunk(
        xk, ak, bk, ck, block_p=block_p, interpret=interpret)

    # inter-chunk recurrence in jnp (tiny rank-N state, carried f32)
    ac = ak.astype(jnp.float32).reshape(bsz, h, nc, chunk)
    a_cum = jnp.cumsum(ac, axis=-1)
    chunk_decay = jnp.exp(a_cum[..., -1])                  # (B,H,nc)
    states = states.reshape(bsz, h, nc, n, p)

    def step(s, inp):
        st, dec = inp
        return s * dec[..., None, None] + st, s
    # internal state layout is (N, P); the contract's is (B, H, P, N)
    s0 = (jnp.zeros((bsz, h, n, p), jnp.float32)
          if init_state is None
          else init_state.swapaxes(-1, -2).astype(jnp.float32))
    s_final, prev = jax.lax.scan(
        step, s0, (states.transpose(2, 0, 1, 3, 4),
                   chunk_decay.transpose(2, 0, 1)))
    prev = prev.transpose(1, 2, 0, 3, 4)                   # (B,H,nc,N,P)

    state_decay = jnp.exp(a_cum)                           # (B,H,nc,Q)
    ck5 = ck.astype(jnp.float32).reshape(bsz, h, nc, chunk, n)
    y_off = jnp.einsum("bhcqn,bhcnp,bhcq->bhcqp", ck5, prev, state_decay)
    y = y_diag.reshape(bsz, h, nc, chunk, p) + y_off
    y = y.reshape(bsz, h, l, p).transpose(0, 2, 1, 3)      # (B,L,H,P)
    # final state layout to match ssd_chunked: (B, H, P, N)
    return y.astype(x.dtype), s_final.swapaxes(-1, -2)
