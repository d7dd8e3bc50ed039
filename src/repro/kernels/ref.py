"""Pure-jnp oracles for the fused ABFT kernels.

``matmul_ref`` is the ground-truth GEMM.  ``abft_matmul_ref`` mirrors the
kernel's blocked accumulation order exactly (k-chunked f32 sums) so the
kernel's residual/bound outputs can be compared with tight tolerances.
``attention_ref`` is plain softmax attention for the flash kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def matmul_ref(x: jnp.ndarray, w: jnp.ndarray, out_dtype=None) -> jnp.ndarray:
    out_dtype = out_dtype or x.dtype
    return jnp.matmul(
        x.astype(F32), w.astype(F32), precision="highest"
    ).astype(out_dtype)


def _pad_to(a, m, n):
    return jnp.pad(a, ((0, m - a.shape[0]), (0, n - a.shape[1])))


def abft_matmul_ref(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    mode: str = "1s",
    bm: int,
    bk: int,
    bn: int,
    out_dtype=None,
):
    """Oracle for the padded kernel: returns (y, res, bnd) with the same
    shapes and (chunked) accumulation structure as the kernel."""
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    kw, n = w.shape
    assert k == kw
    assert m % bm == 0 and k % bk == 0 and n % bn == 0
    gm, gk, gn = m // bm, k // bk, n // bn

    xf = x.astype(F32).reshape(gm, bm, gk, bk)
    wf = w.astype(F32).reshape(gk, bk, gn, bn)

    # Main GEMM: per-(i,j) block accumulated over k chunks.
    # (gm, bm, gk, bk) x (gk, bk, gn, bn) -> (gm, bm, gn, bn)
    acc = jnp.einsum("aikb,kbcn->aicn", xf, wf,
                     preferred_element_type=F32, precision="highest")
    y2 = acc.reshape(m, n)
    y_mat = jnp.swapaxes(acc, 1, 2)  # (gm, gn, bm, bn)

    if mode == "2s":
        a_sum = xf.sum(axis=1)                      # (gm, gk, bk)
        b_sum = wf.sum(axis=3)                      # (gk, bk, gn)
        a_abs = jnp.abs(xf).sum(axis=1)
        b_abs = jnp.abs(wf).sum(axis=3)
        chk = jnp.einsum("agk,gkc->ac", a_sum, b_sum)       # (gm, gn)
        bnd = jnp.einsum("agk,gkc->ac", a_abs, b_abs)
        total = y_mat.sum(axis=(2, 3))                      # (gm, gn)
        res = jnp.abs(chk - total)
        return y2.astype(out_dtype), res, bnd

    # one-sided / replica: per-(i,j) block, per-row residual.
    b_sum = wf.sum(axis=3)                          # (gk, bk, gn)
    b_abs = jnp.abs(wf).sum(axis=3)
    chk = jnp.einsum("aikb,kbc->aic", xf, b_sum)    # (gm, bm, gn)
    bnd = jnp.einsum("aikb,kbc->aic", jnp.abs(xf), b_abs)
    rowsum = y_mat.sum(axis=3)                      # (gm, gn, bm)
    res = jnp.abs(chk.transpose(0, 2, 1) - rowsum)  # (gm, gn, bm)
    if mode == "replica":
        # replica recomputes the same product — residual is (numerically)
        # zero; the oracle reports zero.
        res = jnp.zeros_like(res)
        bnd = jnp.abs(y_mat).sum(axis=3)
        return y2.astype(out_dtype), res, bnd
    return y2.astype(out_dtype), res, bnd.transpose(0, 2, 1)


def attention_ref(q, k, v, *, causal: bool = True, lengths=None):
    """Softmax attention in f32.  q: (B, Lq, H, D); k/v: (B, Lk, KV,
    D[v]) with the H query heads grouped kv-major (head h reads kv head
    h // (H // KV)); ``causal`` aligns query i with key i; ``lengths``
    (B,) masks each row's keys at and past its length.  Returns
    (B, Lq, H, Dv) f32."""
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    qg = q.astype(F32).reshape(B, Lq, KV, H // KV, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(F32),
                   precision="highest") * D ** -0.5
    mask = jnp.ones((B, 1, 1, Lq, Lk), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((Lq, Lk), bool))
    if lengths is not None:
        valid = jnp.arange(Lk)[None, :] < jnp.asarray(lengths)[:, None]
        mask &= valid[:, None, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    o = jnp.einsum("bkgqs,bskv->bqkgv", p, v.astype(F32),
                   precision="highest")
    return o.reshape(B, Lq, H, v.shape[-1])
