"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernel tests assert_allclose against, and
the fallback implementation on backends without Pallas support.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.precision import mm


# ---------------------------------------------------------------------------
# blockgram: G = A @ A^T for a short-and-fat block (Ranky local gram)
# ---------------------------------------------------------------------------

def blockgram(a_blk: jnp.ndarray) -> jnp.ndarray:
    """(M, N) -> (M, M) gram in f32 accumulation."""
    a32 = a_blk.astype(jnp.float32)
    return mm(a32, a32.T)


# ---------------------------------------------------------------------------
# sparse_gram: G = E @ E^T from a padded-ELL sparse block (Ranky sparse path)
# ---------------------------------------------------------------------------

def sparse_gram(
    col_rows: jnp.ndarray, col_vals: jnp.ndarray, m: int
) -> jnp.ndarray:
    """(C, K) padded-ELL slots -> (M, M) gram in f32.

    Scatters the slots into the (C, M) stored-column panel and contracts
    over stored columns: G[r1, r2] = sum_c P[c, r1] P[c, r2].  Work and
    memory are nnz-proportional (C ~ stored columns), never M x W.
    Padding slots must carry val == 0 (the container builder guarantees
    it); duplicate (column, row) slots accumulate, matching the kernel.
    """
    c = col_rows.shape[0]
    p = jnp.zeros((c, m), jnp.float32).at[
        jnp.arange(c)[:, None], col_rows
    ].add(col_vals.astype(jnp.float32))
    return mm(p.T, p)


# ---------------------------------------------------------------------------
# sketch_panel: S = Omega @ E over stored columns (randomized range finder)
# ---------------------------------------------------------------------------

def sketch_panel(
    omega: jnp.ndarray, col_rows: jnp.ndarray, col_vals: jnp.ndarray
) -> jnp.ndarray:
    """(L, M) test matrix x (C, K) padded-ELL slots -> (L, C) panel.

    out[l, c] = sum_k omega[l, rows[c, k]] * vals[c, k] — the sketch
    ``Omega @ E`` of one sparse block restricted to its stored columns
    (callers scatter to (L, W) through col_ids).  Computed as an O(nnz*L)
    gather-and-reduce: no (M, W) or (C, M) intermediate, so it stays
    cheap even in the tall-row regime where M >> C.  Padding slots carry
    val == 0 and are inert; duplicate (column, row) slots accumulate.
    """
    gathered = jnp.take(omega.astype(jnp.float32), col_rows, axis=1)  # (L, C, K)
    return jnp.sum(gathered * col_vals.astype(jnp.float32)[None], axis=-1)


# ---------------------------------------------------------------------------
# topk_score: fused q . diag(s) V^T scoring + running top-k (serving path)
# ---------------------------------------------------------------------------

def topk_score(
    qs: jnp.ndarray,      # (B, k) queries with diag(s) already folded in
    v: jnp.ndarray,       # (N, k) right factors (f32 or int8)
    k_top: int,
    *,
    scale: Optional[jnp.ndarray] = None,  # (N,) per-item dequant scales
    valid_n=None,                          # rows >= valid_n are masked out
    index_offset=0,                        # added to returned indices
):
    """(B, k_top) top scores + indices of ``qs @ v.T`` (ground truth).

    The oracle materializes the full (B, N) score matrix — exactly what
    the fused kernel must never do — and selects with ``jax.lax.top_k``,
    whose documented tie rule (equal scores -> lowest index first, values
    in descending order) is the ONE selection semantics the kernel
    reproduces bit-for-bit.  ``scale`` folds per-item int8 dequantization
    into the score (score[b, j] = (qs[b] . v[j]) * scale[j]); ``valid_n``
    masks padding rows to -inf so they can never be selected (callers
    guarantee k_top <= valid rows and finite scores); ``valid_n`` and
    ``index_offset`` may be traced scalars (the sharded backend feeds
    per-device offsets).
    """
    scores = mm(qs.astype(jnp.float32), v.astype(jnp.float32).T)  # (B, N)
    if scale is not None:
        scores = scores * scale.astype(jnp.float32)[None, :]
    if valid_n is not None:
        cols = jnp.arange(v.shape[0])[None, :]
        scores = jnp.where(cols < valid_n, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, k_top)
    return vals, (idx + index_offset).astype(jnp.int32)


# ---------------------------------------------------------------------------
# flash_attention: fused causal/local GQA attention with optional softcap
# ---------------------------------------------------------------------------

def flash_attention(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Sk, D)
    v: jnp.ndarray,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = full; >0 = sliding window (gemma2 local layers)
    softcap: float = 0.0,  # 0 = off; >0 = tanh logit softcap
    scale: Optional[float] = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5

    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)

    qi = jnp.arange(sq)[:, None] + (sk - sq)  # right-aligned (decode prefix)
    ki = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= qi >= ki
    if window > 0:
        mask &= (qi - ki) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp (lax.scan over KV
    chunks).  Numerically identical to flash_attention but never
    materializes the (Sq, Sk) score matrix in HLO — this is what the
    models use on non-TPU backends (and what the dry-run lowers), so the
    roofline memory term reflects the kernel's true traffic.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    if sk % block_k:
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )

    q32 = q.astype(jnp.float32) * scale
    nblk = sk // block_k
    kc = jnp.moveaxis(k.reshape(b, hkv, nblk, block_k, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, hkv, nblk, block_k, d), 2, 0)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)

    def step(carry, inp):
        acc, m_run, l_run = carry
        ki, kb, vb = inp
        kb = jnp.repeat(kb.astype(jnp.float32), group, axis=1)
        vb = jnp.repeat(vb.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * block_k + jnp.arange(block_k)[None, :]
        mask = jnp.ones((sq, block_k), bool)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, sq, 1), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nblk), kc, vc)
    )
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def _flash_fwd_chunked(q32, k, v, *, causal, window, softcap, block_k, group):
    """Shared forward: returns (out_f32, lse).  q32 pre-scaled f32."""
    b, hq, sq, d = q32.shape
    sk = k.shape[2]
    nblk = sk // block_k
    kc = jnp.moveaxis(k.reshape(b, -1, nblk, block_k, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, -1, nblk, block_k, d), 2, 0)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)

    def step(carry, inp):
        acc, m_run, l_run = carry
        ki, kb, vb = inp
        kb = jnp.repeat(kb, group, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * block_k + jnp.arange(block_k)[None, :]
        mask = jnp.ones((sq, block_k), bool)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, sq, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nblk), kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    return acc / l, m + jnp.log(l)


def flash_attention_vjp(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Flash attention with a manual VJP that RECOMPUTES scores per KV
    chunk in the backward pass (saves only (out, lse) — exactly the
    Pallas/production recompute semantics).  Removes the O(S^2 / chunks)
    probability tensors the autodiff'd scan saves for backward."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    if sk % block_k or sq < 2:
        return chunked_flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, block_k=min(block_k, sk))

    @jax.custom_vjp
    def _attn(q, k, v):
        q32 = q.astype(jnp.float32) * scale
        out, _ = _flash_fwd_chunked(
            q32, k, v, causal=causal, window=window, softcap=softcap,
            block_k=block_k, group=group)
        return out.astype(q.dtype)

    def _fwd(q, k, v):
        q32 = q.astype(jnp.float32) * scale
        out, lse = _flash_fwd_chunked(
            q32, k, v, causal=causal, window=window, softcap=softcap,
            block_k=block_k, group=group)
        return out.astype(q.dtype), (q, k, v, out, lse)

    def _bwd(res, dout):
        q, k, v, out, lse = res
        q32 = q.astype(jnp.float32) * scale
        do = dout.astype(jnp.float32)
        delta = jnp.sum(do * out, axis=-1, keepdims=True)  # (B,Hq,Sq,1)
        nblk = sk // block_k
        kc = jnp.moveaxis(k.reshape(b, hkv, nblk, block_k, d), 2, 0)
        vc = jnp.moveaxis(v.reshape(b, hkv, nblk, block_k, d), 2, 0)
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)

        def step(dq_acc, inp):
            ki, kb, vb = inp
            kb32 = jnp.repeat(kb, group, axis=1).astype(jnp.float32)
            vb32 = jnp.repeat(vb, group, axis=1).astype(jnp.float32)
            s_raw = jnp.einsum("bhqd,bhkd->bhqk", q32, kb32)
            if softcap > 0.0:
                s_cap = softcap * jnp.tanh(s_raw / softcap)
            else:
                s_cap = s_raw
            k_pos = ki * block_k + jnp.arange(block_k)[None, :]
            mask = jnp.ones((sq, block_k), bool)
            if causal:
                mask &= q_pos >= k_pos
            if window > 0:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask[None, None], s_cap, -1e30)
            p = jnp.exp(s - lse)                        # (B,Hq,Sq,block_k)
            dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, do)
            dp = jnp.einsum("bhqd,bhkd->bhqk", do, vb32)
            ds = p * (dp - delta)
            if softcap > 0.0:
                # d(tanh)/ds_raw from the UNMASKED capped score (masked
                # entries already have p == 0 -> ds == 0)
                ds = ds * (1.0 - jnp.square(s_cap / softcap))
            ds = jnp.where(mask[None, None], ds, 0.0)
            dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kb32) * scale
            dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
            # fold grouped q-heads back into their kv head
            dk_c = dk_c.reshape(b, hkv, group, block_k, d).sum(axis=2)
            dv_c = dv_c.reshape(b, hkv, group, block_k, d).sum(axis=2)
            return dq_acc, (dk_c, dv_c)

        dq0 = jnp.zeros((b, hq, sq, d), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(
            step, dq0, (jnp.arange(nblk), kc, vc))
        dk = jnp.moveaxis(dks, 0, 2).reshape(b, hkv, sk, d)
        dv = jnp.moveaxis(dvs, 0, 2).reshape(b, hkv, sk, d)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    _attn.defvjp(_fwd, _bwd)
    return _attn(q, k, v)


# ---------------------------------------------------------------------------
# ssd_scan: Mamba-2 state-space-duality recurrence (sequential oracle)
# ---------------------------------------------------------------------------

def ssd_scan(
    x: jnp.ndarray,   # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H) post-softplus step sizes
    a: jnp.ndarray,   # (H,) negative decay rates (A in mamba2)
    b_mat: jnp.ndarray,  # (B, L, G, N) input projections
    c_mat: jnp.ndarray,  # (B, L, G, N) output projections
    *,
    h0: Optional[jnp.ndarray] = None,  # (B, H, P, N) initial state
    return_state: bool = False,
):
    """Sequential SSD: h_t = exp(dt_t a_h) h_{t-1} + (dt_t x_t) outer B_t;
    y_t = h_t @ C_t.  Heads share B/C within groups of size H//G."""
    bsz, seq, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    b32 = jnp.repeat(b_mat.astype(jnp.float32), rep, axis=2)  # (B, L, H, N)
    c32 = jnp.repeat(c_mat.astype(jnp.float32), rep, axis=2)

    decay = jnp.exp(dt32 * a.astype(jnp.float32)[None, None, :])  # (B, L, H)

    def step(h_prev, t):
        xt, dtt, bt, ct, at = t
        # h: (B, H, P, N)
        upd = (dtt[..., None, None] * xt[..., :, None]) * bt[..., None, :]
        h_new = at[..., None, None] * h_prev + upd
        y = jnp.einsum("bhpn,bhn->bhp", h_new, ct)
        return h_new, y

    init = (
        jnp.zeros((bsz, h, p, n), jnp.float32)
        if h0 is None
        else h0.astype(jnp.float32)
    )
    xs = (
        jnp.moveaxis(x32, 1, 0),
        jnp.moveaxis(dt32, 1, 0),
        jnp.moveaxis(b32, 1, 0),
        jnp.moveaxis(c32, 1, 0),
        jnp.moveaxis(decay, 1, 0),
    )
    h_fin, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)  # (B, L, H, P)
    if return_state:
        return y, h_fin
    return y


def ssd_scan_chunked(
    x: jnp.ndarray,   # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H)
    a: jnp.ndarray,   # (H,)
    b_mat: jnp.ndarray,  # (B, L, G, N)
    c_mat: jnp.ndarray,  # (B, L, G, N)
    *,
    chunk: int = 128,
    return_state: bool = True,
):
    """Chunked SSD in pure jnp — the structural twin of the Pallas kernel
    (kernels/ssd_scan.py): lax.scan over L/chunk chunks carrying only the
    (B, H, P, N) state; intra-chunk work is three MXU-shaped matmuls.

    vs the per-timestep oracle this changes the backward-pass residuals
    from O(L) per-step states to O(L/chunk) per-chunk states — the
    REPRO_PERF=ssd_chunked hillclimb lever.
    """
    bsz, seq, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    if seq % chunk:
        return ssd_scan(x, dt, a, b_mat, c_mat, return_state=return_state)
    nchunks = seq // chunk

    x32 = x.astype(jnp.float32).reshape(bsz, nchunks, chunk, h, p)
    dt32 = dt.astype(jnp.float32).reshape(bsz, nchunks, chunk, h)
    b32 = b_mat.astype(jnp.float32).reshape(bsz, nchunks, chunk, g, n)
    c32 = c_mat.astype(jnp.float32).reshape(bsz, nchunks, chunk, g, n)
    a32 = a.astype(jnp.float32)

    ii = jnp.arange(chunk)[:, None]
    jj = jnp.arange(chunk)[None, :]
    tri = ii >= jj

    def step(h_prev, inp):
        xc, dtc, bc, cc = inp            # (B, chunk, H, P) etc (chunk first moved)
        seg = dtc * a32[None, None, :]   # (B, Q, H)
        la = jnp.cumsum(seg, axis=1)     # (B, Q, H)
        br = jnp.repeat(bc, rep, axis=2)  # (B, Q, H, N)
        cr = jnp.repeat(cc, rep, axis=2)
        cb = jnp.einsum("bihn,bjhn->bhij", cr, br)        # (B, H, Q, Q)
        decay = jnp.exp(la[:, :, None] - la[:, None, :])  # (B, Q, Q, H)
        decay = jnp.where(tri[None, :, :, None], decay, 0.0)
        scores = cb * jnp.moveaxis(decay, 3, 1) * \
            jnp.moveaxis(dtc, 1, 2)[:, :, None, :]        # (B, H, Q, Q)
        y_intra = jnp.einsum("bhij,bjhp->bihp", scores, xc)
        # inter-chunk: carried state contribution
        ch = jnp.einsum("bihn,bhpn->bihp", cr, h_prev)
        y = y_intra + jnp.exp(la)[..., None] * ch
        # state update
        w = jnp.exp(la[:, -1:, :] - la) * dtc             # (B, Q, H)
        upd = jnp.einsum("bihp,bihn->bhpn", xc * w[..., None], br)
        h_new = jnp.exp(la[:, -1, :])[:, :, None, None] * h_prev + upd
        return h_new, y

    h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x32, 1, 0), jnp.moveaxis(dt32, 1, 0),
          jnp.moveaxis(b32, 1, 0), jnp.moveaxis(c32, 1, 0))
    h_fin, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, seq, h, p).astype(x.dtype)
    if return_state:
        return y, h_fin
    return y
