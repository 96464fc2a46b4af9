"""Pure-jnp oracles for every Pallas kernel (used by the allclose tests)."""
from __future__ import annotations

import jax.numpy as jnp


def rmnp_rownorm_apply_ref(g, v, w, scale, wd, *, beta: float,
                           eps: float = 1e-8):
    """Single-pass fused apply: momentum EMA + row normalize + weight update.

    g: (..., d_in, d_out) fp32; v: fp32 or bf16 momentum storage; w: weights
    (math in fp32, returned in w.dtype); scale already folds lr *
    rms_lr_scale.  Op order matches the Pallas kernel exactly (update =
    -scale*(d + wd*w), then w + update).
    """
    w32 = w.astype(jnp.float32)
    v_new = beta * v.astype(jnp.float32) + (1.0 - beta) * g.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(jnp.square(v_new), axis=-2, keepdims=True))
    d = v_new / (norm + eps)
    w_new = w32 + (-scale) * (d + wd * w32)
    return v_new.astype(v.dtype), w_new.astype(w.dtype)


def matmul_ref(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def ns_step_ref(x, a: float, b: float, c: float):
    """One quintic Newton-Schulz iteration: a*X + (b*G + c*G@G) @ X, G = X X^T."""
    g = x @ x.T
    return a * x + (b * g + c * (g @ g)) @ x


def dominance_ref(v, eps: float = 1e-12):
    """(r_avg, r_min, r_max) of the Gram V^T V for stored (d_in, d_out) V."""
    gram = v.T @ v
    m = gram.shape[-1]
    diag = jnp.diagonal(gram)
    off = jnp.sum(jnp.abs(gram), axis=-1) - jnp.abs(diag)
    r = diag / (off / max(1, m - 1) + eps)
    return jnp.mean(r), jnp.min(r), jnp.max(r)


def chunked_attention_ref(q, k, v, *, causal: bool = True,
                          chunk_q: int = 512, chunk_k: int = 512):
    """Memory-efficient (online-softmax) attention oracle, pure jnp.

    q: (B,S,H,hd); k/v: (B,S,K,hd) GQA.  Matches dense softmax attention
    exactly; S^2 scores only ever exist as (chunk_q x chunk_k) tiles.
    Also serves as the recompute path for the Pallas kernel's backward.
    """
    import jax

    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    hdv = v.shape[-1]
    cq = min(chunk_q, S)
    ck = min(chunk_k, S)
    if S % cq:
        cq = S
    if S % ck:
        ck = S
    nq, nk = S // cq, S // ck
    qr = q.reshape(B, nq, cq, K, G, hd)
    kr = k.reshape(B, nk, ck, K, hd)
    vr = v.reshape(B, nk, ck, K, hdv)
    scale = 1.0 / (hd ** 0.5)

    outs = []
    for qi in range(nq):
        qb = qr[:, qi].astype(jnp.float32)
        acc = jnp.zeros((B, K, G, cq, hdv), jnp.float32)
        m = jnp.full((B, K, G, cq), -1e30, jnp.float32)
        ell = jnp.zeros((B, K, G, cq), jnp.float32)
        hi = ((qi + 1) * cq + ck - 1) // ck if causal else nk
        for ki in range(hi):
            kb = kr[:, ki].astype(jnp.float32)
            vb = vr[:, ki].astype(jnp.float32)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb) * scale
            if causal:
                qpos = qi * cq + jnp.arange(cq)
                kpos = ki * ck + jnp.arange(ck)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            ell = ell * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, vb)
            m = m_new
        out = acc / (ell[..., None] + 1e-30)
        outs.append(jnp.transpose(out, (0, 3, 1, 2, 4)))  # (B,cq,K,G,hdv)
    return (jnp.concatenate(outs, axis=1)
            .reshape(B, S, H, hdv).astype(q.dtype))
