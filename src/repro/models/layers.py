"""Shared transformer layer primitives: RMSNorm, RoPE, GQA + MLA attention
(dense / flash kernel / chunked / decode paths), SwiGLU FFN.

Shape conventions: activations (B, S, D); per-head tensors (B, S, H, hd);
all matmul weights stored (..., d_in, d_out).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.distributed.sharding import current_mesh, logical
from repro.kernels.flash_attention import (block_sizes, flash_attention,
                                           flash_route)

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple           # logical axis names, len == len(shape)
    init: str = "fan_in"  # fan_in | normal | zeros | ones
    scale: float = 1.0
    dtype: Optional[str] = None  # None => model dtype (caches: fp32 for states)


def materialize(spec: ParamSpec, key, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "normal":
        return (spec.scale * jax.random.normal(key, spec.shape)).astype(dtype)
    # fan_in: last-2 dim is d_in
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    std = spec.scale / (fan_in ** 0.5)
    return (std * jax.random.normal(key, spec.shape)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / RoPE
# ---------------------------------------------------------------------------

def _rms_norm_raw(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with a bf16-discipline backward (EXPERIMENTS.md §Perf).

    Autodiff through the f32 internals materializes f32 cotangent chains
    for the whole residual stream (2x HBM traffic + f32 partial-sum
    all-reduces in the sharded matmul backward).  The handwritten VJP
    keeps reductions in f32 but emits the activation cotangent in the
    activation dtype."""
    return _rms_norm_raw(x, scale, eps)


def _rms_norm_fwd(x, scale, eps):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    out = ((xf * inv) * scale.astype(jnp.float32)).astype(x.dtype)
    return out, (x, inv, scale)


def _rms_norm_bwd(eps, res, g):
    x, inv, scale = res
    sf = scale.astype(jnp.float32)
    # one reduce kernel (reads x, g bf16 -> (B,S,1) f32):
    mean_gsx = jnp.mean((g.astype(jnp.float32) * sf) * x.astype(jnp.float32),
                        axis=-1, keepdims=True)
    c = (inv * inv * inv) * mean_gsx                     # (B,S,1) f32, tiny
    # one elementwise kernel (reads x, g bf16 + tiny f32 rows, writes bf16;
    # f32 lives in registers only — no (B,S,D) f32 materialization):
    dx = (g.astype(jnp.float32) * (sf * inv)
          - x.astype(jnp.float32) * c).astype(x.dtype)
    dscale = jnp.sum(g.astype(jnp.float32) * x.astype(jnp.float32) * inv,
                     axis=tuple(range(g.ndim - 1))).astype(scale.dtype)
    return dx, dscale


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _rope_rotate(x: jax.Array, positions: jax.Array, theta: float,
                 sign: float) -> jax.Array:
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = sign * jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32.  RoPE is a rotation, so
    its VJP is the inverse rotation — handwritten so the cotangent stays
    in the activation dtype (see rms_norm)."""
    return _rope_rotate(x, positions, theta, 1.0)


def _rope_fwd(x, positions, theta):
    return _rope_rotate(x, positions, theta, 1.0), positions


def _rope_bwd(theta, positions, g):
    # g has the primal's dtype; the inverse rotation emits the same dtype
    return _rope_rotate(g, positions, theta, -1.0), None


apply_rope.defvjp(_rope_fwd, _rope_bwd)


# ---------------------------------------------------------------------------
# Attention math
# ---------------------------------------------------------------------------

_FLASH_THRESHOLD = 8192  # off the flash kernels: chunked attention from this S
_Q_CHUNK = 2048
_KV_CHUNK = 2048


def _dense_attention(q, k, v, causal: bool, q_offset: int = 0):
    """q: (B,Sq,H,hd); k/v: (B,Skv,K,hd) with H % K == 0. Returns (B,Sq,H,hdv)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    q = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _chunked_attention(q, k, v, causal: bool, qc: int, kc: int):
    """Blockwise online-softmax attention (flash-style, XLA level).

    Perf structure (see EXPERIMENTS.md §Perf):
      * Python loop over q blocks (static index) so each block's causal kv
        scan has a *static* bound — no wasted MXU work on masked blocks
        (vs scanning all nk: ~2x flops for causal).
      * kv-step body under jax.checkpoint: the (qc x kc) probability tiles
        are recomputed in backward, never saved — activation traffic drops
        from O(S^2) to O(S^2 * kc / S) live at a time.
      * probabilities cast to the value dtype (bf16) before the PV matmul.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    hdv = v.shape[-1]
    qc, kc = min(qc, S), min(kc, S)
    if S % qc:
        qc = S
    if S % kc:
        kc = S
    nq, nk = S // qc, S // kc
    # Broadcast KV to full heads: a (K, G) split defeats GSPMD's head
    # sharding (model axis rarely divides K alone), replicating the whole
    # attention 16x.  Repeating KV costs O(S*hd) extra reads but lets the
    # flat H axis shard cleanly; every tile below is annotated so the
    # (qc x kc) score tiles stay head-sharded.
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    head_axes = ("batch", None, "heads", None)
    q = logical(q, head_axes)
    k = logical(k, head_axes)
    v = logical(v, head_axes)
    qr = q.reshape(B, nq, qc, H, hd)
    kr = k.reshape(B, nk, kc, H, hd)
    vr = v.reshape(B, nk, kc, H, hdv)
    scale = 1.0 / (hd ** 0.5)
    tile_axes = ("batch", "heads", None, None)

    def kv_step_factory(qi):
        def kv_step(carry, inp):
            acc, m, l = carry
            kb, vb, ki = inp
            qb = qr[:, qi]
            s = jnp.einsum("bqhd,bshd->bhqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            s = logical(s, tile_axes)
            if causal:
                qpos = qi * qc + jnp.arange(qc)
                kpos = ki * kc + jnp.arange(kc)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (logical(acc_new, ("batch", "heads", None, None)),
                    m_new, l_new), None
        return jax.checkpoint(kv_step)

    blocks = []
    for qi in range(nq):
        acc0 = jnp.zeros((B, H, qc, hdv), jnp.float32)
        m0 = jnp.full((B, H, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        hi = ((qi + 1) * qc + kc - 1) // kc if causal else nk
        xs = (kr[:, :hi].swapaxes(0, 1), vr[:, :hi].swapaxes(0, 1),
              jnp.arange(hi))
        (acc, m, l), _ = jax.lax.scan(kv_step_factory(qi), (acc0, m0, l0), xs)
        out = acc / (l[..., None] + 1e-30)
        blocks.append(jnp.transpose(out, (0, 2, 1, 3)))  # (B,qc,H,hdv)
    out = jnp.concatenate(blocks, axis=1)
    return logical(out.astype(q.dtype), ("batch", None, "heads", None))


_routes = threading.local()


@contextlib.contextmanager
def recording_attention_routes() -> Iterator[Dict[str, str]]:
    """Collect the route of every ``impl="auto"`` attention call traced
    inside the block: ``"attention (B,S,H,hd)"`` -> ``"flash"``, or
    ``"dense: <why not flash>"`` / ``"chunked: <why not flash>"``."""
    log: Dict[str, str] = {}
    outer = getattr(_routes, "log", None)
    _routes.log = log
    try:
        yield log
    finally:
        _routes.log = outer


def attention_route(q, k, v, causal: bool,
                    q_offset: int = 0) -> Tuple[str, str]:
    """``impl="auto"``'s choice for these shapes: ``("flash", "")``, or
    the XLA path (chunked self-attention from ``_FLASH_THRESHOLD``, dense
    below) with the reason the flash kernels' rule
    (``kernels/flash_attention.py:flash_route``) gave."""
    S, Skv = q.shape[1], k.shape[1]
    mesh = current_mesh()
    why = flash_route(jax.default_backend(), S, Skv, q_offset, q.shape[-1],
                      v.shape[-1], causal, mesh.size if mesh else 1)
    if why is True:
        return "flash", ""
    if S >= _FLASH_THRESHOLD and S == Skv:
        return "chunked", why
    return "dense", why


def attention(q, k, v, causal=True, q_offset=0, impl: str = "auto",
              chunk_q: int = _Q_CHUNK, chunk_k: int = _KV_CHUNK):
    """impl: auto | dense | chunked | pallas.  "auto" = the Pallas flash
    kernels where :func:`attention_route` sends the shape (TPU), else
    chunked above the S threshold and dense below; "pallas" = the flash
    kernels at ``chunk_q``/``chunk_k`` blocks (TPU; runs in interpret mode
    elsewhere -- tests only)."""
    S = q.shape[1]
    if impl == "pallas":
        interp = jax.default_backend() != "tpu"
        return flash_attention(q, k, v, causal, min(chunk_q, S),
                               min(chunk_k, S), interp)
    if impl == "auto":
        route, why = attention_route(q, k, v, causal, q_offset)
        log = getattr(_routes, "log", None)
        if log is not None:
            B, _, H, hd = q.shape
            log[f"attention ({B},{S},{H},{hd})"] = (
                route + (f": {why}" if why else ""))
        if route == "flash":
            return flash_attention(q, k, v, causal,
                                   *block_sizes(S, q.shape[-1]), False)
        impl = route
    if impl == "chunked" and S == k.shape[1]:  # self-attention only
        return _chunked_attention(q, k, v, causal, chunk_q, chunk_k)
    return _dense_attention(q, k, v, causal, q_offset)


def decode_attention(q, k_cache, v_cache, pos):
    """q: (B,1,H,hd); caches (B,S,K,hd); attend to positions <= pos."""
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    G = H // K
    S = k_cache.shape[1]
    qf = q.reshape(B, K, G, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qf, k_cache,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    mask = jnp.arange(S) <= pos
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs = {
        "norm": ParamSpec((d,), ("embed",), "ones"),
        "wq": ParamSpec((d, H * hd), ("d_in", "heads")),
        "wk": ParamSpec((d, K * hd), ("d_in", "heads")),
        "wv": ParamSpec((d, K * hd), ("d_in", "heads")),
        "wo": ParamSpec((H * hd, d), ("heads", "d_in")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), (None,), "ones")
        specs["k_norm"] = ParamSpec((hd,), (None,), "ones")
    return specs


def gqa_cache_specs(cfg: ModelConfig, batch: int, seq: int):
    K, hd = cfg.n_kv_heads, cfg.head_dim
    kv_seq = "long_seq" if batch == 1 else "kv_seq"
    return {
        "k": ParamSpec((batch, seq, K, hd), ("batch", kv_seq, "kv_heads", None), "zeros"),
        "v": ParamSpec((batch, seq, K, hd), ("batch", kv_seq, "kv_heads", None), "zeros"),
    }


def gqa_apply(cfg: ModelConfig, p, x, positions, mode: str,
              cache=None, pos=None):
    """Returns (y, new_cache)."""
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (h @ p["wk"]).reshape(B, S, K, hd)
    v = (h @ p["wv"]).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, ("batch", "seq", "heads", None))

    new_cache = None
    if mode == "decode":
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, pos, 0, 0))
        out = decode_attention(q, kc, vc, pos)
        new_cache = {"k": kc, "v": vc}
    else:
        out = attention(q, k, v, causal=True, impl=cfg.attn_impl,
                        chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k)
        if mode == "prefill":
            new_cache = {"k": k.astype(x.dtype), "v": v.astype(x.dtype)}
    y = out.reshape(B, S, H * hd) @ p["wo"]
    return logical(y, ("batch", "res_seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# MLA attention layer (DeepSeek-V2 style; cache stores the compressed latent)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, H = cfg.d_model, cfg.n_heads
    m: MLAConfig = cfg.mla
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    specs = {"norm": ParamSpec((d,), ("embed",), "ones")}
    if m.q_lora_rank:
        specs["wq_a"] = ParamSpec((d, m.q_lora_rank), ("d_in", "lora"))
        specs["q_a_norm"] = ParamSpec((m.q_lora_rank,), (None,), "ones")
        specs["wq_b"] = ParamSpec((m.q_lora_rank, H * qk_dim), ("lora", "heads"))
    else:
        specs["wq"] = ParamSpec((d, H * qk_dim), ("d_in", "heads"))
    specs["wkv_a"] = ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("d_in", "lora"))
    specs["kv_a_norm"] = ParamSpec((m.kv_lora_rank,), (None,), "ones")
    specs["wkv_b"] = ParamSpec(
        (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)), ("lora", "heads"))
    specs["wo"] = ParamSpec((H * m.v_head_dim, d), ("heads", "d_in"))
    return specs


def mla_cache_specs(cfg: ModelConfig, batch: int, seq: int):
    m = cfg.mla
    kv_seq = "long_seq" if batch == 1 else "kv_seq"
    return {
        "ckv": ParamSpec((batch, seq, m.kv_lora_rank), ("batch", kv_seq, "lora"), "zeros"),
        "k_rope": ParamSpec((batch, seq, m.qk_rope_head_dim), ("batch", kv_seq, None), "zeros"),
    }


def _mla_qkv(cfg, p, h, positions):
    B, S, _ = h.shape
    H = cfg.n_heads
    m = cfg.mla
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        q = rms_norm(h @ p["wq_a"], p["q_a_norm"], cfg.rms_eps) @ p["wq_b"]
    else:
        q = h @ p["wq"]
    q = q.reshape(B, S, H, qk_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = h @ p["wkv_a"]
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_a_norm"], cfg.rms_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def _mla_expand_kv(cfg, p, ckv, k_rope):
    """Expand latent cache into per-head k/v."""
    B, S, _ = ckv.shape
    H = cfg.n_heads
    m = cfg.mla
    kv = (ckv @ p["wkv_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    return k, v


def mla_apply(cfg: ModelConfig, p, x, positions, mode: str, cache=None, pos=None):
    B, S, d = x.shape
    H = cfg.n_heads
    m = cfg.mla
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    q_nope, q_rope, ckv, k_rope = _mla_qkv(cfg, p, h, positions)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = logical(q, ("batch", "seq", "heads", None))

    new_cache = None
    if mode == "decode":
        ckv_c = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        kr_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, pos, 0))
        k, v = _mla_expand_kv(cfg, p, ckv_c, kr_c)
        out = decode_attention(q, k, v, pos)
        new_cache = {"ckv": ckv_c, "k_rope": kr_c}
    else:
        k, v = _mla_expand_kv(cfg, p, ckv, k_rope)
        out = attention(q, k, v, causal=True, impl=cfg.attn_impl,
                        chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k)
        if mode == "prefill":
            new_cache = {"ckv": ckv.astype(x.dtype), "k_rope": k_rope.astype(x.dtype)}
    y = out.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    return logical(y, ("batch", "res_seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------

def ffn_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    return {
        "norm": ParamSpec((d,), ("embed",), "ones"),
        "w_in": ParamSpec((d, 2 * ff), ("d_in", "mlp")),   # fused [gate; up]
        "w_out": ParamSpec((ff, d), ("mlp", "d_in")),
    }


def ffn_apply(cfg: ModelConfig, p, x):
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    gu = h @ p["w_in"]
    gate, up = jnp.split(gu, 2, axis=-1)
    # silu in the activation dtype: bf16 silu is standard practice and
    # avoids (B, S, d_ff)-sized f32 round-trips fwd + bwd (§Perf A6)
    y = jax.nn.silu(gate) * up
    y = logical(y, ("batch", "seq", "mlp"))
    return logical(y @ p["w_out"], ("batch", "res_seq", "embed"))
