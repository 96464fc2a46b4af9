"""Model assembly: pattern-driven block stacks (scan over repeating layer
units), token/frontend embeddings, LM head, loss, KV/SSM caches.

A config's per-layer ``pattern`` is decomposed as  prefix + unit * n_units
(e.g. Jamba: unit of 8 layers scanned 4x; DeepSeek-V2: 1 dense-FFN prefix
layer + 26 scanned MoE layers).  Scanning keeps the HLO small and compile
times bounded at 62-layer scale.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import logical
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

MIXERS = {
    "gqa": (L.gqa_specs, L.gqa_apply, L.gqa_cache_specs),
    "mla": (L.mla_specs, L.mla_apply, L.mla_cache_specs),
    "mamba": (S.mamba_specs, S.mamba_apply, S.mamba_cache_specs),
    "mlstm": (S.mlstm_specs, S.mlstm_apply, S.mlstm_cache_specs),
    "slstm": (S.slstm_specs, S.slstm_apply, S.slstm_cache_specs),
}


# ---------------------------------------------------------------------------
# Stack planning
# ---------------------------------------------------------------------------

def plan_stack(pattern) -> Tuple[int, int, int]:
    """Return (prefix_len, unit_len, n_units) with pattern == prefix + unit*n."""
    n = len(pattern)
    best = (n, 1, 0)  # fully-unrolled fallback: all layers in the prefix
    best_p = n + 1
    for q in range(0, min(3, n)):
        rest = pattern[q:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == tuple(rest[:p]) * (len(rest) // p):
                if p < best_p:
                    best, best_p = (q, p, len(rest) // p), p
                break
    return best


def _layer_specs(cfg: ModelConfig, mixer: str, ffn: str) -> Dict[str, Any]:
    specs = {"mixer": MIXERS[mixer][0](cfg)}
    if ffn == "dense":
        specs["ffn"] = L.ffn_specs(cfg)
    elif ffn == "moe":
        specs["ffn"] = M.moe_specs(cfg)
    return specs


def _stack_spec(spec: L.ParamSpec, n_units: int) -> L.ParamSpec:
    return L.ParamSpec((n_units,) + spec.shape, ("layers",) + tuple(spec.axes),
                       spec.init, spec.scale, spec.dtype)


def build_param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.padded_vocab
    q, p, n = plan_stack(cfg.pattern)
    specs: Dict[str, Any] = {
        "embed": {"tokens": L.ParamSpec((V, d), ("vocab", "embed"), "normal", 0.02)},
        "final_norm": L.ParamSpec((d,), ("embed",), "ones"),
    }
    for i in range(q):
        mixer, ffn = cfg.pattern[i]
        specs[f"prefix_{i}"] = _layer_specs(cfg, mixer, ffn)
    if n:
        unit = {}
        for j in range(p):
            mixer, ffn = cfg.pattern[q + j]
            unit[f"layer_{j}"] = _layer_specs(cfg, mixer, ffn)
        specs["stack"] = jax.tree_util.tree_map(
            lambda sp: _stack_spec(sp, n), unit,
            is_leaf=lambda x: isinstance(x, L.ParamSpec))
    if not cfg.tie_embeddings:
        specs["lm_head"] = L.ParamSpec((d, V), ("d_in", "vocab"), "fan_in")
    return specs


def build_cache_specs(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    q, p, n = plan_stack(cfg.pattern)
    specs: Dict[str, Any] = {}
    for i in range(q):
        mixer, _ = cfg.pattern[i]
        specs[f"prefix_{i}"] = MIXERS[mixer][2](cfg, batch, seq)
    if n:
        unit = {}
        for j in range(p):
            mixer, _ = cfg.pattern[q + j]
            unit[f"layer_{j}"] = MIXERS[mixer][2](cfg, batch, seq)
        specs["stack"] = jax.tree_util.tree_map(
            lambda sp: _stack_spec(sp, n), unit,
            is_leaf=lambda x: isinstance(x, L.ParamSpec))
    return specs


def _tree_materialize(specs, key, dtype):
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, L.ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [L.materialize(sp, k, dtype) for sp, k in zip(leaves, keys, strict=False)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    return _tree_materialize(build_param_specs(cfg), key, jnp.dtype(cfg.dtype))


def init_cache(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    specs = build_cache_specs(cfg, batch, seq)
    return jax.tree_util.tree_map(
        lambda sp: jnp.zeros(sp.shape, jnp.dtype(sp.dtype or cfg.dtype)),
        specs, is_leaf=lambda x: isinstance(x, L.ParamSpec))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _apply_layer(cfg, mixer, ffn, p, x, positions, mode, cache, pos):
    out, new_cache = MIXERS[mixer][1](cfg, p["mixer"], x, positions, mode, cache, pos)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if ffn == "dense":
        x = x + L.ffn_apply(cfg, p["ffn"], x)
    elif ffn == "moe":
        y, aux = M.moe_apply(cfg, p["ffn"], x)
        x = x + y
    return x, new_cache, aux


_REMAT_POLICIES = {
    "full": None,  # save nothing
    "dots": "dots_saveable",
    "none": "everything_saveable",
}


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], mode: str,
            cache=None, pos=None, remat: str = "full",
            return_hidden: bool = False):
    """mode: train | prefill | decode.  Returns (logits, new_cache, aux);
    with ``return_hidden`` the first element is the final-norm hidden state
    (the caller applies the LM head, e.g. chunked in loss_fn)."""
    q, p, n = plan_stack(cfg.pattern)

    tokens = batch.get("tokens")
    if cfg.frontend == "audio_frames" and mode != "decode" and "frames" in batch:
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
        B, Sq_len = x.shape[0], x.shape[1]
    else:
        B, Sq_len = tokens.shape
        x = params["embed"]["tokens"][tokens]
        if cfg.frontend == "vision" and mode != "decode" and "vision_embeds" in batch:
            nf = batch["vision_embeds"].shape[1]
            x = jnp.concatenate(
                [batch["vision_embeds"].astype(x.dtype), x[:, nf:]], axis=1)
    x = logical(x, ("batch", "res_seq", "embed"))

    if mode == "decode":
        positions = jnp.full((B, 1), pos, jnp.int32)
    else:
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(Sq_len, dtype=jnp.int32), (B, Sq_len))

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    # --- prefix layers (unrolled) ---------------------------------------
    for i in range(q):
        mixer, ffn = cfg.pattern[i]
        c = cache.get(f"prefix_{i}") if cache else None
        x, nc, aux = _apply_layer(cfg, mixer, ffn, params[f"prefix_{i}"],
                                  x, positions, mode, c, pos)
        aux_total = aux_total + aux
        if nc is not None:
            new_cache[f"prefix_{i}"] = nc

    # --- scanned stack ----------------------------------------------------
    if n:
        unit_kinds = [cfg.pattern[q + j] for j in range(p)]

        def apply_unit(x_in, aux_in, unit_params, unit_cache):
            ncs = {}
            xcur = x_in
            a = aux_in
            for j, (mixer, ffn) in enumerate(unit_kinds):
                cj = unit_cache[f"layer_{j}"] if unit_cache is not None else None
                xcur, nc, aux = _apply_layer(
                    cfg, mixer, ffn, unit_params[f"layer_{j}"],
                    xcur, positions, mode, cj, pos)
                a = a + aux
                if nc is not None:
                    ncs[f"layer_{j}"] = nc
            return xcur, a, (ncs if ncs else None)

        if cache is not None:
            # decode: cache rides in the carry and is updated in place at the
            # unit index — lets XLA alias the (donated) cache buffers instead
            # of copying the whole stack through scan xs/ys.
            def unit_body(carry, xs):
                x_in, aux_in, cache_all = carry
                unit_params, idx = xs
                unit_cache = jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0, keepdims=False),
                    cache_all)
                xcur, a, ncs = apply_unit(x_in, aux_in, unit_params, unit_cache)
                cache_all = jax.tree_util.tree_map(
                    lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                        c, nc.astype(c.dtype), idx, 0), cache_all, ncs)
                return (xcur, a, cache_all), None

            xs = (params["stack"], jnp.arange(n, dtype=jnp.int32))
            (x, aux_total, stack_caches), _ = jax.lax.scan(
                unit_body, (x, aux_total, cache["stack"]), xs)
            new_cache["stack"] = stack_caches
        else:
            def unit_body(carry, unit_params):
                x_in, aux_in = carry
                xcur, a, ncs = apply_unit(x_in, aux_in, unit_params, None)
                return (xcur, a), ncs

            body = unit_body
            if mode == "train":
                policy_name = _REMAT_POLICIES.get(remat, None)
                policy = (getattr(jax.checkpoint_policies, policy_name)
                          if policy_name else None)
                body = jax.checkpoint(unit_body, policy=policy)

            (x, aux_total), stack_caches = jax.lax.scan(
                body, (x, aux_total), params["stack"])
            if stack_caches is not None:
                new_cache["stack"] = stack_caches

    # --- head ---------------------------------------------------------------
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    if return_hidden:
        return x, (new_cache if new_cache else None), aux_total
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tokens"].T
    else:
        logits = x @ params["lm_head"]
    logits = logical(logits, ("batch", "seq", "vocab"))
    return logits, (new_cache if new_cache else None), aux_total


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

_LOSS_CHUNK = 1024


def _ce_terms(logits, labels, mask):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask)


def loss_fn(cfg: ModelConfig, params, batch, remat: str = "full"):
    """Cross-entropy with the LM head applied in sequence chunks so the full
    (B, S, V) fp32 logits tensor is never materialized (the head matmul is
    recomputed in the backward pass via jax.checkpoint).  Runs under the
    ``forward`` scope, so each compiled instruction's ``op_name`` says
    forward, or ``transpose(jvp(forward))`` for its gradient."""
    with jax.named_scope("forward"):
        hidden, _, aux = forward(cfg, params, batch, "train", remat=remat,
                                 return_hidden=True)
        head = (params["embed"]["tokens"].T if cfg.tie_embeddings
                else params["lm_head"])
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels_c = jnp.maximum(labels, 0)
        B, S, _ = hidden.shape

        if S % _LOSS_CHUNK == 0 and S > _LOSS_CHUNK:
            nchunk = S // _LOSS_CHUNK
            hs = jnp.moveaxis(hidden.reshape(B, nchunk, _LOSS_CHUNK, -1), 1, 0)
            ls = jnp.moveaxis(labels_c.reshape(B, nchunk, _LOSS_CHUNK), 1, 0)
            ms = jnp.moveaxis(mask.reshape(B, nchunk, _LOSS_CHUNK), 1, 0)

            @jax.checkpoint
            def chunk(acc, xs):
                h, l, m = xs
                return acc + _ce_terms(h @ head, l, m), None

            nll_sum, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (hs, ls, ms))
        else:
            nll_sum = _ce_terms(hidden @ head, labels_c, mask)

        denom = jnp.maximum(jnp.sum(mask), 1.0)
        nll = nll_sum / denom
        loss = nll + aux
        return loss, {"loss": loss, "nll": nll, "aux": aux, "ntokens": denom}
