"""Plain float32 reference of the dense decoder LM and its first training
steps, for the comparison that decides ``correct``.

The model: token embedding, ``num_layers`` pre-norm blocks (RMSNorm,
multi-head attention with ``n_kv_heads`` key/value heads, rotary position
embedding on the two halves of each head, causal softmax; RMSNorm, SwiGLU
FFN whose input matrix holds ``[gate; up]``), a final RMSNorm and the LM
head (the embedding's transpose where ``tie_embeddings``).  The loss is
the mean next-token cross-entropy, with the softmax over the vocabulary
padded to a multiple of 256 as the program pads it (the padded ids are
never labels).  The step: the gradient of that loss, clipped to global
norm ``clip_norm``; RMNP (momentum, then each output neuron's fan-in
column l2-normalised, ``w -= lr * scale * (d + wd * w)`` with ``scale =
max(1, sqrt(d_out / d_in))``) on every matrix, the embedding and head
included; AdamW on the norm scales; both learning rates on a cosine with
10% linear warm-up.  Weights are stored in the configuration's dtype
(bfloat16) and the momentum in float32, as the configuration states;
every operation is float32 at ``Precision.HIGHEST``.

It imports nothing of the program and takes nothing it made: the weights
are drawn here from the seed in the program's published order of
parameters (leaves sorted by path, one key each), and the batches come
from ``bench.reference.data``.  It runs layer by layer and in blocks of
rows, so it fits one chip beside nothing else; over several chips each
takes its share of the rows and the layer gradients are summed across
them one layer at a time.

``precision="fp8"`` is the control: every matrix product takes its
operands rounded to float8 with a per-tensor scale (e4m3 forward, e5m2
for the gradients), the precision step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import data

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# path fragments of the leaves the mixed optimizer gives to AdamW
NON_MATRIX = ("norm", "bias", "scale", "a_log", "dt_", "conv")
SUPPORTED = {"name", "family", "num_layers", "d_model", "n_heads",
             "n_kv_heads", "d_ff", "vocab", "head_dim", "tie_embeddings",
             "rope_theta", "rms_eps", "dtype"}


class Leaf:
    """One parameter: its shape and how it is drawn."""
    __slots__ = ("shape", "init", "scale")

    def __init__(self, shape, init, scale=1.0):
        self.shape, self.init, self.scale = tuple(shape), init, scale


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def _dims(m: Dict[str, Any]):
    unknown = set(m) - SUPPORTED
    if unknown:
        raise ValueError(f"the dense reference has no {sorted(unknown)}")
    d, heads = m["d_model"], m["n_heads"]
    return dict(d=d, H=heads, K=m["n_kv_heads"],
                hd=m.get("head_dim") or d // heads, ff=m["d_ff"],
                V=padded_vocab(m["vocab"]), L=m["num_layers"],
                tied=bool(m.get("tie_embeddings", False)),
                theta=float(m.get("rope_theta", 10_000.0)),
                eps=float(m.get("rms_eps", 1e-6)),
                dtype=jnp.dtype(m.get("dtype", "bfloat16")))


def param_specs(m: Dict[str, Any]) -> Dict[str, Any]:
    x = _dims(m)
    d, L, H, K, hd, ff, V = (x[k] for k in ("d", "L", "H", "K", "hd", "ff",
                                            "V"))
    specs = {
        "embed": {"tokens": Leaf((V, d), "normal", 0.02)},
        "final_norm": Leaf((d,), "ones"),
        "stack": {"layer_0": {
            "mixer": {"norm": Leaf((L, d), "ones"),
                      "wq": Leaf((L, d, H * hd), "fan_in"),
                      "wk": Leaf((L, d, K * hd), "fan_in"),
                      "wv": Leaf((L, d, K * hd), "fan_in"),
                      "wo": Leaf((L, H * hd, d), "fan_in")},
            "ffn": {"norm": Leaf((L, d), "ones"),
                    "w_in": Leaf((L, d, 2 * ff), "fan_in"),
                    "w_out": Leaf((L, ff, d), "fan_in")}}},
    }
    if not x["tied"]:
        specs["lm_head"] = Leaf((d, V), "fan_in")
    return specs


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def init_params(m: Dict[str, Any], key):
    """The initial weights from ``key`` (``PRNGKey(seed)``): leaves in
    sorted path order, one key each from ``split(key, n_leaves)``;
    ``normal`` leaves are ``scale * N(0, 1)``, ``fan_in`` leaves ``N(0, 1)
    / sqrt(fan_in)``, norm scales ones; all cast to the configuration's
    dtype."""
    dtype = _dims(m)["dtype"]
    leaves, treedef = jax.tree_util.tree_flatten(param_specs(m),
                                                 is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))

    def draw(sp: Leaf, k):
        if sp.init == "ones":
            return jnp.ones(sp.shape, dtype)
        if sp.init == "normal":
            return (sp.scale * jax.random.normal(k, sp.shape)).astype(dtype)
        std = sp.scale / (sp.shape[-2] ** 0.5)
        return (std * jax.random.normal(k, sp.shape)).astype(dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [draw(sp, k) for sp, k in zip(leaves, keys, strict=True)])


def leaf_paths(tree) -> Dict[str, Any]:
    """``{'a/b/c': leaf}`` for a tree of nested dicts."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def is_matrix(path: str, shape) -> bool:
    if any(tok in path.lower() for tok in NON_MATRIX):
        return False
    return len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1


def slice_norms(tree) -> Dict[str, jax.Array]:
    """Float32 l2 norm of every matrix and vector the optimizer treats on
    its own: each layer's slice of a stacked ``stack/`` leaf (an ``(L,)``
    array), every other leaf whole (a scalar).  Traceable."""
    out = {}
    for path, leaf in leaf_paths(tree).items():
        x = jnp.square(leaf.astype(F32))
        if path.startswith("stack/"):
            out[path] = jnp.sqrt(jnp.sum(x, axis=tuple(range(1, x.ndim))))
        else:
            out[path] = jnp.sqrt(jnp.sum(x))
    return out


def flatten_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    """``slice_norms`` output on the host: ``{'path[i]': norm}`` for stacked
    slices, ``{'path': norm}`` for whole leaves."""
    out = {}
    for path, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{path}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[path] = float(v)
    return out


# ---------------------------------------------------------------------------
# matrix products: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------

def _q8(x, dtype):
    """``x`` rounded to float8 ``dtype`` under a per-tensor scale that maps
    its largest magnitude to the format's largest finite value."""
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * s).astype(dtype).astype(F32) / s


def _dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dot8(spec: str, a, b):
    return _dot(spec, _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn))


def _dot8_fwd(spec, a, b):
    qa, qb = _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn)
    return _dot(spec, qa, qb), (qa, qb)


def _dot8_bwd(spec, res, g):
    qa, qb = res
    g8 = _q8(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda a, b: _dot(spec, a, b), qa, qb)
    return vjp(g8)


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def _product(precision: str):
    if precision == "highest":
        return _dot
    if precision == "fp8":
        return _dot8
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate the two halves of each head."""
    hd, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


SCORE_BYTES = 128 * 2**20   # float32 scores of one block of heads


def _attention(q, k, v, dot):
    """Causal softmax attention, q: (B, S, K, G, hd), k and v: (B, S, K,
    hd); a block of key/value heads at a time, so that the float32 scores
    of at most about ``SCORE_BYTES`` exist at once."""
    B, S, K, G, hd = q.shape
    per_head = B * G * S * S * 4
    hb = max([h for h in range(1, K + 1)
              if K % h == 0 and h * per_head <= SCORE_BYTES] or [1])
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def block(qkv):
        qb, kb, vb = qkv
        s = dot("bqkgh,bskh->bkgqs", qb, kb) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return dot("bkgqs,bskh->bqkgh", jax.nn.softmax(s, axis=-1), vb)

    def split(t):   # (B, S, K, ...) -> (K / hb, B, S, hb, ...)
        return jnp.moveaxis(t.reshape((B, S, K // hb, hb) + t.shape[3:]),
                            2, 0)

    o = jax.lax.map(block, (split(q), split(k), split(v)))
    return jnp.moveaxis(o, 0, 2).reshape(B, S, K, G, hd)


def _layer(x, lp, dims, dot):
    """One block on float32 rows ``x`` (B, S, d) with float32 weights."""
    B, S, d = x.shape
    H, K, hd = dims["H"], dims["K"], dims["hd"]
    a = lp["mixer"]
    h = _rms_norm(x, a["norm"], dims["eps"])
    q = _rope(dot("bsd,de->bse", h, a["wq"]).reshape(B, S, H, hd),
              dims["theta"])
    k = _rope(dot("bsd,de->bse", h, a["wk"]).reshape(B, S, K, hd),
              dims["theta"])
    v = dot("bsd,de->bse", h, a["wv"]).reshape(B, S, K, hd)
    o = _attention(q.reshape(B, S, K, H // K, hd), k, v, dot)
    x = x + dot("bse,ed->bsd", o.reshape(B, S, H * hd), a["wo"])
    f = lp["ffn"]
    h = _rms_norm(x, f["norm"], dims["eps"])
    gate, up = jnp.split(dot("bsd,de->bse", h, f["w_in"]), 2, axis=-1)
    return x + dot("bse,ed->bsd", jax.nn.silu(gate) * up, f["w_out"])


def _head_nll(x, final_norm, head, labels, dims, dot, chunk):
    """Summed cross-entropy of rows ``x`` (B, S, d), in token chunks so the
    float32 logits of only ``chunk`` tokens exist at a time."""
    d = x.shape[-1]
    h = _rms_norm(x, final_norm, dims["eps"]).reshape(-1, d)
    lab = labels.reshape(-1)
    n = h.shape[0]
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError(f"{n} tokens do not split into chunks of {chunk}")

    @jax.checkpoint
    def one(acc, xs):
        hc, lc = xs
        logits = dot("td,dv->tv", hc, head)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold), None

    total, _ = jax.lax.scan(one, jnp.zeros((), F32),
                            (h.reshape(n // chunk, chunk, d),
                             lab.reshape(n // chunk, chunk)))
    return total


def _up(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _blocks(x, rows):
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


def _unblock(x):
    return x.reshape((-1,) + x.shape[2:])


def nll_and_grads(params, tokens, labels, dims, dot, *, rows: int,
                  n_tokens: int, chunk: int = 1024,
                  axis: Optional[str] = None):
    """Summed cross-entropy of the rows and the float32 gradient of the
    mean over ``n_tokens`` tokens for every parameter (both summed over
    ``axis`` where the rows are split across chips).  Layer by layer: the
    forward keeps each layer's input, the backward recomputes one layer at
    a time from it, ``rows`` rows at a time."""
    def reduce(t):
        return jax.lax.psum(t, axis) if axis else t

    layer = functools.partial(_layer, dims=dims, dot=dot)
    x0 = params["embed"]["tokens"][tokens].astype(F32)

    def fwd(x, lp):
        lp = _up(lp)
        y = jax.lax.map(lambda xb: layer(xb, lp), _blocks(x, rows))
        return _unblock(y), x

    stack = params["stack"]["layer_0"]
    xL, saved = jax.lax.scan(fwd, x0, stack)
    fn = params["final_norm"].astype(F32)
    head = (params["embed"]["tokens"].T if dims["tied"]
            else params["lm_head"]).astype(F32)
    nll, head_vjp = jax.vjp(
        lambda x, f, h: _head_nll(x, f, h, labels, dims, dot, chunk),
        xL, fn, head)
    dx, dfn, dhead = head_vjp(jnp.asarray(1.0 / n_tokens, F32))

    def bwd(dx, xs):
        x_in, lp = xs
        lp = _up(lp)

        def blk(acc, xd):
            xb, db = xd
            _, vjp = jax.vjp(layer, xb, lp)
            dxb, dlp = vjp(db)
            return jax.tree_util.tree_map(jnp.add, acc, dlp), dxb

        zero = jax.tree_util.tree_map(jnp.zeros_like, lp)
        dlp, dxb = jax.lax.scan(blk, zero, (_blocks(x_in, rows),
                                            _blocks(dx, rows)))
        return _unblock(dxb), reduce(dlp)

    dx0, dstack = jax.lax.scan(bwd, dx, (saved, stack), reverse=True)
    demb = jnp.zeros(params["embed"]["tokens"].shape, F32).at[
        tokens.reshape(-1)].add(
        dx0.reshape(-1, dx0.shape[-1]))
    grads = {"stack": {"layer_0": dstack}, "final_norm": reduce(dfn)}
    if dims["tied"]:
        grads["embed"] = {"tokens": reduce(demb + dhead.T)}
    else:
        grads["embed"] = {"tokens": reduce(demb)}
        grads["lm_head"] = reduce(dhead)
    return reduce(nll), grads


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def lr_at(peak: float, total: int, step):
    """Cosine decay to 0 after a linear warm-up over 10% of ``total``."""
    warm_steps = max(1, int(total * 0.1))
    step = jnp.asarray(step, F32)
    warm = peak * step / warm_steps
    prog = jnp.clip((step - warm_steps) / max(1, total - warm_steps), 0, 1)
    return jnp.where(step < warm_steps, warm,
                     peak * 0.5 * (1 + jnp.cos(jnp.pi * prog)))


def init_state(params):
    """Float32 momentum of every leaf (RMNP's on matrices, AdamW's first
    moment elsewhere) and AdamW's second moment on the non-matrix leaves."""
    paths = leaf_paths(params)
    m = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    nu = {k: jnp.zeros(p.shape, F32) for k, p in paths.items()
          if not is_matrix(k, p.shape)}
    return {"m": m, "nu": nu}


def apply_update(params, state, grads, step, job: Dict[str, Any],
                 total_steps: int):
    """Clip, then one RMNP / AdamW update.  Returns new params (stored in
    their own dtype), new state and the unclipped global norm."""
    opt = job["optimizer"]
    gl = leaf_paths(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in gl.values()))
    clip = job["clip_norm"]
    cscale = jnp.minimum(1.0, clip / (gnorm + 1e-12)) if clip > 0 else 1.0
    lr_m = lr_at(job["lr_matrix"], total_steps, step)
    lr_a = lr_at(job["lr_adamw"], total_steps, step)
    beta, wd = opt["beta"], opt["weight_decay"]
    b1, b2 = opt["adam_b1"], opt["adam_b2"]
    t = jnp.asarray(step, F32) + 1.0
    pl, ml = leaf_paths(params), leaf_paths(state["m"])
    new_p, new_m, new_nu = {}, {}, {}
    for k, w in pl.items():
        g = gl[k] * cscale
        w32 = w.astype(F32)
        if is_matrix(k, w.shape):
            v = beta * ml[k] + (1.0 - beta) * g
            d = v / (jnp.sqrt(jnp.sum(v * v, axis=-2, keepdims=True))
                     + opt["rn_eps"])
            scale = lr_m * max(1.0, (w.shape[-1] / w.shape[-2]) ** 0.5)
            w_new = w32 + (-scale) * (d + wd * w32)
        else:
            v = b1 * ml[k] + (1 - b1) * g
            nu = b2 * state["nu"][k] + (1 - b2) * jnp.square(g)
            dd = (v / (1.0 - b1 ** t)) / (jnp.sqrt(nu / (1.0 - b2 ** t))
                                          + opt["adam_eps"])
            w_new = w32 + (-lr_a) * (dd + wd * w32)
            new_nu[k] = nu
        new_p[k], new_m[k] = w_new.astype(w.dtype), v
    return (_unflatten_like(params, new_p),
            {"m": _unflatten_like(state["m"], new_m), "nu": new_nu}, gnorm)


def _unflatten_like(tree, by_path: Dict[str, Any]):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, [by_path[k] for k in keys])


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------

def readings(model: Dict[str, Any], job: Dict[str, Any], seed: int,
             total_steps: int, devices, *, n_steps: int = 3,
             precision: str = "highest", fault: str = "",
             log=lambda msg: None) -> Dict[str, Any]:
    """What the program's first ``n_steps`` steps should give from this
    seed: each step's loss (``loss``), every slice's momentum norm after
    the first step (``moment``: the clipped first gradient as the
    optimizer holds it) and every slice's weight change after
    ``n_steps`` steps (``change``).

    ``fault`` plants a fault in this reference, put in the program's
    place, to read what the comparison makes of it: ``half_batch`` (loss
    and gradient of the first half of the rows only), ``one_chip`` (of
    the first chip's share of the rows only: the exchange between chips
    left out).  The numbers are host floats."""
    dims = _dims(model)
    dot = _product(precision)
    B, S = job["batch"], job["seq"]
    n = len(devices)
    keep = {"": B, "half_batch": B // 2, "one_chip": B // n}[fault]
    rows = min(job.get("reference_rows", 1), keep // n)
    if keep % n or (keep // n) % rows:
        raise ValueError(f"{keep} rows do not split over {n} chips in "
                         f"blocks of {rows}")
    n_tok = keep * S
    mesh = Mesh(np.asarray(devices), ("rows",))
    rep = NamedSharding(mesh, P())
    by_rows = NamedSharding(mesh, P("rows"))

    def grads_fn(params, tokens, labels):
        return nll_and_grads(params, tokens, labels, dims, dot, rows=rows,
                             n_tokens=n_tok, axis="rows")

    sharded_grads = jax.shard_map(
        grads_fn, mesh=mesh, in_specs=(P(), P("rows"), P("rows")),
        out_specs=(P(), P()), check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, state, tokens, labels, step):
        nll, grads = sharded_grads(params, tokens, labels)
        params, state, _ = apply_update(params, state, grads, step, job,
                                        total_steps)
        return params, state, nll / n_tok

    init = jax.jit(functools.partial(init_params, model), out_shardings=rep)

    @functools.partial(jax.jit, out_shardings=rep)
    def change_norms(params, key):
        w0 = init_params(model, key)
        return slice_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(F32) - b.astype(F32), params, w0))

    norms = jax.jit(slice_norms, out_shardings=rep)

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(seed)
    params = init(key)
    state = jax.jit(init_state, out_shardings=rep)(params)
    losses, moment = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(n_steps):
            log(f"[reference] step {step} at {time.perf_counter() - t0:.3f} s")
            b = data.batch(seed, step, B, S, model["vocab"])
            tokens, labels = b["tokens"][:keep], b["labels"][:keep]
            tokens = jax.device_put(tokens, by_rows)
            labels = jax.device_put(labels, by_rows)
            params, state, loss = train_step(params, state, tokens, labels,
                                             jnp.int32(step))
            losses.append(float(loss))
            if step == 0:
                moment = flatten_norms(jax.device_get(norms(state["m"])))
        log(f"[reference] steps done at {time.perf_counter() - t0:.3f} s")
        change = flatten_norms(jax.device_get(change_norms(params, key)))
    log(f"[reference] done at {time.perf_counter() - t0:.3f} s")
    del params, state
    return {"loss": losses, "moment": moment, "change": change}
