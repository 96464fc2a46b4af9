"""Lower (never execute) every optimizer x wire x accum combo.

Each combo builds the REAL training step — ``make_dp_train_step`` on the
reduced gpt2-60m config over an abstract 4-device ``data`` mesh — and
produces :class:`Artifacts` from two compiler views of it:

* ``jax.make_jaxpr`` over abstract operands (the memory pass's view);
* AOT ``jax.jit(step, donate_argnums=(0, 1)).lower(...).compile()``
  post-optimization HLO text (the sharding/donation/overlap passes'
  view).

Nothing is ever run: params, optimizer state and batch are
``jax.eval_shape`` / ``ShapeDtypeStruct`` abstractions end to end.

Every combo lowers the bucketed engine's ZeRO-2 path
(``shard_axis="data", shard_size=4``, reduce-scattered gradient shards,
pipelined schedule forced with ``overlap=True`` so the serialized
fallback never masks a pipelining regression).  Wire
``int8-ef`` turns on the int8 error-feedback gradient compression.

Requires >= 4 CPU devices (``XLA_FLAGS=--xla_force_host_platform_\
device_count=4`` before jax import — ``repro.analysis.check`` arranges
this; tests use a subprocess).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.analysis.framework import Artifacts, Combo, DonatedLeaf, WIRES

N_DEV = 4
_LR = 1e-2

# lazily-built shared model fixtures (one per process; plan caches and
# param avals are pure metadata so sharing across combos is safe)
_FIXTURE: Dict[str, object] = {}


def build_combos(optimizers: Optional[List[str]] = None,
                 wires: Optional[List[str]] = None,
                 accums: Optional[List[int]] = None) -> List[Combo]:
    """The full matrix: every registry optimizer x wire at
    ``accum=1``, plus the rmnp ZeRO-2 accumulation points (the pipelined
    schedule interacts with the accumulation scan, so both wires get an
    ``accum=4`` combo).  Filters narrow the matrix for the CLI."""
    from repro.core import optimizer_names

    names = list(optimizers) if optimizers else list(optimizer_names())
    combos = [Combo(n, w, 1) for n in names for w in WIRES]
    if not optimizers or "rmnp" in names:
        combos.append(Combo("rmnp", "fp32", 4))
        combos.append(Combo("rmnp", "int8-ef", 4))
    # guarded lowerings: the non-finite guard's post-update selects must
    # not cost the pipelined step its zero serialization edges, its
    # donation aliasing or its memory profile — rmnp + normuon on both
    # wires (the fault-injection proof matrix) plus the accum interaction
    for n in ("rmnp", "normuon"):
        if not optimizers or n in names:
            combos += [Combo(n, w, 1, guard=True)
                       for w in WIRES]
    if not optimizers or "rmnp" in names:
        combos.append(Combo("rmnp", "fp32", 4, guard=True))
    if wires:
        combos = [c for c in combos if c.wire in wires]
    if accums:
        combos = [c for c in combos if c.accum in accums]
    return combos


def _fixture():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_data_mesh
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state

    if _FIXTURE:
        return _FIXTURE
    if jax.device_count() < N_DEV:
        raise RuntimeError(
            f"analysis lowering needs >= {N_DEV} devices but jax sees "
            f"{jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={N_DEV} before jax "
            f"is imported (run via python -m repro.analysis.check)")
    cfg = get_config("gpt2-60m").reduced()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    comp = jax.eval_shape(lambda p: init_dp_state(p, N_DEV), params)
    toks = jax.ShapeDtypeStruct((4 * N_DEV, 16), jnp.int32)
    _FIXTURE.update(
        cfg=cfg, params=params, comp=comp,
        batch={"tokens": toks, "labels": toks},
        mesh=make_data_mesh(N_DEV))
    return _FIXTURE


def make_combo_optimizer(combo: Combo):
    """The registry optimizer a combo lowers with."""
    from repro.core import make_optimizer

    return make_optimizer(combo.optimizer, lr_matrix=_LR, shard_axis="data",
                          shard_size=N_DEV)


def _donated_leaves(params, opt_state) -> Tuple[DonatedLeaf, ...]:
    """Flat HLO entry parameter numbers for the donated trees.  jit
    flattens its arguments in order, so params' leaves take numbers
    ``0..n-1`` and opt_state's the next ``m`` (donate_argnums=(0, 1))."""
    from repro.core.types import tree_paths

    out: List[DonatedLeaf] = []
    num = 0
    for prefix, tree in (("params", params), ("opt_state", opt_state)):
        for path, leaf in tree_paths(tree):
            out.append(DonatedLeaf(
                param_number=num, path=f"{prefix}/{path}",
                shape=tuple(leaf.shape), dtype=str(leaf.dtype)))
            num += 1
    return tuple(out)


def lower_combo(combo: Combo, *, break_mode: Optional[str] = None) -> Artifacts:
    """Build and lower one combo into :class:`Artifacts`.

    ``break_mode`` deliberately degrades the step so tests can prove the
    passes catch real regressions: ``"gather-momentum"`` all-gathers every
    momentum shard back to the full bucket inside the step (memory +
    sharding must fire); ``"drop-donation"`` lowers without
    ``donate_argnums`` while still reporting the leaves as donated
    (donation must fire)."""
    import jax
    import jax.numpy as jnp

    from repro.train.dp_step import make_dp_train_step

    fx = _fixture()
    opt = make_combo_optimizer(combo)
    params, comp, batch = fx["params"], fx["comp"], fx["batch"]
    opt_state = jax.eval_shape(opt.init, params)

    base_step = make_dp_train_step(
        fx["cfg"], opt, fx["mesh"], compress=combo.compress,
        accum=combo.accum, guard=combo.guard, zero2=True,
        opt_state=opt_state, overlap=True)

    if break_mode == "gather-momentum":
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import bucket_specs

        state_specs = bucket_specs(opt_state, fx["mesh"])

        def step(p, s, c, b, t):
            p2, s2, c2, m = base_step(p, s, c, b, t)

            # the regression under test: reconstruct every momentum
            # bucket on every rank after the update
            def regather(v, spec):
                if not any(ax == "data" for ax in spec):
                    return v

                def gather(shard):
                    return jax.lax.all_gather(shard, "data", axis=0,
                                              tiled=True)

                return jax.shard_map(gather, mesh=fx["mesh"], in_specs=spec,
                                     out_specs=P(), check_vma=False)(v)

            m = dict(m)
            m["_gathered_momentum_norm"] = sum(
                jnp.sum(regather(v, state_specs.buckets[k]).astype(
                    jnp.float32) ** 2)
                for k, v in s2.buckets.items())
            return p2, s2, c2, m
    else:
        step = base_step

    args = (params, opt_state, comp, batch, jnp.int32(0))
    jaxpr = jax.make_jaxpr(step)(*args)
    donate = () if break_mode == "drop-donation" else (0, 1)
    hlo = jax.jit(step, donate_argnums=donate).lower(*args).compile().as_text()

    meta = opt.state_meta(params) if opt.state_meta is not None else ()
    return Artifacts(
        combo=combo, jaxpr=jaxpr, hlo_text=hlo, buckets=tuple(meta),
        donated=_donated_leaves(params, opt_state), n_dev=N_DEV,
        overlap=True)
