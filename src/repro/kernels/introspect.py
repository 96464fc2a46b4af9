"""Block-spec / launch metadata extraction for Pallas kernels.

The kernel-lint analysis pass (``repro.analysis.kernel_lint``) needs to
see every ``pallas_call`` a function traces to — its grid, each operand's
block shape and memory space, the kernel body jaxpr — without executing
anything.  This module walks a traced jaxpr (reusing the duck-typed
recursion of ``kernels.ops``) and normalizes the jax-internal
``GridMapping`` / ``BlockMapping`` structures into plain tuples, so the
lint does not couple to jax's private class layout in more than one
place.

Index maps are evaluated concretely (``jax.core.eval_jaxpr`` over grid
points, corner-sampled for huge grids) to answer the grid-covers-array
question; our index maps are rectilinear (each block coordinate depends
on grid axes independently), for which the per-dimension interval-union
check in :func:`block_coverage` is exact.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

FULL_EVAL_LIMIT = 4096  # grid points; above this, sample corners only


class BlockInfo(NamedTuple):
    origin: str                      # "args[i]" / "outputs[j]"
    block_shape: Tuple[Optional[int], ...]
    array_shape: Tuple[int, ...]
    dtype: str
    memspace: str                    # "vmem" | "smem" | "any"
    index_map: Any                   # ClosedJaxpr grid idx -> block idx


class KernelLaunch(NamedTuple):
    name: str                        # kernel function name
    grid: Tuple[int, ...]
    in_blocks: Tuple[BlockInfo, ...]
    out_blocks: Tuple[BlockInfo, ...]
    scratch_shapes: Tuple[Tuple[Tuple[int, ...], str], ...]
    kernel_jaxpr: Any                # the kernel body Jaxpr

    @property
    def blocks(self) -> Tuple[BlockInfo, ...]:
        return self.in_blocks + self.out_blocks

    def vmem_block_bytes(self) -> int:
        """VMEM the launch's pipelined blocks hold per grid program: every
        non-SMEM block double-buffered at its own dtype, plus scratch —
        the block part of ``rmnp_update.stripe_vmem_bytes``."""
        total = 0
        for b in self.blocks:
            if b.memspace == "smem":
                continue
            n = 1
            for d in b.block_shape:
                n *= (d or 1)
            total += 2 * n * np.dtype(b.dtype).itemsize
        for shape, dtype in self.scratch_shapes:
            n = 1
            for d in shape:
                n *= d
            total += n * np.dtype(dtype).itemsize
        return total


def _memspace(block_aval) -> str:
    s = str(block_aval).lower()
    if "smem" in s:
        return "smem"
    if "vmem" in s or "memref" in s:
        return "vmem"
    return "any"


def _block_dim(d) -> Optional[int]:
    # an int, None (squeezed), or a jax block-dim object (Blocked carries
    # block_size; Squeezed has none)
    if d is None or isinstance(d, int):
        return d
    return getattr(d, "block_size", None)


def _block_info(bm, origin_fallback: str) -> BlockInfo:
    aval = bm.array_aval
    return BlockInfo(
        origin=str(getattr(bm, "origin", "") or origin_fallback),
        block_shape=tuple(_block_dim(d) for d in bm.block_shape),
        array_shape=tuple(aval.shape),
        dtype=str(aval.dtype),
        memspace=_memspace(bm.transformed_block_aval),
        index_map=bm.index_map_jaxpr)


def _from_eqn(eqn) -> KernelLaunch:
    gm = eqn.params["grid_mapping"]
    bms = list(gm.block_mappings)
    n_in = gm.num_inputs
    infos = [_block_info(bm, f"operand[{i}]") for i, bm in enumerate(bms)]
    kernel_jaxpr = eqn.params["jaxpr"]
    scratch: List[Tuple[Tuple[int, ...], str]] = []
    n_scratch = getattr(gm, "num_scratch_operands", 0)
    if n_scratch:
        for var in kernel_jaxpr.invars[len(bms):len(bms) + n_scratch]:
            aval = var.aval
            scratch.append((tuple(getattr(aval, "shape", ())),
                            str(getattr(aval, "dtype", ""))))
    name = eqn.params.get("name") or "pallas_call"
    return KernelLaunch(
        name=name, grid=tuple(gm.grid),
        in_blocks=tuple(infos[:n_in]),
        out_blocks=tuple(infos[n_in:n_in + gm.num_outputs]),
        scratch_shapes=tuple(scratch),
        kernel_jaxpr=kernel_jaxpr)


def collect_kernel_launches(fn, *args, **kwargs) -> List[KernelLaunch]:
    """Trace ``fn`` (never run it) and return every ``pallas_call`` launch
    found in its jaxpr, recursing into nested call/control-flow jaxprs."""
    from repro.kernels.ops import _sub_jaxprs, _walk_eqns

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    launches: List[KernelLaunch] = []

    def visit(eqn):
        if eqn.primitive.name == "pallas_call":
            launches.append(_from_eqn(eqn))
        return 0

    for j in _sub_jaxprs(closed):
        _walk_eqns(j, visit)
    return launches


def _eval_index_map(index_map, idxs) -> Tuple[int, ...]:
    closed = index_map
    out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *idxs)
    return tuple(int(x) for x in out)


def _grid_points(grid: Tuple[int, ...]):
    total = 1
    for g in grid:
        total *= max(1, g)
    if total <= FULL_EVAL_LIMIT:
        return itertools.product(*(range(max(1, g)) for g in grid))
    # corner sample: min/max along each axis (exact for monotone maps)
    return itertools.product(*({0, max(1, g) - 1} for g in grid))


def block_coverage(launch: KernelLaunch, block: BlockInfo) -> Dict[str, Any]:
    """Evaluate the block's index map over the grid and report, per array
    dimension, whether the union of block intervals covers ``[0, dim)``
    and whether any block starts fully out of bounds.  ``None`` entries in
    ``block_shape`` (squeezed dims) are treated as size-1 blocks."""
    shape = tuple(d or 1 for d in block.block_shape)
    starts_per_dim: List[set] = [set() for _ in shape]
    for idxs in _grid_points(launch.grid):
        bidx = _eval_index_map(block.index_map, idxs)
        for d, (i, b) in enumerate(zip(bidx, shape, strict=False)):
            starts_per_dim[d].add(i * b)
    uncovered: List[Tuple[int, int, int]] = []   # (dim, gap_start, gap_end)
    out_of_bounds: List[Tuple[int, int]] = []    # (dim, start)
    for d, (b, n) in enumerate(zip(shape, block.array_shape, strict=False)):
        covered_to = 0
        for s in sorted(starts_per_dim[d]):
            if s >= n:
                out_of_bounds.append((d, s))
                continue
            if s > covered_to:
                uncovered.append((d, covered_to, s))
            covered_to = max(covered_to, s + b)
        if covered_to < n:
            uncovered.append((d, covered_to, n))
    return {"uncovered": uncovered, "out_of_bounds": out_of_bounds,
            "covers": not uncovered and not out_of_bounds}
