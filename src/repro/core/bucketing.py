"""Shape-bucketed fused update engine.

The per-leaf RMNP path launches one preconditioner kernel per matrix
parameter — at GPT-2-XL scale that is ~200 tiny launches per step, and the
step is dominated by dispatch overhead rather than the paper's O(mn) math.
Transformer parameter trees, however, contain only a handful of *distinct*
matrix shapes (qkv, attn-out, mlp-in, mlp-out, ...), so we:

  1. group every matrix leaf by its trailing ``(d_in, d_out)`` shape after
     flattening leading scan/expert axes (a ``(layers, d, 4d)`` stack
     contributes ``layers`` slices to the ``d x 4d`` bucket),
  2. stack each bucket into a single ``(L, d_in, d_out)`` operand, and
  3. run the rule's single-pass apply (the RMNP kernel) once per *bucket*
     instead of once per *leaf*.

The leaf->bucket plan is pure static metadata (paths, shapes, offsets):
it is computed once at optimizer ``init`` and reused by ``update``; the
gather/scatter are reshapes + concatenates that XLA folds into the step.
Momentum is stored stacked per bucket (optionally in bf16), so the whole
optimizer state for the matrix partition is a small dict of big buffers —
ideal for buffer donation and for per-bucket sharding later.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import PyTree, tree_paths


class BucketEntry(NamedTuple):
    path: str                  # '/'-joined tree path of the leaf
    shape: Tuple[int, ...]     # full leaf shape, leading axes included
    lead: int                  # prod(shape[:-2]) — slices this leaf occupies
    offset: int                # first slice of this leaf in the stacked bucket


class Bucket(NamedTuple):
    key: str                   # "d_inxd_out", e.g. "768x3072"
    d_in: int
    d_out: int
    size: int                  # L — total stacked slices across all entries
    entries: Tuple[BucketEntry, ...]
    # L rounded up to the plan's pad multiple (the ZeRO shard-axis size):
    # stacked buffers are allocated at padded_size so *every* bucket divides
    # the axis; pad slices carry zero grad/momentum and are dropped by
    # scatter.  0 (the default, for plans built before padding existed)
    # means "no padding", i.e. == size.
    padded_size: int = 0

    @property
    def padded(self) -> int:
        return self.padded_size or self.size


class BucketPlan(NamedTuple):
    buckets: Tuple[Bucket, ...]

    @property
    def n_leaves(self) -> int:
        return sum(len(b.entries) for b in self.buckets)

    @property
    def paths(self) -> frozenset:
        """Leaf paths the plan covers (the matrix partition)."""
        return frozenset(e.path for b in self.buckets for e in b.entries)


class PlanCache:
    """Tiny LRU for leaf->bucket plans keyed on :func:`plan_signature`.

    One optimizer instance can serve many parameter trees (a long-lived
    serving process cycling adapters, eval harnesses sweeping model sizes);
    an unbounded dict would leak plan metadata for every signature ever
    seen.  Plans are cheap to rebuild, so a small LRU loses nothing."""

    def __init__(self, maxsize: int = 8):
        if maxsize < 1:
            raise ValueError(f"PlanCache needs maxsize >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._plans: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._plans)

    def get(self, key, build: Callable[[], "BucketPlan"]) -> "BucketPlan":
        if key in self._plans:
            self._plans.move_to_end(key)
            return self._plans[key]
        plan = build()
        self._plans[key] = plan
        while len(self._plans) > self.maxsize:
            self._plans.popitem(last=False)
        return plan


def bucket_key(d_in: int, d_out: int) -> str:
    return f"{d_in}x{d_out}"


def _lead(shape) -> int:
    n = 1
    for s in shape[:-2]:
        n *= s
    return n


def plan_signature(params: PyTree,
                   predicate: Optional[Callable[[str, jax.Array], bool]] = None):
    """Hashable description of the leaves a plan depends on (for caching)."""
    return tuple((path, tuple(leaf.shape))
                 for path, leaf in tree_paths(params)
                 if predicate is None or predicate(path, leaf))


def build_plan(params: PyTree,
               predicate: Optional[Callable[[str, jax.Array], bool]] = None,
               pad_multiple: int = 1) -> BucketPlan:
    """Group leaves selected by ``predicate`` (default: ``ndim >= 2``) into
    ``(d_in, d_out)`` buckets; the other leaves stay out of the plan.

    ``pad_multiple`` (the ZeRO shard-axis size) rounds every bucket's
    stacked ``L`` up to a multiple, so uneven buckets shard instead of
    falling back to replication: pad slices are zero-filled by
    :func:`gather`, stay identically zero through the RMNP update (zero
    grad -> zero momentum -> the row-normalize eps floor keeps ``d`` zero),
    and are never read back by :func:`scatter`."""
    if pad_multiple < 1:
        raise ValueError(f"pad_multiple must be >= 1, got {pad_multiple}")
    groups: Dict[Tuple[int, int], list] = {}
    for path, leaf in tree_paths(params):
        is_mat = (predicate(path, leaf) if predicate is not None
                  else getattr(leaf, "ndim", 0) >= 2)
        if not is_mat:
            continue
        d_in, d_out = leaf.shape[-2], leaf.shape[-1]
        groups.setdefault((d_in, d_out), []).append((path, tuple(leaf.shape)))
    buckets = []
    for (d_in, d_out) in sorted(groups):
        entries, offset = [], 0
        for path, shape in groups[(d_in, d_out)]:
            lead = _lead(shape)
            entries.append(BucketEntry(path=path, shape=shape,
                                       lead=lead, offset=offset))
            offset += lead
        padded = -(-offset // pad_multiple) * pad_multiple
        buckets.append(Bucket(key=bucket_key(d_in, d_out), d_in=d_in,
                              d_out=d_out, size=offset,
                              entries=tuple(entries), padded_size=padded))
    return BucketPlan(buckets=tuple(buckets))


def init_buckets(plan: BucketPlan, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Zero-initialised stacked momentum, one ``(padded L, d_in, d_out)``
    buffer per bucket (the whole matrix-partition optimizer state)."""
    return {b.key: jnp.zeros((b.padded, b.d_in, b.d_out), dtype)
            for b in plan.buckets}


def _bucket_parts(bucket: Bucket, by_path, dtype=None):
    """The planned leaves of one bucket as ``(lead, d_in, d_out)`` slabs (in
    entry order, shapes validated) plus the dtype pads must be created in."""
    parts = []
    for e in bucket.entries:
        leaf = by_path.get(e.path)
        if leaf is None:
            raise ValueError(
                f"bucket plan references leaf {e.path!r} (bucket "
                f"{bucket.key!r}) but the tree has no such path — was the "
                f"plan built for a different params tree?")
        if leaf.shape != e.shape:
            raise ValueError(f"leaf {e.path!r} changed shape: plan has "
                             f"{e.shape}, tree has {leaf.shape}")
        part = leaf.reshape(e.lead, bucket.d_in, bucket.d_out)
        parts.append(part.astype(dtype) if dtype is not None else part)
    pad_dtype = dtype if dtype is not None else jnp.result_type(
        *[p.dtype for p in parts])
    return parts, pad_dtype


@jax.named_scope("gather")
def gather(plan: BucketPlan, tree: PyTree, dtype=None) -> Dict[str, jax.Array]:
    """Stack the planned leaves of ``tree`` into per-bucket operands.  Pad
    slices (``padded_size > size``) are zero-filled — mathematically inert
    through the RMNP update and dropped by :func:`scatter`."""
    by_path = dict(tree_paths(tree))
    out = {}
    for b in plan.buckets:
        parts, pad_dtype = _bucket_parts(b, by_path, dtype)
        if b.padded > b.size:
            parts.append(jnp.zeros((b.padded - b.size, b.d_in, b.d_out),
                                   pad_dtype))
        out[b.key] = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return out


@jax.named_scope("gather")
def gather_chunks(plan: BucketPlan, tree: PyTree, n_chunks: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Stack the planned leaves of ``tree`` into ``(n_chunks, padded_L /
    n_chunks, d_in, d_out)`` per-bucket operands — :func:`gather` pre-split
    along ``L`` into the per-rank chunks of an ``n_chunks``-way ZeRO axis
    (chunk ``j`` is rank ``j``'s shard; pad slices zero-filled).

    This is the ZeRO-2 gradient layout: ``all_to_all`` / ``psum_scatter``
    consume the leading chunk axis directly, so the monolithic
    ``(padded_L, d_in, d_out)`` bucket is never materialized — the largest
    fp32 gradient intermediate per rank is one chunk."""
    by_path = dict(tree_paths(tree))
    out = {}
    for b in plan.buckets:
        csize = _chunk_size(b, n_chunks)
        parts, pad_dtype = _bucket_parts(b, by_path, dtype)
        chunks = []
        for j in range(n_chunks):
            lo, hi = j * csize, (j + 1) * csize
            pieces = []
            for e, part in zip(b.entries, parts, strict=False):
                s, t = max(lo, e.offset), min(hi, e.offset + e.lead)
                if s < t:
                    pieces.append(part[s - e.offset:t - e.offset])
            filled = sum(p.shape[0] for p in pieces)
            if filled < csize:  # tail pad of the last chunk(s)
                pieces.append(jnp.zeros((csize - filled, b.d_in, b.d_out),
                                        pad_dtype))
            chunks.append(pieces[0] if len(pieces) == 1
                          else jnp.concatenate(pieces, axis=0))
        out[b.key] = jnp.stack(chunks, axis=0)
    return out


def _chunk_size(bucket: Bucket, n_chunks: int) -> int:
    """Per-chunk slice count of a bucket split ``n_chunks`` ways; raises
    (naming the fix) when the padded size does not divide."""
    if bucket.padded % n_chunks:
        raise ValueError(
            f"bucket {bucket.key!r}: padded size {bucket.padded} is not "
            f"divisible by n_chunks={n_chunks} — build the plan with "
            f"pad_multiple=n_chunks (optimizer shard_size)")
    return bucket.padded // n_chunks


def init_chunk_acc(plan: BucketPlan, n_chunks: int,
                   dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Zero-initialised chunked gradient accumulators, one ``(n_chunks,
    padded_L / n_chunks, d_in, d_out)`` buffer per bucket — the carry of the
    microbatch-accumulation scan (:func:`accumulate_chunks`)."""
    return {b.key: jnp.zeros((n_chunks, _chunk_size(b, n_chunks), b.d_in,
                              b.d_out), dtype)
            for b in plan.buckets}


def accumulate_chunks(plan: BucketPlan, tree: PyTree,
                      acc: Dict[str, jax.Array], n_chunks: int,
                      dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Fold one microbatch's planned leaves of ``tree`` into the chunked
    per-bucket accumulators ``acc`` (from :func:`init_chunk_acc`).

    The leaves are chunked *first* (:func:`gather_chunks`) and added in the
    ``(n_chunks, padded_L / n_chunks, d_in, d_out)`` layout, so microbatch
    gradient accumulation never materializes the monolithic ``(padded_L,
    d_in, d_out)`` bucket — the ZeRO-2 invariant holds for ``accum > 1``.
    Chunking is pure slicing (linear), so accumulate-then-reduce is exactly
    the reduce of the accumulated per-leaf gradients."""
    chunks = gather_chunks(plan, tree, n_chunks, dtype=dtype)
    return {k: acc[k] + chunks[k] for k in acc}


def scatter_chunks(plan: BucketPlan, chunks: Dict[str, jax.Array],
                   base: PyTree) -> PyTree:
    """Inverse of :func:`gather_chunks`: reassemble each planned leaf of
    ``base`` from its pieces across the chunk axis (pad slices dropped;
    non-planned leaves pass through untouched).  Per-leaf slicing — the
    monolithic ``(padded_L, d_in, d_out)`` bucket is never rebuilt."""
    from repro.core.types import map_with_path

    slices = {}
    for b in plan.buckets:
        for e in b.entries:
            slices[e.path] = (b, e)

    def visit(path, leaf):
        hit = slices.get(path)
        if hit is None:
            return leaf
        b, e = hit
        stacked = chunks[b.key]
        csize = stacked.shape[1]
        pieces = []
        for j in range(stacked.shape[0]):
            lo, hi = j * csize, (j + 1) * csize
            s, t = max(lo, e.offset), min(hi, e.offset + e.lead)
            if s < t:
                pieces.append(stacked[j, s - lo:t - lo])
        out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)
        return out.reshape(e.shape)

    return map_with_path(visit, base)


@jax.named_scope("scatter")
def scatter(plan: BucketPlan, stacked: Dict[str, jax.Array],
            base: PyTree, cast: bool = False) -> PyTree:
    """Inverse of :func:`gather`: slice each bucket back into the planned
    leaves of ``base`` (non-planned leaves pass through untouched).  Pad
    slices beyond ``size`` are never read — padded buckets scatter for free.
    ``cast=True`` restores each base leaf's dtype — needed when the bucket
    was gathered without an explicit dtype and a mixed-dtype bucket promoted
    on concatenation (the engine scatters *params*, whose dtypes must stay
    stable across steps)."""
    from repro.core.types import map_with_path

    slices = {}
    for b in plan.buckets:
        for e in b.entries:
            slices[e.path] = (b.key, e)

    def visit(path, leaf):
        hit = slices.get(path)
        if hit is None:
            return leaf
        key, e = hit
        out = stacked[key][e.offset:e.offset + e.lead].reshape(e.shape)
        return out.astype(leaf.dtype) if cast else out

    return map_with_path(visit, base)


def unpad_buckets(plan: BucketPlan,
                  bufs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Strip the pad slices from per-bucket stacked buffers: ``(padded L,
    ...)`` -> ``(true L, ...)``.  Works on the momentum buckets and on the
    rule slot stripes alike (only the leading axis is interpreted).

    Together with :func:`repad_buckets` this is the elastic reshard: the
    *only* mesh-size-dependent quantity in the stacked layout is
    ``padded_size`` (= ceil(L / shard_size) * shard_size), and pad slices
    are identically zero by the engine's invariant, so unpad -> repad under
    the new plan relocates the state to any mesh size without touching a
    single real slice."""
    out = {}
    for b in plan.buckets:
        buf = bufs[b.key]
        if buf.shape[0] != b.padded:
            raise ValueError(
                f"bucket {b.key!r}: buffer holds {buf.shape[0]} slices but "
                f"the plan stacks {b.size} padded to {b.padded} — was this "
                f"buffer produced under a different plan / shard_size?")
        out[b.key] = buf[:b.size]
    return out


def repad_buckets(plan: BucketPlan,
                  bufs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Inverse of :func:`unpad_buckets` under ``plan``: zero-pad each
    true-``(L, ...)`` buffer back to the plan's padded size.  Zero fill is
    exact — pad slices carry zero grad/momentum/slot state by construction
    (see :func:`build_plan`)."""
    out = {}
    for b in plan.buckets:
        buf = jnp.asarray(bufs[b.key])
        if buf.shape[0] != b.size:
            raise ValueError(
                f"bucket {b.key!r}: buffer holds {buf.shape[0]} slices but "
                f"the plan stacks {b.size} — unpad under the writing plan "
                f"before repadding under this one")
        if b.padded > b.size:
            pad = jnp.zeros((b.padded - b.size,) + tuple(buf.shape[1:]),
                            buf.dtype)
            buf = jnp.concatenate([buf, pad], axis=0)
        out[b.key] = buf
    return out


def shard_count(bucket: Bucket, l_loc: int) -> int:
    """Number of ZeRO shards implied by a local momentum buffer of ``l_loc``
    slices: 1 (the full padded buffer) or ``padded_size / l_loc``.  Any
    other ``l_loc`` is a corrupt or mismatched buffer — a stale checkpoint
    restored onto a different mesh, or a plan rebuilt with a different
    ``pad_multiple`` — and silently ``dynamic_slice``-ing with it would
    produce garbage updates, so it raises instead."""
    psize = bucket.padded
    if l_loc < 1 or psize % l_loc:
        raise ValueError(
            f"bucket {bucket.key!r}: momentum buffer holds {l_loc} slices "
            f"but the bucket stacks {bucket.size} (padded to {psize}); "
            f"expected the full padded buffer or an exact 1/N shard with "
            f"{psize} % l_loc == 0 — was the optimizer state restored from "
            f"a different mesh or built with a different shard_size?")
    return psize // l_loc

