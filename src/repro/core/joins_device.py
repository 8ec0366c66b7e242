"""Device-resident (jittable) COO join tier (paper §4.4–§4.6).

The host tier in ``repro.core.joins`` materializes join outputs as numpy
COO sets — exact, nnz-proportional, but stuck on the host: every sparse
join forces a device→host→device round-trip and the whole-plan GSPMD
staging of ``repro.plan.executor`` cannot cross it. This module is the
same relational semantics expressed as pure JAX over **static-capacity
buffers**, so sparse joins trace into jit (and into the one-program SPMD
staging) like any dense operator.

The trick shared by every family is segment expansion over static
buffers: both entry sets compact row-major into nnz-bounded side buffers
(entries stay grouped by join key), each compacted entry of the probe
side owns one segment — its key's (or its match run's) whole partner
run — and the segments unroll into ``arange(capacity)`` slots via

    seg  = repeat(arange(n_entries), counts, total_repeat_length=cap)
    slot = t + (partner_run_base - segment_start)[seg]   # one gather

followed by cache-resident gathers of the pre-staged coordinate/value
buffers. ``capacity`` is static — chosen at plan time from the
propagated nnz bounds (``repro.plan.masks``) — and the true ``total``
comes back with the result so the executor can detect overflow and fall
back to the host oracle (values may have drifted under an unchanged
block mask). Slots past ``total`` (and merge results equal to zero,
matching the host tier's post-merge filter) are masked out of ``valid``.

Every function returns a ``DeviceCOO``: ``idx [cap, order]``
(int16 when every dimension fits, else int32), ``val [cap]``,
``valid [cap] bool``, ``total`` (scalar int32, the number of expansion
slots actually needed). ``coo_to_host`` converts to the host
``COOTensor`` at the jit boundary; inside a staged plan the buffers
stay on device end to end. The host tier remains the oracle these
implementations are property-tested against (``tests/test_sparse_device``).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core import bloom as bloommod
from repro.core.predicates import Field
from repro.core.sparsity import SparsityProfile


class DeviceCOO(NamedTuple):
    """Static-capacity COO buffer (a jit-friendly pytree of arrays)."""

    idx: jnp.ndarray     # [cap, order] int32
    val: jnp.ndarray     # [cap]
    valid: jnp.ndarray   # [cap] bool — slot holds a live (nonzero) entry
    total: jnp.ndarray   # scalar int32 — expansion slots actually required


def coo_to_host(coo: DeviceCOO, shape: Tuple[int, ...]):
    """Materialize a ``DeviceCOO`` as the host tier's ``COOTensor``."""
    import numpy as np

    from repro.core.joins import COOTensor
    from repro.obs.trace import annotate, span
    with span("d2h", what="coo"):
        keep = np.asarray(coo.valid)
        idx = np.asarray(coo.idx)
        val = np.asarray(coo.val)
        annotate(view_bytes=keep.nbytes + idx.nbytes + val.nbytes)
    return COOTensor(idx[keep].astype(np.int64), val[keep], shape)


def overflowed(coo: DeviceCOO) -> bool:
    """True when the static capacity was too small (results truncated)."""
    return int(coo.total) > int(coo.valid.shape[0])


# ---------------------------------------------------------------------------
# Shared machinery.
# ---------------------------------------------------------------------------

def _expand_meta(counts: jnp.ndarray, cap: int):
    """Per-segment prefix sums + the slot validity mask, without the
    expansion itself. Returns ``(ends, starts, valid, total)`` — the
    metadata both the fused ``coo_expand`` kernel (which re-derives
    segment ids from ``ends`` on the fly) and the repeat-based expansion
    need."""
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    starts = ends - counts           # exclusive prefix sum
    # int32 cumsum can wrap on a pathological total; a float32 shadow sum
    # (exact below 2²⁴ > any device capacity) catches that as an overflow
    total = jnp.where(
        jnp.sum(counts, dtype=jnp.float32) > jnp.float32(cap),
        _OVERFLOW_TOTAL, ends[-1])
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    return ends, starts, valid, total


def _segment_expand(counts: jnp.ndarray, cap: int):
    """Expand variable-size segments into ``cap`` static slots.

    Returns ``(seg, starts, valid, total)``: for each slot ``t < total``
    the segment it falls in, plus the exclusive per-segment prefix sum.
    Slot ``t``'s rank within its segment is ``t - starts[seg[t]]``;
    callers that really need a source position ``base[seg] + rank``
    should fold the base in as ``t + (base - starts)[seg]`` — one
    cap-sized gather instead of two. ``seg`` comes from ``jnp.repeat``
    (markedly faster on XLA CPU than a slot-range cumsum or
    searchsorted); slots past the total repeat the last segment id — the
    same clamp the downstream gathers need anyway (masked by ``valid``).
    """
    counts = counts.astype(jnp.int32)
    ends, starts, valid, total = _expand_meta(counts, cap)
    seg = jnp.repeat(jnp.arange(counts.shape[0], dtype=jnp.int32), counts,
                     total_repeat_length=cap)
    return seg, starts, valid, total


def _entry_compact(live: jnp.ndarray, cap: int):
    """Stable stream compaction of a flat boolean mask into ``cap`` slots.

    Returns ``(idx, count, slot_live)``: ``idx[s]`` is the flat source
    index of the ``s``-th live element (slots ≥ count clamp to the last
    index and must stay masked). Gather-formulated — slot ``s`` finds its
    source with a ``searchsorted`` over the inclusive prefix sum — because
    the scatter formulation serializes on XLA CPU; this way the work is
    O(n) cumsum + O(cap · log n) vectorized binary search.

    ``count > cap`` means entries were dropped — callers surface that
    through the overflow guard. This is what keeps the downstream sort /
    searchsorted work O(nnz bound) instead of O(m·n).

    Accepts ``live`` of rank 1 or 2 (row-major flattening either way):
    the rank-2 form computes the prefix sum as independent row scans +
    tiny row offsets, which XLA CPU runs several times faster than one
    long 1-D scan.
    """
    if live.ndim == 2:
        inner = jnp.cumsum(live, axis=1, dtype=jnp.int32)
        row_tot = inner[:, -1]
        off = jnp.cumsum(row_tot, dtype=jnp.int32) - row_tot
        pos = (inner + off[:, None]).reshape(-1)
    else:
        pos = jnp.cumsum(live, dtype=jnp.int32)   # inclusive live counts
    n = pos.shape[0]
    count = pos[-1]
    s = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.clip(jnp.searchsorted(pos, s + 1, side="left"),
                   0, n - 1).astype(jnp.int32)
    return idx, count, s < count


def _live(v: jnp.ndarray, inducing: bool) -> jnp.ndarray:
    return (v != 0) if inducing else jnp.ones(v.shape, bool)


def round_capacity(c: float) -> int:
    """Canonical COO buffer rounding: floor 8, multiple-of-8 — shared by
    the planner's capacity annotation and the per-call join API so their
    staged-cache keys and buffer shapes can never desynchronize."""
    return max(8, -(-int(c) // 8) * 8)


def _coord_dtype(*dims: int):
    """Narrowest dtype for output coordinates: the idx buffers dominate
    the capacity-sized write traffic, so halving them when every
    dimension fits int16 is a measurable win (``coo_to_host`` widens to
    int64 regardless)."""
    return jnp.int16 if max(dims) < (1 << 15) else jnp.int32


# sentinel total forcing the executor's overflow fallback when a SIDE
# buffer (not the expansion buffer) was too small for the actual entries
_OVERFLOW_TOTAL = jnp.int32(2 ** 30)


def _finish(idx: jnp.ndarray, vals: jnp.ndarray, valid: jnp.ndarray,
            total: jnp.ndarray) -> DeviceCOO:
    """Apply the post-merge zero filter. Slots outside ``valid`` keep
    whatever the clamped gathers produced — consumers must mask by
    ``valid`` (as ``coo_to_host`` does); blanking them here would cost a
    cap-sized ``where`` per buffer for purely cosmetic zeros."""
    return DeviceCOO(idx, vals, valid & (vals != 0),
                     total.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Join families. All mirrors of the host implementations in core.joins —
# same entry sets, same post-merge filter — expressed over static buffers.
# ---------------------------------------------------------------------------

def d2d_device(a: jnp.ndarray, b: jnp.ndarray, left: Field, right: Field,
               merge: Callable, prof: SparsityProfile, cap: int, *,
               cap_a: Optional[int] = None,
               cap_b: Optional[int] = None,
               kernel_backend: Optional[str] = None) -> DeviceCOO:
    """Single-dimension join (§4.4) as segment-based gathers.

    Replaces the host tier's Python per-key expansion loop. Both entry
    sets compact (row-major, so entries stay grouped by join key) into
    static side buffers; per-key cartesian-product sizes expand through
    the fused ``coo_expand`` registry kernel (segment ids + operand /
    coordinate gathers + merge in one pass). Output order 3:
    (key, other_A, other_B), D1-first layout.
    """
    from repro.kernels import registry
    aa = a if left is Field.RID else a.T
    bb = b if right is Field.RID else b.T
    d1 = min(aa.shape[0], bb.shape[0])  # inner join on the key domain
    aa, bb = aa[:d1, :], bb[:d1, :]
    d2, d3 = aa.shape[1], bb.shape[1]
    cap_a = aa.size if cap_a is None else min(cap_a, aa.size)
    cap_b = bb.size if cap_b is None else min(cap_b, bb.size)
    live_a = _live(aa, prof.inducing_x)
    live_b = _live(bb, prof.inducing_y)
    idx_a, na, slot_a = _entry_compact(live_a, cap_a)
    idx_b, nb_n, _ = _entry_compact(live_b, cap_b)
    cnt_b = jnp.sum(live_b, axis=1, dtype=jnp.int32)   # entries per key
    b_starts = jnp.cumsum(cnt_b, dtype=jnp.int32) - cnt_b
    # pre-gather coordinates and values into the compacted (nnz-sized)
    # buffers: the kernel's cap-sized expansion then reads from small,
    # cache-resident arrays instead of the full m·n matrices
    cdt = _coord_dtype(d1, d2, d3)
    key_a = idx_a // d2
    kc_a, cc_a = key_a.astype(cdt), (idx_a % d2).astype(cdt)
    col_b = (idx_b % d3).astype(cdt)
    av_c = aa.reshape(-1)[idx_a]
    bv_c = bb.reshape(-1)[idx_b]
    # expand over A *entries* (not keys): each compacted A entry owns one
    # segment — its key's whole B run — so the per-slot index math needs
    # no variable-divisor div/mod; the emitted order still matches the
    # host tier (keys ascending, row-major within a key)
    counts = jnp.where(slot_a, cnt_b[key_a], 0)
    ends, starts, valid, total = _expand_meta(counts, cap)
    delta = b_starts[key_a] - starts  # B-run base − own segment start
    idx, vals = registry.dispatch(
        "coo_expand", ends, delta, av_c, jnp.stack([kc_a, cc_a], axis=1),
        bv_c, col_b[:, None], backend=kernel_backend, merge=merge, cap=cap)
    total = jnp.where((na > cap_a) | (nb_n > cap_b), _OVERFLOW_TOTAL,
                      total)
    return _finish(idx, vals, valid, total)


def v2v_device(a: jnp.ndarray, b: jnp.ndarray, merge: Callable,
               prof: SparsityProfile, cap: int, *,
               cap_a: Optional[int] = None,
               cap_b: Optional[int] = None,
               use_bloom: bool = False,
               bloom_params: bloommod.BloomParams = bloommod.BloomParams(),
               kernel_backend: Optional[str] = None) -> DeviceCOO:
    """Entry join (§4.5): Bloom pre-filter + sort-merge, fully on device.

    Both entry sets first compact into static side buffers (``cap_a`` /
    ``cap_b``, plan-time nnz bounds), so the sort and the two
    ``searchsorted``s run over O(nnz) slots like the host tier — not over
    the full m·n cells. Match runs then expand through the segment
    machinery. The Bloom probe goes through ``kernels.registry.dispatch``
    (Pallas on TPU, jnp oracle elsewhere) — probing only zeroes *counts*,
    so false positives cost expansion slots but never change the result.
    """
    skip_zeros = prof.inducing_x or prof.inducing_y
    p, q = b.shape
    av, bv = a.reshape(-1), b.reshape(-1)
    cap_a = av.shape[0] if cap_a is None else min(cap_a, av.shape[0])
    cap_b = bv.shape[0] if cap_b is None else min(cap_b, bv.shape[0])
    idx_a, na, slot_a = _entry_compact(_live(a, skip_zeros), cap_a)
    idx_b, nb, slot_b = _entry_compact(_live(b, skip_zeros), cap_b)
    avc = av[idx_a]
    if use_bloom:
        from repro.kernels import registry
        filt = bloommod.build(bv, bloom_params, skip_zeros=skip_zeros)
        hits = registry.dispatch(
            "bloom_probe", filt, avc, backend=kernel_backend,
            num_hashes=bloom_params.num_hashes,
            log2_bits=bloom_params.log2_bits)
        slot_a = slot_a & hits
    sort_key = jnp.where(slot_b, bv[idx_b], jnp.inf)
    order_b = jnp.argsort(sort_key).astype(jnp.int32)
    skey = sort_key[order_b]
    lo = jnp.searchsorted(skey, avc, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(skey, avc, side="right").astype(jnp.int32)
    counts = jnp.where(slot_a, hi - lo, 0)
    # pre-gather output coordinates (and values) into nnz-sized sorted
    # buffers so the fused expansion reads cache-resident arrays
    n = a.shape[1]
    cdt = _coord_dtype(a.shape[0], n, p, q)
    arow, acol = (idx_a // n).astype(cdt), (idx_a % n).astype(cdt)
    bsorted = idx_b[order_b]
    brow, bcol = (bsorted // q).astype(cdt), (bsorted % q).astype(cdt)
    ends, starts, valid, total = _expand_meta(counts, cap)
    delta = lo - starts               # match-run base − own segment start
    # skey IS the matched B value buffer (exact equality join), so only
    # the A side needs a separate value buffer
    from repro.kernels import registry
    idx, vals = registry.dispatch(
        "coo_expand", ends, delta, avc, jnp.stack([arow, acol], axis=1),
        skey, jnp.stack([brow, bcol], axis=1), backend=kernel_backend,
        merge=merge, cap=cap)
    total = jnp.where((na > cap_a) | (nb > cap_b), _OVERFLOW_TOTAL, total)
    return _finish(idx, vals, valid, total)


def cross_device(a: jnp.ndarray, b: jnp.ndarray, merge: Callable,
                 prof: SparsityProfile, cap: int, *,
                 cap_a: Optional[int] = None,
                 cap_b: Optional[int] = None) -> DeviceCOO:
    """Cross product (§4.2): all pairs over the compacted entry sets."""
    n, q = a.shape[1], b.shape[1]
    av, bv = a.reshape(-1), b.reshape(-1)
    cap_a = av.shape[0] if cap_a is None else min(cap_a, av.shape[0])
    cap_b = bv.shape[0] if cap_b is None else min(cap_b, bv.shape[0])
    idx_a, na, _ = _entry_compact(_live(a, prof.inducing_x), cap_a)
    idx_b, nb, _ = _entry_compact(_live(b, prof.inducing_y), cap_b)
    # na·nb can wrap int32 for large entry sets; the float32 shadow
    # product (cap ≤ 2²³, well inside f32 exactness) guards the compare
    total = jnp.where(
        na.astype(jnp.float32) * nb.astype(jnp.float32) > jnp.float32(cap),
        _OVERFLOW_TOTAL, na * nb)
    t = jnp.arange(cap, dtype=jnp.int32)
    nb1 = jnp.maximum(nb, 1)
    ia = idx_a[jnp.clip(t // nb1, 0, cap_a - 1)]
    ib = idx_b[jnp.clip(t % nb1, 0, cap_b - 1)]
    vals = merge(av[ia], bv[ib])
    cdt = _coord_dtype(a.shape[0], n, b.shape[0], q)
    idx = jnp.stack([(ia // n).astype(cdt), (ia % n).astype(cdt),
                     (ib // q).astype(cdt), (ib % q).astype(cdt)], axis=1)
    total = jnp.where((na > cap_a) | (nb > cap_b), _OVERFLOW_TOTAL, total)
    return _finish(idx, vals, t < jnp.minimum(total, cap), total)


def d2v_device(a: jnp.ndarray, b: jnp.ndarray, dim: Field, merge: Callable,
               prof: SparsityProfile, cap: int, *,
               cap_a: Optional[int] = None) -> DeviceCOO:
    """Dimension-entry join (§4.6): γ = dim_A = val_B.

    Every B entry whose value is an integral index in range routes to one
    row (or column) of A; the per-entry segment is that line's live cells
    (found through the same row-major entry compaction as D2D).
    """
    q = b.shape[1]
    aa = a if dim is Field.RID else a.T
    limit, d2 = aa.shape
    cap_a = aa.size if cap_a is None else min(cap_a, aa.size)
    bv = b.reshape(-1)
    as_int = bv.astype(jnp.int32)
    # zero B entries are NULL and never join (even though 0 is a valid
    # dimension index) — matching the host tier's nonzero entry set
    valid_b = (bv != 0) & (bv == as_int.astype(bv.dtype)) \
        & (as_int >= 0) & (as_int < limit)
    bkey = jnp.clip(as_int, 0, limit - 1)
    live_a = _live(aa, prof.inducing_x)
    fa_all = aa.reshape(-1)
    idx_a, na, _ = _entry_compact(live_a, cap_a)
    cnt_a = jnp.sum(live_a, axis=1, dtype=jnp.int32)
    a_starts = jnp.cumsum(cnt_a, dtype=jnp.int32) - cnt_a
    counts = jnp.where(valid_b, cnt_a[bkey], 0)
    e, starts, valid, total = _segment_expand(counts, cap)
    key = bkey[e]
    delta = a_starts[bkey] - starts   # A-run base − own segment start
    fa = idx_a[jnp.clip(jnp.arange(cap, dtype=jnp.int32) + delta[e],
                        0, cap_a - 1)]
    col = fa % d2
    vals = merge(fa_all[fa], bv[e])
    i, j = (key, col) if dim is Field.RID else (col, key)
    cdt = _coord_dtype(limit, d2, b.shape[0], q)
    idx = jnp.stack([i.astype(cdt), j.astype(cdt),
                     (e // q).astype(cdt), (e % q).astype(cdt)], axis=1)
    total = jnp.where(na > cap_a, _OVERFLOW_TOTAL, total)
    return _finish(idx, vals, valid, total)


def v2d_device(a: jnp.ndarray, b: jnp.ndarray, dim: Field, merge: Callable,
               prof: SparsityProfile, cap: int, *,
               cap_a: Optional[int] = None) -> DeviceCOO:
    """val_A = dim_B: the D2V mirror with roles (and index blocks) swapped.
    ``cap_a`` sizes the compaction of B — the line-matrix side here."""
    flipped = SparsityProfile(inducing_x=prof.inducing_y,
                              inducing_y=prof.inducing_x)
    t = d2v_device(b, a, dim, lambda x, y: merge(y, x), flipped, cap,
                   cap_a=cap_a)
    return DeviceCOO(t.idx[:, [2, 3, 0, 1]], t.val, t.valid, t.total)


# ---------------------------------------------------------------------------
# Host-side capacity planning (used by repro.plan.masks for leaf joins and
# by direct callers sizing a one-off device join).
# ---------------------------------------------------------------------------

def exact_capacity(a, b, pred, prof: SparsityProfile) -> int:
    """Exact expansion-slot count of a COO join — one O(nnz log nnz)
    host scan over the input entry sets (no merge evaluation; the
    post-merge zero filter can only shrink the result, so this is also a
    guaranteed buffer capacity for the current values)."""
    import numpy as np

    from repro.core.predicates import JoinKind
    a = np.asarray(a)
    b = np.asarray(b)
    kind = pred.kind
    if kind is JoinKind.CROSS:
        na = np.count_nonzero(a) if prof.inducing_x else a.size
        nb = np.count_nonzero(b) if prof.inducing_y else b.size
        return int(na) * int(nb)
    if kind is JoinKind.D2D:
        aa = a if pred.left is Field.RID else a.T
        bb = b if pred.right is Field.RID else b.T
        d1 = min(aa.shape[0], bb.shape[0])
        ca = np.count_nonzero(aa[:d1], axis=1) if prof.inducing_x \
            else np.full(d1, aa.shape[1], np.int64)
        cb = np.count_nonzero(bb[:d1], axis=1) if prof.inducing_y \
            else np.full(d1, bb.shape[1], np.int64)
        return int((ca.astype(np.int64) * cb).sum())
    if kind is JoinKind.V2V:
        skip = prof.inducing_x or prof.inducing_y
        av, bv = a.reshape(-1), b.reshape(-1)
        if skip:
            av, bv = av[av != 0], bv[bv != 0]
        bv = np.sort(bv)
        lo = np.searchsorted(bv, av, side="left")
        hi = np.searchsorted(bv, av, side="right")
        return int((hi - lo).sum())
    if kind in (JoinKind.D2V, JoinKind.V2D):
        if kind is JoinKind.V2D:  # mirror: roles swap, profile flips
            a, b = b, a
            prof = SparsityProfile(prof.inducing_y, prof.inducing_x)
            dim = pred.right
        else:
            dim = pred.left
        aa = a if dim is Field.RID else a.T
        bv = b.reshape(-1)
        as_int = bv.astype(np.int64)
        valid = (bv != 0) & (bv == as_int) & (as_int >= 0) \
            & (as_int < aa.shape[0])
        keys = as_int[valid]
        cnt = np.count_nonzero(aa, axis=1) if prof.inducing_x \
            else np.full(aa.shape[0], aa.shape[1], np.int64)
        return int(cnt[keys].sum())
    raise ValueError(kind)
