"""Segment reductions: the TPU group-by engine.

Replaces DataFusion's hash aggregate (reference: RowHash in its GroupBy
exec) with segment ops over dense integer group ids — the TPU-friendly
formulation (SURVEY.md §7.3 item 3): tags are already dictionary codes, so a
GROUP BY is (combine key codes) → (segment_sum/min/max) → (decompose codes).

Two group-id strategies:

- **dense grid** — total key cardinality is bounded (e.g. hosts × hours in
  TSBS double-groupby-all): group id = row-major mix of key codes; empty
  cells masked out after reduction. Sort-free, one scatter pass.
- **sort-based** — unbounded/sparse key space: sort rows by combined key,
  dense-rank by change points, reduce over ranks. Still static-shape.

Dtype rules mirror ops.masks: float aggs in the input float dtype, integer
sum/min/max in int64 (no float round-trip), mean always float. Empty
segments: float min/max/mean → NaN, int min/max → 0 (consult count).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.ops.masks import valid_mask

_I64_MIN = np.int64(np.iinfo(np.int64).min)
_I64_MAX = np.int64(np.iinfo(np.int64).max)


def combine_keys(
    keys: list[jnp.ndarray], cards: list[int]
) -> tuple[jnp.ndarray, int]:
    """Row-major combine of dense key codes into one int64 id per row.

    ``cards[i]`` is the (static) cardinality bound of ``keys[i]``. Codes
    outside [0, card) (e.g. -1 for "unseen") poison the row id to -1 so the
    caller's mask can drop it.
    """
    total = 1
    for c in cards:
        total *= int(c)
    out = jnp.zeros_like(keys[0], dtype=jnp.int64)
    bad = jnp.zeros(keys[0].shape, dtype=bool)
    for k, c in zip(keys, cards):
        k64 = k.astype(jnp.int64)
        bad = bad | (k64 < 0) | (k64 >= c)
        out = out * c + jnp.clip(k64, 0, c - 1)
    return jnp.where(bad, -1, out), total


def decompose_keys(seg_ids: jnp.ndarray, cards: list[int]) -> list[jnp.ndarray]:
    """Invert combine_keys for a dense grid: group id → per-key codes."""
    out = []
    rem = seg_ids.astype(jnp.int64)
    for c in reversed(cards):
        out.append((rem % c).astype(jnp.int32))
        rem = rem // c
    return list(reversed(out))


def _prep(values, seg_ids, num_segments, mask):
    """Shared validity/overflow-routing: returns (m, ids) with invalid rows
    routed to segment num_segments (sliced off by callers)."""
    m = valid_mask(values, mask if mask is not None else jnp.ones(values.shape, bool))
    m = m & (seg_ids >= 0) & (seg_ids < num_segments)
    ids = jnp.where(m, seg_ids, num_segments).astype(jnp.int32)
    return m, ids


def _seg_count(m, ids, ns, sorted_):
    return jax.ops.segment_sum(
        m.astype(jnp.int64), ids, num_segments=ns, indices_are_sorted=sorted_
    )


def segment_reduce(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    op: str,
    mask: jnp.ndarray | None = None,
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    """Masked, NaN-aware segment reduction.

    Invalid rows (mask False, NaN value, or seg_id outside [0,num_segments))
    contribute nothing.
    """
    m, ids = _prep(values, seg_ids, num_segments, mask)
    ns = num_segments + 1
    srt = indices_are_sorted
    is_float = jnp.issubdtype(values.dtype, jnp.floating)

    if op == "count":
        return _seg_count(m, ids, ns, srt)[:num_segments]

    if op == "sum":
        v = values if is_float else values.astype(jnp.int64)
        s = jax.ops.segment_sum(
            jnp.where(m, v, 0), ids, num_segments=ns, indices_are_sorted=srt
        )[:num_segments]
        if not is_float:
            # ints have no NULL repr on device; 0 matches the int min/max
            # convention (callers mask empty groups via their count)
            return s
        # SQL: SUM over zero rows is NULL, not 0 (surfaces only for
        # global aggregates — grouped empties are gmask-filtered)
        cnt = _seg_count(m, ids, ns, srt)[:num_segments]
        return jnp.where(cnt > 0, s, jnp.nan)

    if op in ("min", "max"):
        fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        if is_float:
            fill = jnp.inf if op == "min" else -jnp.inf
            out = fn(jnp.where(m, values, fill), ids, num_segments=ns,
                     indices_are_sorted=srt)[:num_segments]
            cnt = _seg_count(m, ids, ns, srt)[:num_segments]
            return jnp.where(cnt > 0, out, jnp.nan)
        fill = _I64_MAX if op == "min" else _I64_MIN
        v = values.astype(jnp.int64)
        out = fn(jnp.where(m, v, fill), ids, num_segments=ns,
                 indices_are_sorted=srt)[:num_segments]
        cnt = _seg_count(m, ids, ns, srt)[:num_segments]
        return jnp.where(cnt > 0, out, 0)

    if op == "mean":
        # ints: sum exactly in int64, divide in float (matches masked_reduce)
        v = values if is_float else values.astype(jnp.int64)
        s = jax.ops.segment_sum(
            jnp.where(m, v, 0), ids, num_segments=ns, indices_are_sorted=srt
        )[:num_segments]
        if not is_float:
            s = s.astype(jnp.float32)
        cnt = _seg_count(m, ids, ns, srt)[:num_segments]
        return jnp.where(cnt > 0, s / jnp.maximum(cnt, 1).astype(s.dtype), jnp.nan)

    raise ValueError(f"unknown segment op: {op}")


def segment_mean(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    return segment_reduce(values, seg_ids, num_segments, "mean", mask,
                          indices_are_sorted)


def segment_count(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    return segment_reduce(values, seg_ids, num_segments, "count", mask,
                          indices_are_sorted)


def segment_first_last(
    ts: jnp.ndarray,
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
    last: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-segment (timestamp, value) of the newest (or oldest) valid row.

    Two-pass, overflow-safe formulation (packing ts*N+idx can overflow
    int64 at high cardinality): pass 1 finds the extreme ts per segment;
    pass 2 picks the lowest row index achieving it and gathers the value.
    Reference semantics: TSBS `lastpoint` / mito2 last_row dedup
    (src/mito2/src/read/last_row.rs).
    """
    n = ts.shape[0]
    m, ids = _prep(values, seg_ids, num_segments, mask)
    ns = num_segments + 1

    if last:
        ext = jax.ops.segment_max(jnp.where(m, ts, _I64_MIN), ids, num_segments=ns)
    else:
        ext = jax.ops.segment_min(jnp.where(m, ts, _I64_MAX), ids, num_segments=ns)
    winner = m & (ts == ext[ids])
    idx = jnp.arange(n, dtype=jnp.int64)
    win_idx = jax.ops.segment_min(
        jnp.where(winner, idx, _I64_MAX), ids, num_segments=ns
    )[:num_segments]
    has = win_idx < _I64_MAX
    safe_idx = jnp.where(has, win_idx, 0)
    out_ts = jnp.where(has, ts[safe_idx], 0)
    if jnp.issubdtype(values.dtype, jnp.floating):
        out_val = jnp.where(has, values[safe_idx], jnp.nan)
    else:
        # int values keep their dtype exactly; empty segment -> 0, caller
        # consults a count for SQL NULL (module dtype convention)
        out_val = jnp.where(has, values[safe_idx], 0)
    return out_ts, out_val


def segment_distinct_count(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-segment count of DISTINCT valid values (COUNT(DISTINCT x)).

    Sort-based, TPU-friendly (no hash tables): lexsort rows by
    (segment, value), mark first occurrences at (segment, value) run
    boundaries, segment-sum the marks.  Works for any comparable dtype —
    dictionary codes for tags/strings, raw ints/floats for numerics;
    invalid rows (mask False, NaN, poisoned ids) are excluded.
    Reference semantics: DataFusion COUNT(DISTINCT) via
    src/query/src/datafusion.rs.
    """
    m = valid_mask(values, mask if mask is not None else jnp.ones(values.shape, bool))
    m = m & (seg_ids >= 0) & (seg_ids < num_segments)
    ids = jnp.where(m, seg_ids, num_segments).astype(jnp.int32)
    order = jnp.lexsort((values, ids))
    g = ids[order]
    v = values[order]
    first = jnp.concatenate([
        jnp.ones(1, dtype=bool),
        (g[1:] != g[:-1]) | (v[1:] != v[:-1]),
    ])
    return jax.ops.segment_sum(
        (first & (g < num_segments)).astype(jnp.int64),
        g,
        num_segments=num_segments + 1,
        indices_are_sorted=True,
    )[:num_segments]


def segmented_sum_scan(
    values: jnp.ndarray,
    ids: jnp.ndarray,
    starts: jnp.ndarray,
    ends: jnp.ndarray,
) -> jnp.ndarray:
    """Scatter-free per-segment float sums for NONDECREASING ids.

    Uses a segmented scan that resets at id boundaries instead of a global
    cumsum-diff: a global f32 prefix over millions of rows grows to
    magnitudes where eps(prefix) swamps small group sums, while the
    segmented scan bounds rounding error by each GROUP's own magnitude
    (same associative (value, id) trick as the min/max path below).

    ``values`` is [N] or [N, C] (already masked to 0 on invalid rows);
    ``starts``/``ends`` are the searchsorted segment boundaries. Empty
    segments return 0.
    """
    wide = values.ndim == 2

    def seg_add(a, b):
        av, ai = a
        bv, bi = b
        eq = ai == bi
        return jnp.where(eq[:, None] if wide else eq, av + bv, bv), bi

    scanned, _ids = jax.lax.associative_scan(seg_add, (values, ids))
    s = scanned[jnp.clip(ends - 1, 0, values.shape[0] - 1)]
    nonempty = ends > starts
    return jnp.where(nonempty[:, None] if wide else nonempty, s, 0)


def sorted_segment_reduce(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    op: str,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Scatter-free segment reduction for NONDECREASING seg_ids.

    When the group ids are sorted (data laid out by (series, time) with
    group keys monotone in that order), the reductions jax.ops.segment_*
    lowers to scatter become cumulative sums diffed at group boundaries
    (count, int sums) or a segmented associative scan (float sums,
    min/max). Caller guarantees sortedness of the VALID rows' ids;
    invalid rows may hold any id (they are neutralized).

    Reached only under GREPTIME_SORTED_SEGMENTS=force: the TPU compiler
    takes minutes over the associative scan past ~1M rows and does not
    finish at table size (ROADMAP A5), so no backend is handed this form
    by default even though the scatter form is slow on the chip.

    Semantics identical to segment_reduce.
    """
    is_float = jnp.issubdtype(values.dtype, jnp.floating)
    m = valid_mask(values, mask if mask is not None else jnp.ones(values.shape, bool))
    m = m & (seg_ids >= 0) & (seg_ids < num_segments)
    # out-of-range ids only occur in trailing padding rows (poisoned -1
    # codes); route them past the last segment so the array stays sorted.
    # WHERE-masked rows keep their (valid, sorted) ids and are neutralized
    # by the mask in every accumulation below.
    ids = jnp.where(
        (seg_ids < 0) | (seg_ids >= num_segments), num_segments, seg_ids
    ).astype(jnp.int32)

    grid = jnp.arange(num_segments, dtype=jnp.int32)
    # boundaries over the (sorted) id array
    starts = jnp.searchsorted(ids, grid, side="left")
    ends = jnp.searchsorted(ids, grid, side="right")

    def cs(x):
        return jnp.concatenate(
            [jnp.zeros(1, x.dtype), jnp.cumsum(x)]
        )

    cnt = (cs(m.astype(jnp.int64))[ends] - cs(m.astype(jnp.int64))[starts])
    if op == "count":
        return cnt
    if op in ("sum", "mean"):
        if is_float:
            s = segmented_sum_scan(jnp.where(m, values, 0), ids, starts, ends)
        else:
            # int64 cumsum-diff is exact — keep the cheaper single pass
            v = values.astype(jnp.int64)
            s = cs(jnp.where(m, v, 0))[ends] - cs(jnp.where(m, v, 0))[starts]
        if op == "sum":
            # SQL: float SUM over zero rows is NULL (matches
            # segment_reduce; ints keep 0 — no device NULL repr)
            return jnp.where(cnt > 0, s, jnp.nan) if is_float else s
        sf = s.astype(jnp.float32) if not is_float else s
        return jnp.where(cnt > 0, sf / jnp.maximum(cnt, 1).astype(sf.dtype),
                         jnp.nan)
    if op in ("min", "max"):
        if is_float:
            fill = jnp.inf if op == "min" else -jnp.inf
            v = jnp.where(m, values, fill)
        else:
            fill = _I64_MAX if op == "min" else _I64_MIN
            v = jnp.where(m, values.astype(jnp.int64), fill)
        combine = jnp.minimum if op == "min" else jnp.maximum

        def seg_op(a, b):
            # carry = (value, id); reset the running extreme at id changes
            av, ai = a
            bv, bi = b
            keep = ai == bi
            return jnp.where(keep, combine(av, bv), bv), bi

        scanned, _ids = jax.lax.associative_scan(seg_op, (v, ids))
        out = scanned[jnp.clip(ends - 1, 0, v.shape[0] - 1)]
        if is_float:
            return jnp.where(cnt > 0, out, jnp.nan)
        return jnp.where(cnt > 0, out, 0)
    raise ValueError(f"unknown sorted segment op: {op}")


def compact_groups(
    combined_ids: jnp.ndarray, mask: jnp.ndarray, num_groups: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based dense ranking for sparse key spaces.

    Returns (dense_ids [N] — rank of each row's group in sorted key order,
    group_keys [num_groups] — the combined key per rank, group_mask
    [num_groups]). ``num_groups`` is a static bound (≤ padded rows).
    Rows with mask False or a poisoned (-1) key get dense id num_groups
    (overflow, caller slices).
    """
    valid_row = mask & (combined_ids >= 0)
    key = jnp.where(valid_row, combined_ids, _I64_MAX)
    order = jnp.argsort(key)
    sorted_key = key[order]
    new_grp = jnp.concatenate(
        [jnp.array([0], jnp.int32),
         (sorted_key[1:] != sorted_key[:-1]).astype(jnp.int32)]
    )
    rank_sorted = jnp.cumsum(new_grp)
    # scatter ranks back to original row order
    dense = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    dense = jnp.where(valid_row, dense, num_groups)
    # representative key per rank
    group_keys = jnp.full((num_groups + 1,), _I64_MAX, dtype=jnp.int64)
    group_keys = group_keys.at[
        jnp.where(sorted_key != _I64_MAX, rank_sorted, num_groups)
    ].set(jnp.where(sorted_key != _I64_MAX, sorted_key, _I64_MAX))
    group_keys = group_keys[:num_groups]
    group_mask = group_keys != _I64_MAX
    return dense, group_keys, group_mask
