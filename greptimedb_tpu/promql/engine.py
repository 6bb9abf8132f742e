"""PromQL evaluation: range queries as dense [series, steps] tensor programs.

Pipeline per selector (SURVEY.md §3.3's hot loop, TPU-shaped):
1. host: match series against label matchers over the region's series
   registry (dictionary codes, no string work on device);
2. device: one jitted window kernel per (table shape-class, range, steps)
   gathers the matched series' samples that can fall in a window out of
   the (tsid, ts)-sorted resident table into a dense [series, W] slab and
   computes per-(series, step) window stats on it — boundaries by counting
   slab timestamps, sums by counter-reset-adjusted prefix sums along W
   (exact Prometheus extrapolation, reference
   src/promql/src/functions/extrapolate_rate.rs:56), min/max under the
   window mask;
3. device: cross-series aggregation = segment reduction over the series
   axis; binary-op vector matching joins series on host, aligns rows on
   device.

NaN encodes "absent" throughout (Prometheus staleness semantics).
"""

from __future__ import annotations

import collections
import collections.abc
import math
import re
import typing
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.compile import named_jit
from greptimedb_tpu.errors import PlanError, TableNotFound, Unsupported
from greptimedb_tpu.promql.parser import (
    Aggregation, BinaryExpr, FunctionCall, LabelMatcher, NumberLit, PromExpr,
    StringLit, SubqueryExpr, UnaryExpr, VectorSelector, parse_promql,
)
from greptimedb_tpu.storage.memtable import TSID
from greptimedb_tpu.utils.tracing import (
    TRACER, count_histogram_dispatch, count_window_dispatch,
)

DEFAULT_LOOKBACK_S = 300.0

_I64_MAX = np.int64(np.iinfo(np.int64).max)


class LazySeriesLabels(collections.abc.Sequence):
    """Label dicts for a matched series set, decoded ON DEMAND.

    The round-5 profile showed per-eval O(series) host work dominating the
    1M-series PromQL bench; the single largest term was select_series
    materializing one Python dict per matched series.  This sequence keeps
    only the tsid vector plus references into the region's dictionary
    state (codes + vocabularies) and builds a dict only when someone
    actually indexes it — aggregation never does (group ids come from the
    code columns), so a `sum by(pod) (rate(m[5m]))` run decodes exactly
    the output groups.

    Also carries the selection's provenance (region id, generation,
    matcher key) so eval_aggregation can key its resident group-id cache.
    ``materializations`` counts dict constructions process-wide — the
    tier-1 guard test pins it to O(output groups).
    """

    materializations = 0

    def __init__(self, idx, tag_names, values, tsids, region_id: int,
                 generation: int, matcher_key: tuple, cache):
        self.idx = idx  # SeriesInvertedIndex (codes + vocabs)
        self.tag_names = tag_names
        self.values = values  # column -> raw encoder values (code-indexed)
        self.tsids = tsids  # np.int32 [S]
        self.region_id = region_id
        self.generation = generation
        self.matcher_key = matcher_key
        self.cache = cache  # PromLayoutCache or None

    def _label_at(self, i: int) -> dict:
        LazySeriesLabels.materializations += 1
        tsid = int(self.tsids[i])
        codes = self.idx.codes
        values = self.values
        return {
            name: values[name][int(codes[name][tsid])]
            for name in self.tag_names
            if 0 <= codes[name][tsid] < len(values[name])
        }

    def __len__(self) -> int:
        return len(self.tsids)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._label_at(j) for j in range(*i.indices(len(self)))]
        return self._label_at(i)

    def __eq__(self, other):
        if not isinstance(other, (list, tuple, collections.abc.Sequence)):
            return NotImplemented
        return len(self) == len(other) and all(
            a == b for a, b in zip(self, other))

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq

    def __repr__(self) -> str:
        return f"<LazySeriesLabels n={len(self)}>"


class LazyGroupLabels(collections.abc.Sequence):
    """Aggregation output labels, decoded per GROUP on demand: group g's
    dict comes from its representative (first-appearance) input series via
    the host group-key rule, so semantics are identical to the eager loop
    while only ng dicts are ever built."""

    def __init__(self, source, rep_rows, key_fn):
        self.source = source  # input labels (usually LazySeriesLabels)
        self.rep_rows = rep_rows  # np [ng] row index of each group's rep
        self.key_fn = key_fn  # lab dict -> ((k, str v), ...) group key

    def __len__(self) -> int:
        return len(self.rep_rows)

    def _label_at(self, g: int) -> dict:
        return dict(self.key_fn(self.source[int(self.rep_rows[g])]))

    def __getitem__(self, g):
        if isinstance(g, slice):
            return [self._label_at(j) for j in range(*g.indices(len(self)))]
        return self._label_at(g)

    def __eq__(self, other):
        if not isinstance(other, (list, tuple, collections.abc.Sequence)):
            return NotImplemented
        return len(self) == len(other) and all(
            a == b for a, b in zip(self, other))

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq

    def __repr__(self) -> str:
        return f"<LazyGroupLabels n={len(self)}>"


@dataclass
class EvalResult:
    """A (possibly scalar) instant-vector time series matrix."""

    values: jnp.ndarray  # [S, T] f32; NaN = absent
    labels: "list[dict] | LazySeriesLabels | LazyGroupLabels"  # len S
    is_scalar: bool = False

    @property
    def num_series(self) -> int:
        return len(self.labels)


def matcher_pred(matcher: LabelMatcher):
    """Matcher → (term predicate, negate) — the single definition of
    PromQL matcher semantics, evaluated per DISTINCT term by the inverted
    index (=~ is fully anchored, as in Prometheus)."""
    if matcher.op == "=":
        return (lambda t, mv=matcher.value: t == mv), False
    if matcher.op == "!=":
        return (lambda t, mv=matcher.value: t == mv), True
    if matcher.op in ("=~", "!~"):
        rx = re.compile(matcher.value)
        return (lambda t, rx=rx: rx.fullmatch(t) is not None), (
            matcher.op == "!~"
        )
    raise PlanError(f"bad matcher {matcher.op}")


def _series_group_ids(idx, tsids: np.ndarray, grouping, without: bool):
    """Vectorized by/without group assignment from dictionary-encoded tag
    codes — no per-series Python.  Per relevant column, codes remap to
    canonical str-level term ids (missing merges with "" for ``by``,
    stays distinct for ``without`` — exactly the information the host
    group-key tuple carries); columns combine mixed-radix with dense
    re-encoding before any possible int64 overflow; final ids renumber by
    first appearance so group order matches the host enumeration.

    Returns (gid_dev [S] i32, ng, rep_rows np [ng], row_order_dev [S],
    seg_start np [ng])."""
    gids, ng, rep_rows = _group_ids_host(idx, tsids, grouping, without)
    row_order = np.argsort(gids, kind="stable")
    seg_start = np.searchsorted(gids[row_order], np.arange(ng))
    return (jnp.asarray(gids), ng, rep_rows, jnp.asarray(row_order),
            seg_start)


def _group_ids_host(idx, tsids: np.ndarray, grouping, without: bool):
    """``_series_group_ids``' numbering on the host: (gids np [S] i32,
    ng, rep_rows np [ng])."""
    if without:
        use = sorted(n for n in idx.tag_names if n not in grouping)
    else:
        use = sorted(n for n in grouping if n in idx.codes)
    S = len(tsids)
    tsids64 = tsids.astype(np.int64)
    combined = np.zeros(S, dtype=np.int64)
    ncomb = 1
    for name in use:
        codes = idx.codes_for(name, tsids64)
        V = len(idx.vocabs.get(name, []))
        remap, ncanon = idx.canonical_codes(name, merge_missing_empty=not without)
        pres = (codes >= 0) & (codes < V)
        comp = remap[np.where(pres, codes, V)]
        if ncanon > 1 and ncomb > (1 << 62) // ncanon:
            _u, combined = np.unique(combined, return_inverse=True)
            ncomb = len(_u)
        combined = combined * ncanon + comp
        ncomb *= max(ncanon, 1)
    _uniq, first_idx, inv = np.unique(
        combined, return_index=True, return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty(len(_uniq), dtype=np.int64)
    rank[order] = np.arange(len(_uniq))
    return rank[inv].astype(np.int32), len(_uniq), first_idx[order]


def _group_key(grouping, without: bool):
    """The host group-key rule: label dict → ((name, str value), ...),
    the one definition both group-id providers and the output labels
    read."""

    def key(lab: dict) -> tuple:
        if without:
            keys = sorted(k for k in lab if k not in grouping)
        else:
            keys = sorted(grouping or ())
        return tuple((k, str(lab.get(k, ""))) for k in keys)

    return key


def _bucket_bound(le) -> float:
    """An ``le`` label's upper bound (``+Inf`` included), or NaN where it
    is missing or does not parse: Prometheus skips such a series."""
    try:
        return float(str(le))
    except ValueError:
        return math.nan


def _series_bucket_slots(idx, tsids: np.ndarray, outer):
    """The histogram numbering of a selection, vectorized from the
    dictionary codes like ``_series_group_ids``: outer group o by the
    labels ``outer`` (``by`` semantics), bucket slot k by the parsed
    ``le`` value among the selection's distinct bounds in ascending
    order (``1`` and ``1.0`` are one slot), series id o·K + k, so that a
    segment sum into G·K slots reshapes to [G, K, ·].  A series whose
    ``le`` does not parse goes to the dead slot G·K.  Returns (slots np
    [S] i32, G, K, bounds np f64 [K], rep_rows np [G], filled slots), or
    None where no series has a bucket."""
    gids, ng, rep_rows = _group_ids_host(idx, tsids, outer, False)
    vocab = idx.vocabs.get("le", [])
    parsed = np.array([_bucket_bound(t) for t in vocab] + [math.nan])
    codes = idx.codes_for("le", tsids.astype(np.int64))
    bound = parsed[np.where((codes >= 0) & (codes < len(vocab)), codes,
                            len(vocab))]
    return _number_slots(gids, ng, bound, rep_rows)


def _number_slots(gids: np.ndarray, ng: int, bound: np.ndarray, rep_rows):
    ok = ~np.isnan(bound)
    bounds = np.unique(bound[ok])
    if not len(bounds):
        return None
    k = len(bounds)
    slots = np.where(ok, gids.astype(np.int64) * k
                     + np.searchsorted(bounds, np.where(ok, bound, 0.0)),
                     ng * k)
    filled = len(np.unique(slots[ok]))
    return slots.astype(np.int32), ng, k, bounds, rep_rows, filled


def _label_bucket_slots(labels):
    """``_series_bucket_slots`` over label dicts (whatever produced the
    series): the outer group is every label but ``le``.  Returns the same
    tuple with the outer groups' label dicts in place of rep_rows."""
    n = len(labels)
    gids = np.zeros(n, np.int32)
    bound = np.empty(n)
    groups: dict[tuple, int] = {}
    out_labels: list[dict] = []
    for i, lab in enumerate(labels):
        bound[i] = _bucket_bound(lab.get("le", ""))
        if math.isnan(bound[i]):
            continue
        key = tuple(sorted((k, str(v)) for k, v in lab.items() if k != "le"))
        if key not in groups:
            groups[key] = len(out_labels)
            out_labels.append({k: v for k, v in lab.items() if k != "le"})
        gids[i] = groups[key]
    return _number_slots(gids, len(out_labels), bound, out_labels)


# ---------------------------------------------------------------------------
# Window kernels
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WindowParams:
    """Static shape-class key for window kernels. start_ms is deliberately
    NOT here — it is a traced argument, so repeated queries at different
    times reuse one compiled program."""

    step_ms: int
    num_steps: int
    range_ms: int  # window width (lookback for instant selectors)
    num_sel: int  # padded selected series count
    total_series: int
    kind: str  # which stats to compute
    # samples gathered a matched series (``slab_width``): a function of
    # the query's span and the layout's spacing, never of a request's
    # times, so every evaluation of one dashboard panel shares a program
    slab_w: int
    # rounds of the search for a series' first sample in its own run
    # (``search_bits``): 2**run_bits exceeds the layout's longest run, so it
    # moves only when the longest series doubles
    run_bits: int
    # the layout carries the values' low word (``SelectorData.sort_layout``:
    # a DOUBLE column that passes 2^24): the kernel takes one array more
    # and works on f64(high) + f64(low).  Left out of a narrow class's
    # canonical key (compile/shape.py), which stays what it was
    wide: bool = field(default=False, metadata={"omit_default": True})


_KERNEL_CACHE: dict[WindowParams, object] = {}

# widest slab that is swept: up to it a window edge is ONE compare-select
# traversal of [S, T, F] that counts the edge and reads every 32-bit word
# of what the kind reads there (``_read_edges``: 4 to 6 ps a cell on a v5e
# for the count and three or four words, where a pass of its own for each
# cost 2 to 8 ps, an f64 the most), min/max two more, and the gathered
# chunks are folded to the F = max(W, chunk) columns a window can read
# (``Slab.fold``: counts and one-hot picks need no time order); past it
# searches and gathers along the gathered slab as it is (10 ns an element
# there, whatever W; they need ascending rows and would save 128 columns
# of 8,320) — the same integers and the same picked words either way
# (PERF.md, PRs 31, 35 and 38)
_SWEEP_WIDTH = 1 << 13


def _pow2(n: int) -> int:
    """The power of two at or above ``n`` (1 for n ≤ 1)."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def _split_i64(x):
    """An int64 array as (high int32, low uint32) words.  The resident
    layout keeps timestamps this way: the TPU has no 64-bit integers, and
    an int64 ARGUMENT is split into its words by every program that takes
    it — two table-sized writes a request; words split once, when the
    layout is built, are read in place."""
    return (x >> 32).astype(jnp.int32), x.astype(jnp.uint32)


def _join_i64(hi, lo):
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def _join_f64(hi, lo=None):
    """A value from its f32 words: the one word of a narrow layout as it
    is, the two of a wide one (storage/cache.py ``low_word_col``) as
    f64(hi) + f64(lo) — the form the TPU itself keeps an f64 in."""
    if lo is None:
        return hi
    return hi.astype(jnp.float64) + lo.astype(jnp.float64)


def _split_f64(x):
    """An f64 array as the two f32 words ``_join_f64`` joins: hi = f32(x),
    lo = f32(x − hi).  On the TPU that is the f64's own representation, so
    the joined words are ``x`` again bit for bit; elsewhere they hold its
    first 48 bits.  Taken on a [S, G] slab so that what crosses an
    [S, T, F] traversal is 32-bit words (``_read_edges``).  ±inf keeps a
    zero low word (inf − inf would make it NaN)."""
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    return hi, jnp.where(jnp.isinf(hi), 0, lo)


def slab_width(step_ms: int, num_steps: int, range_ms: int, spacing: int,
               max_run: int) -> int:
    """Slab width W for one shape class: every sample a series can have
    in (start − range, end] fits when consecutive samples lie at least
    ``spacing`` apart (the layout's densest spacing), rounded up to a
    power of two and capped at the longest series' padded run.
    Irregular data with a tiny smallest gap simply gets the cap: the
    whole series row."""
    span = (num_steps - 1) * step_ms + range_ms
    return min(_pow2(span // max(int(spacing), 1) + 2), _pow2(max_run))


def search_bits(max_run: int) -> int:
    """Search rounds that cover every series' run: the table has 2**24
    rows where a series has a hundred, and each round is a gather of two
    words a matched series (10 ns an element on a v5e, PERF.md)."""
    return max(int(max_run), 1).bit_length()


def swept_columns(slab_w: int, n: int) -> int:
    """Columns of one matched series that every [S, T, ·] pass of a window
    program runs over, for slab width ``slab_w`` on a layout of ``n`` rows
    read in chunks of gcd(n, 128): the folded slab's max(W, chunk) where
    the slab is swept, the gathered chunks (one more) where it is
    searched.  Static shapes only: ``_slab_geometry`` takes its widths
    from here, and so does the counter that says the fold engaged."""
    c = math.gcd(n, 128)
    readable = -(-slab_w // c) * c
    return readable if slab_w <= _SWEEP_WIDTH else readable + c


class SortLayout(typing.NamedTuple):
    """A resident table's columns in (tsid, ts) order with the row pointer
    (``_build_sort_layout``): the one argument every window program takes
    its table as.  WIDE where it carries the values' low word (a DOUBLE
    column that passes 2^24, storage/cache.py ``low_word_col``); a narrow
    layout has ``None`` there, which is no leaf, so its programs take the
    four arrays they always took."""

    ts_hi: jnp.ndarray  # [N] i32, the timestamps' words (``_split_i64``)
    ts_lo: jnp.ndarray  # [N] u32
    val_s: jnp.ndarray  # [N] f32
    row_ptr: jnp.ndarray  # [total_series + 1] i32
    val_lo: "jnp.ndarray | None" = None  # [N] f32: v − f64(val_s)

    @property
    def wide(self) -> bool:
        return self.val_lo is not None


def search_rounds(run_bits: int, n: int) -> int:
    """Scalar rounds of the first-sample search on a layout of ``n`` rows
    read in chunks of c = gcd(n, 128): the rounds for bits ``run_bits − 1``
    down to log2(c); the last log2(c) are one count over the two gathered
    chunks that hold the c candidates left (``_first_rows``).  Static,
    like ``sweep_passes``: the program's rounds and the dispatch counter
    both come from here."""
    return max(run_bits - (math.gcd(n, 128).bit_length() - 1), 0)


def count_dispatch(p: WindowParams, args, selected: int,
                   programs: int = 1) -> None:
    """Count one dispatch of ``programs`` window programs of class ``p``
    over the kernel arguments ``args`` (``_prep_window``'s): ``p``'s own
    and, with two, the sizing pass that places the edges for it."""
    n = args[0].val_s.shape[0]
    count_window_dispatch(
        selected, p.num_sel, p.slab_w, swept_columns(p.slab_w, n), programs,
        wide=p.wide, passes=sweep_passes(p.kind, p.slab_w)
        + (programs - 1) * sweep_passes("cnt_max", p.slab_w),
        rounds=search_rounds(p.run_bits, n))


def _count_le(probe, length, thr, bits: int, lowest: int = 0):
    """For each query, how many of the first ``length`` elements of its
    ascending run are ≤ ``thr``; ``probe(i)`` reads element ``i`` of each
    query's run.  A branchless binary search unrolled over ``bits``
    rounds (2**bits must exceed every length), each round one gather of
    a scalar a query — no loop the compiler carries an operand through.
    With ``lowest`` the rounds below that bit are left out: the count
    comes out rounded down to a multiple of 2**lowest."""
    c = jnp.zeros_like(length)
    for k in reversed(range(lowest, bits)):
        cand = c + (1 << k)
        c = jnp.where((cand <= length) & (probe(cand - 1) <= thr), cand, c)
    return c


def _row_pointer(ts_s, tsid_s, n_valid, total_series: int):
    """Query-independent geometry of a (tsid, ts)-sorted layout whose
    ``n_valid`` valid rows come first: the CSR row pointer
    i32[total_series + 1] (series s owns rows [ptr[s], ptr[s+1])), the
    densest spacing (smallest gap between two consecutive samples of one
    series) and the longest run."""
    n = ts_s.shape[0]
    sids = jnp.arange(total_series + 1, dtype=jnp.int32)
    row_ptr = _count_le(
        lambda i: tsid_s[jnp.clip(i, 0, n - 1)],
        jnp.broadcast_to(n_valid.astype(jnp.int32), sids.shape), sids - 1,
        n.bit_length())
    same = (tsid_s[1:] == tsid_s[:-1]) & (
        jnp.arange(1, n, dtype=jnp.int32) < n_valid)
    spacing = jnp.min(jnp.where(same, ts_s[1:] - ts_s[:-1], _I64_MAX),
                      initial=_I64_MAX)
    max_run = jnp.max(row_ptr[1:] - row_ptr[:-1])
    return row_ptr, spacing, max_run


@named_jit("promql_sort_layout", static_argnums=4)
def _build_sort_layout(ts, val, tsid, mask, total_series: int, val_lo=None):
    """Composite-key sort of a resident table, QUERY-INDEPENDENT: the key
    packs (tsid, ts − ts_min) with a stride covering the table's full time
    span, so the permutation (and everything derived from it) depends
    only on the data — it is built once per (region generation, field
    column) and served resident by PromLayoutCache instead of being
    re-derived inside every window kernel call.  Invalid rows (padding,
    NULL values) sort to the end via a +inf key, so each series' run
    holds valid samples only.

    Returns (ts_hi, ts_lo, val_s, row_ptr, spacing, max_run): the sorted
    columns (timestamps as ``_split_i64`` words) and ``_row_pointer``'s
    geometry (spacing/max_run are 0-d; the caller reads them once, as
    host integers, when the layout is built).  With ``val_lo`` (the
    resident table's low word of a DOUBLE column, storage/cache.py) one
    output more, the low word sorted the same way: a value travels as two
    f32 words the way a timestamp travels as two 32-bit words.  Without
    it the program is, text for text, the one it was before there were
    wide layouts (a compile cache that holds it keeps serving it);
    ``SelectorData.sort_layout`` names the outputs (``SortLayout``).
    """
    valid = mask & ~jnp.isnan(val)
    any_valid = valid.any()
    ts_min = jnp.where(
        any_valid, jnp.min(jnp.where(valid, ts, _I64_MAX)), jnp.int64(0))
    ts_max = jnp.where(
        any_valid,
        jnp.max(jnp.where(valid, ts, jnp.int64(-(1 << 62)))), jnp.int64(0))
    kp = ts_max - ts_min + 2  # stride: rel = ts - ts_min ∈ [0, kp-2]
    key = jnp.where(valid, tsid.astype(jnp.int64) * kp + (ts - ts_min),
                    _I64_MAX)
    order = jnp.argsort(key)
    ts_s = ts[order]
    out = _split_i64(ts_s) + (val[order],) + _row_pointer(
        ts_s, tsid[order], valid.sum(dtype=jnp.int32), total_series)
    return out if val_lo is None else out + (val_lo[order],)


def _fold(a, off, f: int):
    """[S, f + chunk] gathered columns → the [S, f] a window can read.
    Gathered columns j and j + f are never both readable (j is iff
    j ≥ off, j + f iff j < off), so one select over the first chunk folds
    the sentinel chunk away; the samples come out rotated by ``off``,
    which a count or a one-hot pick does not see."""
    c = a.shape[1] - f
    first = jnp.where(
        jnp.arange(c, dtype=jnp.int32)[None, :] < off[:, None],
        a[:, f:], a[:, :c])
    return first if f == c else jnp.concatenate([first, a[:, c:f]], axis=1)


class Slab(typing.NamedTuple):
    """The matched series' samples that can fall in (start − range, end],
    and the window edges over them — see _slab_geometry.

    ``rel``, ``val`` and ``ok`` are the GATHERED chunks in time order,
    [S, G]: what a prefix or a neighbour compare needs.  The [S, T, ·]
    passes run over ``fold`` of them, [S, width]: where the slab is swept
    the F = G − chunk readable columns rotated by ``off``, no sentinel
    chunk; where it is searched (ascending rows) the gathered slab as it
    is.  ``lo``/``hi`` index a window's samples in time order from the
    slab's own origin (the first readable sample where it is swept, the
    first gathered column where it is searched); ``col`` turns such an
    index into a column of a folded array.

    ``_slab_gather`` leaves the edges ``None``: ``_read_edges`` places
    them, alone (``_slab_geometry``) or in the traversal that reads a
    window's first and last sample (``_window_body``).  A traversal of
    [S, T, width] costs a fixed part a (series, step) cell it writes and a
    part a column and an operation (PERF.md section 5), so what is read
    at one edge is read in one traversal, as 32-bit words: ``val_words``
    are the value's own (one f32 off a narrow layout, the two a wide
    layout keeps), an f64 that the program made goes through
    ``_split_f64``."""

    rel: jnp.ndarray  # [S, G] ts − start_ms; sentinels outside the run
    val: jnp.ndarray  # [S, G] 0 outside the run; f32, f64 off a wide layout
    val_words: tuple  # [S, G] f32 each: ``val`` as ``_join_f64`` joins it
    ok: jnp.ndarray  # [S, G] column holds a readable sample of the series
    off: jnp.ndarray  # [S] i32 gathered column of the first readable row
    sel_ok: jnp.ndarray  # [S]
    thr: tuple  # ([T], [T]) rel of each window's open start and of its end
    sweep: bool  # W is within _SWEEP_WIDTH: the slab is folded and swept
    width: int  # columns the [S, T, ·] passes run over (``swept_columns``)
    lo: "jnp.ndarray | None" = None  # [S, T] index of a window's first sample
    hi: "jnp.ndarray | None" = None  # [S, T] one past its last
    cnt: "jnp.ndarray | None" = None  # [S, T] i32 samples in the window
    has: "jnp.ndarray | None" = None  # [S, T] non-empty and series selected

    def fold(self, a):
        return _fold(a, self.off, self.width) if self.sweep else a

    def col(self, i):
        """The column of a folded array that holds index ``i`` [S, ...]."""
        if not self.sweep:
            return i
        off = self.off.reshape((-1,) + (1,) * (i.ndim - 1))
        return (i + off) % self.width

    def index(self):
        """[S, width] the index each folded column holds (``col``'s
        inverse)."""
        j = jnp.arange(self.width, dtype=jnp.int32)[None, :]
        return (j - self.off[:, None]) % self.width if self.sweep else j

    def edged(self, lo, hi):
        cnt = hi - lo
        return self._replace(lo=lo, hi=hi, cnt=cnt,
                             has=(cnt > 0) & self.sel_ok[:, None])


def _slab_edges(rel, thr, sweep: bool):
    """[S, T] count of each slab row's columns with ``rel`` ≤ ``thr[t]``:
    compares over [S, T, F] of the folded slab (a count needs no order),
    or past _SWEEP_WIDTH a log-W search along the gathered slab's axis
    (rows ascend: −inf sentinels, the samples, +inf sentinels) — the
    same count of samples either way."""
    S, w = rel.shape
    T = thr.shape[0]
    if sweep:
        return jnp.sum(rel[:, None, :] <= thr[None, :, None], axis=-1,
                       dtype=jnp.int32)
    return _count_le(
        lambda i: jnp.take_along_axis(rel, jnp.clip(i, 0, w - 1), axis=1),
        jnp.full((S, T), w, jnp.int32),
        jnp.broadcast_to(thr[None, :], (S, T)), w.bit_length())


def _earlier(a, fill):
    """[S, G] → each column's time-order predecessor, ``fill`` ahead of
    the first: read at a window's index i it is the sample at i − 1."""
    return jnp.concatenate(
        [jnp.full((a.shape[0], 1), fill, a.dtype), a[:, :-1]], axis=1)


# the window edges, in the order ``Slab.thr`` and ``_read_edges`` have them
_EDGES = ("first", "last")


def sweep_passes(kind: str, slab_w: int) -> int:
    """Traversals of [S, T, width] that the program of window kind ``kind``
    emits: none where the slab is searched; where it is swept one a window
    edge (``_read_edges``: the count of the samples at or before the edge
    and whatever the kind reads at the edge's sample, under one mask) and,
    for min/max, the two masked reduces.  A matrix kind's program and its
    sizing pass place the edges and gather.  Static, like
    ``swept_columns``: the dispatch counter reads it."""
    if slab_w > _SWEEP_WIDTH:
        return 0
    return len(_EDGES) + (2 if kind == "minmax" else 0)


def _read_edges(slab: Slab, first=None, last=None):
    """(lo, hi, at_first, at_last): the window edges over the slab, [S, T],
    and what the [S, G] arrays of ``first`` (name → array, time order
    along G) hold at every window's first sample, index clip(lo), and
    those of ``last`` at its last one, clip(hi − 1): name → [S, T].  A
    picked value means something only where the window holds a sample
    (hi > lo).

    An array crosses as 32-bit words: an f64 as the two of ``_split_f64``
    (taken here, on [S, G]; a tuple is taken for words already split, as
    ``Slab.val_words``), joined again on [S, T] — on the TPU the f64's
    own representation, so the value read is the value stored, bit for
    bit, and nothing 64 bits wide is selected or added over [S, T, ·].

    Where the slab is swept an edge is ONE traversal of [S, T, width], a
    variadic reduce that sums along the folded columns the compare that
    counts (rel ≤ thr[t]) and every word under the mask of the one column
    at the edge.  The mask comes from the thresholds, not from the count:
    the first sample is the column past the threshold whose time-order
    predecessor is not, the last one the column at or before it whose
    successor is past it, so no [S, T] index is broadcast along the lanes
    and the count rides in the same traversal.  The cube is laid
    [width, T, S]: the sums come out [T, S] (the layout the TPU compiler
    gives an [S, T] result anyway) by adding whole tiles, where a sum
    along the minor axis reduces every tile across its lanes (PERF.md,
    PR 38: 23.9 ms a call of ``namespace_cpu``'s program against 26.6).
    A one-hot sum has one non-zero term: the words come out as they went
    in.  An edge nothing is read at is counted by ``_slab_edges``.  Where the slab is searched
    the edges are searches and the words gathers at the same indices —
    the same integers and the same words either way."""
    def words_of(a):
        if isinstance(a, tuple):
            return a
        return _split_f64(a) if a.dtype == jnp.float64 else (a,)

    def cube(a):
        # [S, F] as [F, 1, S]: the columns lead, so the sum along them
        # adds whole (step, series) tiles and crosses no lane
        return a.T[:, None, :]

    groups = [{name: words_of(a) for name, a in (group or {}).items()}
              for group in (first, last)]
    flat = [tuple(w for words in g.values() for w in words) for g in groups]
    rel = slab.fold(slab.rel)
    if slab.sweep:
        lowest, highest = jnp.iinfo(rel.dtype).min, jnp.iinfo(rel.dtype).max
        idx = slab.index()
        counts, picked = [], []
        for edge, thr, words in zip(_EDGES, slab.thr, flat):
            if not words:
                counts.append(_slab_edges(rel, jnp.asarray(thr), True))
                picked.append(())
                continue
            # a folded column's time-order neighbour is the column beside
            # it, but at the two ends of the readable samples
            if edge == "first":
                beside = jnp.where(idx == 0, lowest,
                                   jnp.roll(rel, 1, axis=1))
            else:
                beside = jnp.where(idx == slab.width - 1, highest,
                                   jnp.roll(rel, -1, axis=1))
            t = jnp.asarray(thr)[None, :, None]
            le = cube(rel) <= t
            at = (~le & (cube(beside) <= t) if edge == "first"
                  else le & (cube(beside) > t))
            operands = (le.astype(jnp.int32),) + tuple(
                jnp.where(at, cube(slab.fold(a)), jnp.zeros((), a.dtype))
                for a in words)
            count, *sums = jax.lax.reduce(
                operands, tuple(jnp.zeros((), o.dtype) for o in operands),
                lambda x, y: tuple(a + b for a, b in zip(x, y)), (0,))
            counts.append(count.T)
            picked.append([x.T for x in sums])
        lo, hi = counts
    else:
        lo, hi = (_slab_edges(rel, jnp.asarray(t), False) for t in slab.thr)
        picked = [[jnp.take_along_axis(
            a, jnp.clip(i, 0, slab.width - 1), axis=1) for a in words]
            for words, i in zip(flat, (lo, hi - 1))]
    out = []
    for group, words in zip(groups, picked):
        words = iter(words)
        out.append({name: _join_f64(*(next(words) for _ in split))
                    for name, split in group.items()})
    return lo, hi, out[0], out[1]


def _chunks(a, chunk, c: int):
    """[S, k·c]: the aligned ``c``-row chunks ``chunk`` [S, k] of the 1-D
    table column ``a``, read as [n/c, c] (a bitcast of the TPU's 1-D
    tiling): ONE gather op, where the TPU compiler turns a slice-gather
    from a 1-D operand into a loop with an iteration a series.  A chunk
    past the table's last reads the last."""
    n = a.shape[0]
    return a.reshape(n // c, c)[jnp.clip(chunk, 0, n // c - 1)].reshape(
        chunk.shape[0], -1)


def _chunk_rows(chunk, c: int):
    """[S, k·c] the table row each column of ``_chunks(a, chunk, c)``
    stands for (past the table where a chunk was clipped)."""
    rows = chunk[:, :, None] * c + jnp.arange(c, dtype=jnp.int32)
    return rows.reshape(chunk.shape[0], -1)


def _first_rows(layout: SortLayout, sel_tsids, thr, run_bits: int):
    """(sel_ok, r0, run, base) [S] of the selected series: whether the
    slot is a series of the layout, its run [r0, r0 + run) through
    ``row_ptr``, and ``base``, the first row of the run whose timestamp
    is past ``thr`` (r0 + the run's count of timestamps ≤ ``thr``).

    The count is ``_count_le``'s search down to bit log2(c), c = gcd(n,
    128) (``search_rounds`` scalar rounds, none where every run is under c
    long): r0 + that is q, and the count less q's is under c.  The last
    log2(c) rounds are one count over rows [q, q + c), which lie in the two
    aligned chunks from q's on: two [S, 2] gathers of chunk rows
    (``_chunks``) where the rounds would be 2·log2(c) scalar gathers, at
    about the price of one each (PERF.md).  The chunks compare as the
    timestamps' two words against the threshold's, so nothing [S, 2c] is
    64 bits wide.  The same integer as the whole search, for every slot."""
    ts_hi, ts_lo, row_ptr = layout.ts_hi, layout.ts_lo, layout.row_ptr
    n = ts_hi.shape[0]
    # padding slots (-1) and series newer than the layout own no rows
    sel_ok = (sel_tsids >= 0) & (sel_tsids < row_ptr.shape[0] - 1)
    sid = jnp.clip(sel_tsids, 0, row_ptr.shape[0] - 2)
    r0 = row_ptr[sid]
    run = jnp.where(sel_ok, row_ptr[sid + 1] - r0, 0)

    def ts_at(i):
        at = jnp.clip(r0 + i, 0, n - 1)
        return _join_i64(ts_hi[at], ts_lo[at])

    c = math.gcd(n, 128)
    q = r0 + _count_le(ts_at, run, thr, run_bits,
                       run_bits - search_rounds(run_bits, n))
    pair = (q // c)[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]
    rows = _chunk_rows(pair, c)
    hi, lo = _chunks(ts_hi, pair, c), _chunks(ts_lo, pair, c)
    thr_hi, thr_lo = _split_i64(thr)
    le = (hi < thr_hi) | ((hi == thr_hi) & (lo <= thr_lo))
    # the run ascends and fewer than c of its rows from q on are ≤ thr, so
    # the run's rows of the two chunks from q on count as rows [q, q + c)
    cand = (rows >= q[:, None]) & (rows < (r0 + run)[:, None])
    return sel_ok, r0, run, q + jnp.sum(le & cand, axis=1, dtype=jnp.int32)


def _slab_gather(p: WindowParams, layout: SortLayout, sel_tsids,
                 start_ms) -> Slab:
    """The slab of ``_slab_geometry`` before its window edges are placed.

    Gathers, for each selected series, the rows of its run from its first
    sample after ``start − range`` on (found in the series' own run
    through ``row_ptr``) — ``p.slab_w`` of them can fall in the query's
    span.  After the gather nothing has the table's length: work is
    proportional to the matched series, not to the table.

    Off a wide layout the values' low word is gathered beside the high
    one by the same chunks: ``Slab.val`` is the two joined (f64), so no
    consumer of the slab reads half a value, and ``Slab.val_words`` the
    two as they are."""
    val_s = layout.val_s
    T, w = p.num_steps, p.slab_w
    n = val_s.shape[0]
    sel_ok, r0, run, base = _first_rows(layout, sel_tsids,
                                        start_ms - p.range_ms, p.run_bits)
    # whole chunks gathered from the one that holds ``base`` (``_chunks``).
    # One chunk more than W fills, because ``base`` lies anywhere in the
    # first: columns before ``base`` or past the run are masked.
    c = math.gcd(n, 128)
    k = -(-w // c) + 1
    chunk = (base // c)[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    rows = _chunk_rows(chunk, c)
    before = rows < base[:, None]
    ok = ~before & (rows < (r0 + run)[:, None])

    if layout.wide:
        # the two words are joined here, once, so that whatever reads
        # ``Slab.val`` reads all of the value (exact: |low| is under half
        # an ulp of high)
        val_words = tuple(jnp.where(ok, _chunks(a, chunk, c), 0.0)
                          for a in (val_s, layout.val_lo))
        val = _join_f64(*val_words)
    else:
        val = jnp.where(ok, _chunks(val_s, chunk, c), 0.0)
        val_words = (val,)
    # timestamps rebased to start_ms; int32 where the query's span fits
    # (the compare sweep is the slab's widest pass): integer compares stay
    # exact, and a sample beyond the span saturates below the sentinel
    steps = p.step_ms * np.arange(T, dtype=np.int64)
    if int(steps[-1]) + p.range_ms < (1 << 31) - 2:
        tdt, big = np.int32, (1 << 31) - 1
    else:
        tdt, big = np.int64, 1 << 62
    rel = jnp.clip(_join_i64(_chunks(layout.ts_hi, chunk, c),
                             _chunks(layout.ts_lo, chunk, c)) - start_ms,
                   -big, big - 1).astype(tdt)
    rel = jnp.where(ok, rel, jnp.where(before, -big - 1, big).astype(tdt))
    # the sweeps read the folded slab, the searches the gathered one
    return Slab(rel, val, val_words, ok, base % c, sel_ok,
                ((steps - p.range_ms).astype(tdt), steps.astype(tdt)),
                w <= _SWEEP_WIDTH, swept_columns(w, n))


def _slab_geometry(p: WindowParams, layout: SortLayout, sel_tsids,
                   start_ms) -> Slab:
    """Shared window geometry for all window kernels over a PRESORTED
    resident layout (_build_sort_layout): the ONE definition the stats
    kernel, the matrix kernels and the fused programs build on.

    ``_slab_gather``'s slab with every window's half-open index range
    [lo, hi) placed on it with LEFT-EXCLUSIVE window semantics
    (t - range, t].  Where the slab is swept (``Slab.sweep``) the edges
    are counted on the folded slab and come out relative to the first
    readable sample; where it is searched, on the gathered one, relative
    to its first column."""
    slab = _slab_gather(p, layout, sel_tsids, start_ms)
    return slab.edged(*_read_edges(slab)[:2])


def _prefix_sum(x):
    """Inclusive prefix sum along the last axis by doubling shifts:
    log2(n) whole-array adds instead of ``jnp.cumsum``.  The prefixes
    below have to be f64 (windows difference them), and the TPU compiler
    takes minutes over an f64 ``cumsum`` at any length (ROADMAP A4 has the
    seconds), while it builds this form in seconds.  Same dtype, same
    sums, pairwise instead of running order."""
    k = 1
    while k < x.shape[-1]:
        x = x + jnp.concatenate(
            [jnp.zeros(x.shape[:-1] + (k,), x.dtype), x[..., :-k]], axis=-1)
        k *= 2
    return x


def _range_extreme(level, lo, hi, cnt, op):
    """min/max of ``level`` [S, W] over each window's columns [lo, hi)
    from a sparse table built level by level: a window of c samples is
    two overlapping blocks of 2**floor(log2 c) — O(S·W·log W + S·T·log W)
    where the masked sweep over [S, T, W] would not end."""
    w = level.shape[-1]
    fill = jnp.inf if op is jnp.minimum else -jnp.inf
    res = jnp.full(lo.shape, fill, level.dtype)
    k_of = 31 - jax.lax.clz(jnp.maximum(cnt, 1))
    for k in range(w.bit_length()):
        b = 1 << k
        pick = op(
            jnp.take_along_axis(level, jnp.clip(lo, 0, w - 1), axis=1),
            jnp.take_along_axis(level, jnp.clip(hi - b, 0, w - 1), axis=1))
        res = jnp.where((k_of == k) & (cnt > 0), pick, res)
        level = op(level, jnp.concatenate(
            [level[:, b:], jnp.full((level.shape[0], min(b, w)), fill,
                                    level.dtype)], axis=1))
    return res


def _window_kernel(p: WindowParams):  # gl: warm-path
    """Build the jitted kernel computing window stats for selected series.

    Inputs: the presorted resident layout (a ``SortLayout``, see
            _build_sort_layout), sel_tsids [S] i32 (padded with -1),
            start_ms scalar i64.
    Output dict of [S, T] arrays depending on p.kind.
    """
    return named_jit(f"promql_window_{p.kind}")(_window_body(p))


def _window_body(p: WindowParams):  # gl: warm-path
    """The UNJITTED window-stats program for one shape class — the exact
    function ``_window_kernel`` jits.  Exposed separately so the
    whole-plan fused programs (compile/fused.py) can compose it with the
    function epilogue and group reduction inside ONE jit: a single
    program source means fused and unfused window math can never
    diverge."""

    S = p.num_sel

    def kernel(layout, sel_tsids, start_ms):
        slab = _slab_gather(p, layout, sel_tsids, start_ms)
        sel_ok = slab.sel_ok
        # the gathered rows in time order: what reads a neighbour or a
        # prefix reads these, and ``_read_edges`` folds what it sweeps
        g_rel, g_val, ok = slab.rel, slab.val, slab.ok
        val_words = slab.val_words

        # ``g_val`` is f32, or f64 off a wide layout (``p.wide``): every
        # compare, difference and prefix below reads it as it is, and what
        # leaves the kernel as a value is rounded once, after the
        # difference — the extrapolation, the sum over series and the reply
        # are f32 either way (on f32 the cast is no operation)
        def f32(x):
            return x.astype(jnp.float32)

        def ts_of(rel):
            return start_ms + rel.astype(jnp.int64)

        # per-series counter-reset adjustment (for counter kinds).  A
        # window never reads a drop at or before its own first sample, so
        # prefixes that start at the slab's first column give the same
        # differences as table-wide ones
        prev_same = jnp.concatenate(
            [jnp.zeros((S, 1), bool), ok[:, 1:] & ok[:, :-1]], axis=1)
        prev_val = _earlier(g_val, 0)

        # window sums from f64 prefixes along each series' gathered row
        # (``g_val`` is already 0 outside the run), taken in time order:
        # the inclusive prefix at the window's last sample less the one
        # before its first, which is what the prefix's time-order
        # predecessor holds AT the first sample (0 at the row's head: every
        # summand is 0 ahead of the run)
        def cs(x):
            return _prefix_sum(x.astype(jnp.float64))

        def win_sums(prefixes, skip_first=False):
            """name → the sum over each window's samples (but its first
            with ``skip_first``) of the series ``prefixes`` are of, as
            what to read at the two edges."""
            return ({name: c if skip_first else _earlier(c, 0)
                     for name, c in prefixes.items()}, dict(prefixes))

        g_val64 = g_val.astype(jnp.float64)
        tsec = jnp.where(ok, g_rel, 0).astype(jnp.float64) / 1000.0
        sample = {"val": val_words, "rel": g_rel}
        first, last = {}, {}
        if p.kind == "instant":
            last = sample
        if p.kind == "counter":
            drop = jnp.where(prev_same & (prev_val > g_val), prev_val, 0.0)
            adj = g_val64 + _prefix_sum(drop.astype(jnp.float64))
            first = last = {**sample, "adj": adj}
        if p.kind == "counter_rc":
            # resets/changes counts via indicator cumsums — a SEPARATE
            # kind so the (much hotter) rate/increase/delta path doesn't
            # pay two extra prefixes it never reads.  The boundary pair
            # crossing into the window is left out: the indicator at
            # index i compares i-1, i; window pairs are (lo+1..hi-1)
            first, last = win_sums({
                "resets": cs(jnp.where(
                    prev_same & (prev_val > g_val), 1.0, 0.0)),
                "changes": cs(jnp.where(
                    prev_same & (prev_val != g_val), 1.0, 0.0)),
            }, skip_first=True)
        if p.kind == "gauge_window":
            first, last = win_sums({"sum": cs(g_val),
                                    "sum2": cs(g_val64 ** 2)})
            first.update(sample)
            last.update(sample)
        if p.kind == "regression":
            first, last = win_sums({
                "v": cs(g_val), "t": cs(tsec), "tv": cs(tsec * g_val64),
                "t2": cs(tsec * tsec)})
            last["rel"] = g_rel
        if p.kind == "irate":
            # the sample before a window's last is its time-order
            # predecessor AT the last
            last = {**sample, "prev_rel": _earlier(g_rel, 0),
                    "prev_val": tuple(_earlier(a, 0) for a in val_words)}

        lo, hi, fst, lst = _read_edges(slab, first, last)
        slab = slab.edged(lo, hi)
        cnt, has = slab.cnt, slab.has
        has2 = (cnt >= 2) & sel_ok[:, None]
        out = {}
        fcnt = cnt.astype(jnp.float32)
        nan = jnp.float32(jnp.nan)

        if p.kind in ("counter", "counter_rc", "gauge_window", "regression",
                      "instant"):
            out["count"] = jnp.where(has, fcnt, 0.0)
        if p.kind == "instant":
            out["last"] = jnp.where(has, f32(lst["val"]), nan)
            out["last_ts"] = jnp.where(has, ts_of(lst["rel"]), 0)
        if p.kind == "counter":
            d_adj = (lst["adj"] - fst["adj"]).astype(jnp.float32)
            out["first_ts"] = jnp.where(has, ts_of(fst["rel"]), 0)
            out["last_ts"] = jnp.where(has, ts_of(lst["rel"]), 0)
            out["first_val"] = jnp.where(has, f32(fst["val"]), nan)
            out["last_val"] = jnp.where(has, f32(lst["val"]), nan)
            out["delta_adj"] = jnp.where(has2, d_adj, nan)
            out["delta_raw"] = jnp.where(
                has2, f32(lst["val"] - fst["val"]), nan)
        if p.kind == "counter_rc":
            for name in ("resets", "changes"):
                out[name] = jnp.where(
                    has, (lst[name] - fst[name]).astype(jnp.float32), nan)
        if p.kind in ("gauge_window",):
            sum64 = lst["sum"] - fst["sum"]
            sum2_64 = lst["sum2"] - fst["sum2"]
            s = sum64.astype(jnp.float32)
            out["sum"] = jnp.where(has, s, nan)
            out["avg"] = jnp.where(has, s / jnp.maximum(fcnt, 1), nan)
            mean = s.astype(jnp.float64) / jnp.maximum(cnt, 1)
            var = sum2_64 / jnp.maximum(cnt, 1) - mean * mean
            out["var"] = jnp.where(has, jnp.maximum(var, 0.0).astype(jnp.float32), nan)
            out["last"] = jnp.where(has, f32(lst["val"]), nan)
            out["first"] = jnp.where(has, f32(fst["val"]), nan)
            out["first_ts"] = jnp.where(has, ts_of(fst["rel"]), 0)
            out["last_ts"] = jnp.where(has, ts_of(lst["rel"]), 0)
        if p.kind == "regression":
            sw, st, stv, st2 = (lst[k] - fst[k]
                                for k in ("v", "t", "tv", "t2"))
            cn = cnt.astype(jnp.float64)
            denom = cn * st2 - st * st
            slope = jnp.where(denom != 0, (cn * stv - st * sw) / denom, jnp.nan)
            intercept = jnp.where(cn > 0, (sw - slope * st) / cn, jnp.nan)
            out["slope"] = jnp.where(has2, slope.astype(jnp.float32), nan)
            out["intercept"] = jnp.where(has2, intercept.astype(jnp.float32), nan)
            out["last_ts"] = jnp.where(has, ts_of(lst["rel"]), 0)
        if p.kind == "irate":
            # the pair ``_instant_pair`` differences leaves as it was
            # read (f64 off a wide layout): f32 after the difference
            out["last_ts"] = jnp.where(has2, ts_of(lst["rel"]), 0)
            out["prev_ts"] = jnp.where(has2, ts_of(lst["prev_rel"]), 0)
            out["last_val"] = jnp.where(has2, lst["val"], nan)
            out["prev_val"] = jnp.where(has2, lst["prev_val"], nan)
        if p.kind == "minmax":
            # rounding keeps order, so the extreme of the rounded samples
            # is the rounded extreme: the sweep stays f32 on a wide layout
            val, g_val = f32(slab.fold(g_val)), f32(g_val)
            if slab.sweep:
                # reduce over the folded slab under the edge mask
                j = slab.index()[:, None, :]
                in_win = (j >= lo[:, :, None]) & (j < hi[:, :, None])
                mn = jnp.min(jnp.where(in_win, val[:, None, :], jnp.inf),
                             axis=-1)
                mx = jnp.max(jnp.where(in_win, val[:, None, :], -jnp.inf),
                             axis=-1)
            else:
                mn = _range_extreme(jnp.where(ok, g_val, jnp.inf), lo, hi,
                                    cnt, jnp.minimum)
                mx = _range_extreme(jnp.where(ok, g_val, -jnp.inf), lo, hi,
                                    cnt, jnp.maximum)
            out["min"] = jnp.where(jnp.isfinite(mn), mn, nan)
            out["max"] = jnp.where(jnp.isfinite(mx), mx, nan)
        return out

    return kernel


def _count_max_kernel(p: WindowParams):  # gl: warm-path
    """Max samples in any (series, step) window — sizes the matrix
    kernels' static padded width (one cheap pass, cached per shape)."""

    @named_jit("promql_window_cnt_max")
    def kernel(layout, sel_tsids, start_ms):
        slab = _slab_geometry(p, layout, sel_tsids, start_ms)
        return jnp.max(jnp.where(slab.sel_ok[:, None], slab.cnt, 0))

    return kernel


def _matrix_kernel(p: WindowParams, lmax: int, kind: str):  # gl: warm-path
    """Window-matrix kernels: gather each (series, step) window's samples
    (time-ordered, padded to the static width ``lmax``) out of the slab
    into a [S*T, lmax] matrix, then

    - ``quantile``: per-row sort + Prometheus linear-interpolation
      quantile (reference src/promql/src/functions/quantile.rs semantics)
    - ``mad``: median, then median of |x − median| (mad_over_time)
    - ``holt``: Holt's linear (double) exponential smoothing scan over
      the window (reference
      src/promql/src/functions/double_exponential_smoothing.rs)

    Scalar parameters (φ / sf, tf) arrive as traced [T] f32 vectors so
    repeated queries share one compiled program.
    """
    T, S = p.num_steps, p.num_sel

    @named_jit(f"promql_matrix_{kind}")
    def kernel(layout, sel_tsids, start_ms, a1, a2):
        slab = _slab_geometry(p, layout, sel_tsids, start_ms)
        has = slab.has
        cntf = slab.cnt.reshape(-1)  # [S*T]
        j = jnp.arange(lmax, dtype=jnp.int32)
        idx = slab.col(jnp.clip(
            slab.lo[:, :, None] + j[None, None, :], 0, slab.width - 1))
        # [S*T, L] time-ordered window samples
        # rounded to f32 off a wide layout: an order statistic of the
        # rounded samples is the rounded order statistic
        rows = jnp.take_along_axis(
            slab.fold(slab.val).astype(jnp.float32)[:, None, :], idx,
            axis=2).reshape(S * T, lmax)
        ok = j[None, :] < cntf[:, None]
        nan = jnp.float32(jnp.nan)
        inf = jnp.float32(jnp.inf)

        def q_of(sorted_rows, q):
            """Prometheus quantile over per-row ascending values: linear
            interpolation between the two straddling order statistics."""
            rank = q * jnp.maximum(cntf - 1, 0).astype(jnp.float32)
            lo_r = jnp.clip(jnp.floor(rank).astype(jnp.int32), 0, lmax - 1)
            hi_r = jnp.clip(jnp.ceil(rank).astype(jnp.int32), 0, lmax - 1)
            vlo = jnp.take_along_axis(sorted_rows, lo_r[:, None], axis=1)[:, 0]
            vhi = jnp.take_along_axis(sorted_rows, hi_r[:, None], axis=1)[:, 0]
            return vlo + (vhi - vlo) * (rank - lo_r.astype(jnp.float32))

        if kind == "quantile":
            srt = jnp.sort(jnp.where(ok, rows, inf), axis=1)
            qv = jnp.broadcast_to(a1[None, :], (S, T)).reshape(-1)
            res = q_of(srt, qv)
            # Prometheus: φ < 0 → -Inf, φ > 1 → +Inf (NaN propagates)
            res = jnp.where(qv < 0, -inf, jnp.where(qv > 1, inf, res))
        elif kind == "mad":
            srt = jnp.sort(jnp.where(ok, rows, inf), axis=1)
            med = q_of(srt, jnp.float32(0.5))
            dev = jnp.sort(
                jnp.where(ok, jnp.abs(rows - med[:, None]), inf), axis=1)
            res = q_of(dev, jnp.float32(0.5))
        elif kind == "holt":
            sf = jnp.broadcast_to(a1[None, :], (S, T)).reshape(-1)
            tf = jnp.broadcast_to(a2[None, :], (S, T)).reshape(-1)
            s0 = rows[:, 0]
            b0 = rows[:, min(1, lmax - 1)] - s0

            def body(i, carry):
                s, b = carry
                x = jax.lax.dynamic_slice_in_dim(rows, i, 1, axis=1)[:, 0]
                act = i < cntf
                s1 = sf * x + (1 - sf) * (s + b)
                b1 = tf * (s1 - s) + (1 - tf) * b
                return jnp.where(act, s1, s), jnp.where(act, b1, b)

            s_fin, _b = jax.lax.fori_loop(1, lmax, body, (s0, b0))
            # Prometheus needs ≥2 samples and factors in (0, 1)
            param_ok = (sf > 0) & (sf < 1) & (tf > 0) & (tf < 1)
            res = jnp.where((cntf >= 2) & param_ok, s_fin, nan)
        else:  # pragma: no cover
            raise ValueError(f"matrix kind {kind}")
        out = jnp.where(cntf > 0, res, nan).reshape(S, T)
        return jnp.where(has, out, nan)

    return kernel


class SelectorData:
    """Host-side prepared state for one table used by selectors."""

    def __init__(self, db, table: str, events=None):
        # partitioned tables come back as a CombinedRegionView duck-typing
        # the Region surface (encoders/_series/scan_host/num_series)
        region = (
            db._table_view(table) if hasattr(db, "_table_view")
            else db._region_of(table)
        )
        self.db = db
        self.region = region
        self.table = db.cache.get(region)
        self.schema = region.schema
        self.ts_name = region.schema.time_index.name
        self.tag_names = region.tag_names
        self.encoders = region.encoders
        # per-eval cache event counter shared with the evaluator (bench
        # observability: selection/sort/group hit/miss/reject/uncached)
        self.events = events if events is not None else collections.Counter()

    def promql_cache(self):
        """The db's resident PromLayoutCache, or None when the db has
        none.  Both states serve evals from the identical transient-build
        code path, so cached and uncached results are bit-exact by
        construction."""
        return getattr(self.db, "promql_cache", None)

    def field_column(self, matchers: list[LabelMatcher]) -> str:
        fields = [c.name for c in self.schema.field_columns]
        for m in matchers:
            if m.name == "__field__":
                if m.value not in fields:
                    raise PlanError(f"field {m.value} not in {self.table!r}")
                return m.value
        for cand in ("greptime_value", "val", "value"):
            if cand in fields:
                return cand
        if len(fields) == 1:
            return fields[0]
        raise PlanError(
            f"table has {len(fields)} fields; use __field__ matcher: {fields}"
        )

    def select_series(
        self, matchers: list[LabelMatcher]
    ) -> tuple[np.ndarray, jnp.ndarray, LazySeriesLabels]:
        """Returns (tsids, padded device tsids, lazy labels) matching the
        label matchers.

        Inverted-index evaluation (storage/inverted.py): each matcher runs
        once per DISTINCT term of its label and selects via posting lists —
        O(vocabulary) string work, not O(series).  The reference gets the
        same effect from its FST+bitmap inverted index
        (src/index/src/inverted_index/).  The matched tsid set (and its
        pow2-padded device copy) is resident per (region generation,
        matcher set); labels are NOT materialized here — LazySeriesLabels
        decodes a dict only when indexed, so aggregations touch zero
        per-series Python objects."""
        from greptimedb_tpu.storage.inverted import get_series_index

        tag_matchers = [m for m in matchers if m.name != "__field__"]
        mkey = tuple(sorted((m.name, m.op, m.value) for m in tag_matchers))
        # registry-only version: selections (and the group ids derived
        # from them) survive data appends of existing series
        gen = getattr(self.region, "series_generation",
                      self.region.generation)
        idx = get_series_index(self.region)
        cache = self.promql_cache()
        rid = getattr(self.region, "region_id", None)
        sel = None
        if cache is not None and rid is not None:
            sel = cache.lookup("selection", rid, mkey, gen)
            self.events["selection_hit" if sel is not None
                        else "selection_miss"] += 1
        if sel is None:
            sel_tsids = idx.all_tsids
            for m in tag_matchers:
                if sel_tsids.size == 0:
                    break
                pred, neg = matcher_pred(m)
                matched = idx.select(m.name, pred, negate=neg)
                sel_tsids = np.intersect1d(sel_tsids, matched,
                                           assume_unique=True)
            sel_tsids = sel_tsids.astype(np.int32)
            S = _pow2(len(sel_tsids))
            padded = np.full(S, -1, dtype=np.int32)
            padded[: len(sel_tsids)] = sel_tsids
            sel_dev = jnp.asarray(padded)
            if cache is not None and cache.mesh is not None:
                from greptimedb_tpu.parallel.dist import promql_row_shardings

                sh = promql_row_shardings(cache.mesh, S)
                if sh is not None:
                    sel_dev = jax.device_put(sel_dev, sh["rows"])
            sel = (sel_tsids, sel_dev)
            if cache is not None and rid is not None:
                nbytes = sel_tsids.nbytes + int(sel_dev.nbytes)
                if cache.admit(nbytes):
                    cache.store("selection", rid, mkey, gen, sel, nbytes)
                else:
                    self.events["selection_reject"] += 1
        sel_tsids, sel_dev = sel
        # label values decode from the index's shared per-region raw
        # vocabularies — selections hold no per-matcher-set copies
        labels = LazySeriesLabels(
            idx, self.tag_names, idx.raw_values, sel_tsids,
            rid if rid is not None else -1, gen, mkey, cache)
        return sel_tsids, sel_dev, labels

    def sort_layout(self, fieldcol: str) -> tuple:
        """The resident composite-key sort of this table for ``fieldcol``
        (see _build_sort_layout) with its row pointer: (SortLayout,
        spacing, max_run), the last two host integers.  Served
        from PromLayoutCache per (resident-table dicts_version, field
        column); a miss builds and — if admission under the promql_cache
        workload quota succeeds — stores it.  A rejected build serves
        this eval transiently from the same arrays (reject-to-fallback,
        bit-exact either way).

        WIDE (``SortLayout.val_lo``) where the resident table kept the
        column's low word (a DOUBLE that passes 2^24, storage/cache.py
        ``low_word_col``): read off the column, never off an option."""
        cache = self.promql_cache()
        rid = getattr(self.region, "region_id", None)
        version = self.table.dicts_version
        if cache is not None and rid is not None:
            payload = cache.lookup("sort", rid, (fieldcol,), version)
            if payload is not None:
                self.events["sort_hit"] += 1
                return payload
            self.events["sort_miss"] += 1
        from greptimedb_tpu.storage.cache import low_word_col

        cols = self.table.columns
        ts_hi, ts_lo, val_s, row_ptr, spacing, max_run, *val_lo = \
            _build_sort_layout(
                cols[self.ts_name], cols[fieldcol], cols[TSID],
                self.table.row_mask, max(self.region.num_series, 1),
                cols.get(low_word_col(fieldcol)))
        arrays = SortLayout(ts_hi, ts_lo, val_s, row_ptr, *val_lo)
        if cache is not None and cache.mesh is not None:
            from greptimedb_tpu.parallel.dist import promql_row_shardings

            sh = promql_row_shardings(cache.mesh,
                                      int(arrays.val_s.shape[0]))
            if sh is not None:
                # the sorted columns split by rows; the row pointer is
                # small and read whole by every device
                arrays = jax.tree.map(
                    lambda a: jax.device_put(a, sh["rows"]),
                    arrays._replace(row_ptr=None),
                )._replace(row_ptr=arrays.row_ptr)
        spacing, max_run = jax.device_get((spacing, max_run))
        layout = (arrays, int(spacing), int(max_run))
        if cache is not None and rid is not None:
            nbytes = sum(int(a.nbytes) for a in jax.tree.leaves(arrays))
            if cache.admit(nbytes):
                cache.store("sort", rid, (fieldcol,), version, layout,
                            nbytes)
            else:
                self.events["sort_reject"] += 1
        return layout


class PromEvaluator:
    def __init__(self, db, start_s: float, end_s: float, step_s: float,
                 lookback_s: float = DEFAULT_LOOKBACK_S):
        self.db = db
        if end_s < start_s:
            raise PlanError(f"invalid time range: end {end_s} < start {start_s}")
        if step_s <= 0:
            raise PlanError(f"invalid step: {step_s}")
        self.start_ms = int(round(start_s * 1000))
        self.step_ms = max(int(round(step_s * 1000)), 1)
        # integer-ms math: float division can drop the final (inclusive) step
        end_ms = int(round(end_s * 1000))
        self.num_steps = (end_ms - self.start_ms) // self.step_ms + 1
        self.lookback_ms = int(lookback_s * 1000)
        self._data: dict[str, SelectorData] = {}
        self._kernels: dict[tuple, object] = {}
        # NOTE: replay-context hygiene is a statement-boundary concern,
        # handled where statements end (_sql_locked's finally, the batch
        # entry, warmup replays) — an evaluator must NOT clear it here:
        # nested evaluators (subquery operands) are constructed MID-
        # statement and would strip the outer TQL's replay, leaving its
        # kernel classes permanently unwarmable.
        # resident-cache event counter for this evaluation (selection /
        # sort / group × hit / miss / reject)
        self.cache_events: collections.Counter = collections.Counter()
        # per-stage wall ms for this evaluation (selection → sort_layout →
        # window_kernel → group_agg → label_decode), taken from the
        # stages' own clock reads and handed, through execute_tql, to the
        # standalone stage sink so slow TQL queries self-report their
        # breakdown
        self.stage_ms: dict[str, float] = {}

    def _stage_mark(self, name: str, stage) -> None:
        """Add a closed ``TRACER.stage``'s time to ``stage_ms[name]``."""
        self.stage_ms[name] = round(
            self.stage_ms.get(name, 0.0) + stage.seconds * 1000, 3)

    def _timed_kernel(self, name: str, call, sync: bool, compiling: bool,
                      **attrs):
        """Run one kernel dispatch inside stage ``name``; a call that
        compiles sits in a nested ``xla_compile`` stage as well.  The
        device is waited for only on a first call (``sync``) or where
        the slow-query sink asked for the split: steady-state
        evaluations keep the async dispatch pipeline, tracer on or off,
        and their wait is the one ``device_wait`` where the result
        leaves the device."""
        sync = sync or getattr(self.db, "stage_sink", None) is not None
        with TRACER.stage(name, **attrs) as st:
            if compiling:
                with TRACER.stage("xla_compile"):
                    out = call()
            else:
                out = call()
            if sync:
                out = jax.block_until_ready(out)
        self._stage_mark("xla_compile" if compiling else name, st)
        return out

    def _compiler(self):
        """The db's PlanCompiler (persistent AOT store + usage journal),
        or the process default (memory-only classification) for embedded
        evaluators without one."""
        comp = getattr(self.db, "plan_compiler", None)
        if comp is None:
            from greptimedb_tpu.compile.service import default_compiler

            comp = default_compiler()
        return comp

    # ---- plumbing -------------------------------------------------------
    def data_for(self, metric: str) -> SelectorData:
        if metric not in self._data:
            self._data[metric] = SelectorData(self.db, metric,
                                              self.cache_events)
        return self._data[metric]

    def steps_ms(self) -> np.ndarray:
        return self.start_ms + self.step_ms * np.arange(self.num_steps, dtype=np.int64)

    _KIND_KEYS = {
        "instant": ("count", "last", "last_ts"),
        "counter": ("count", "first_ts", "last_ts", "first_val", "last_val",
                    "delta_adj", "delta_raw"),
        "counter_rc": ("count", "resets", "changes"),
        "gauge_window": ("count", "sum", "avg", "var", "last", "first",
                         "first_ts", "last_ts"),
        "regression": ("count", "slope", "intercept", "last_ts"),
        "irate": ("last_ts", "prev_ts", "last_val", "prev_val"),
        "minmax": ("min", "max"),
    }

    def _prep_window(self, sel: VectorSelector, kind: str,
                     range_ms: int | None = None):
        """Shared selector→kernel-args prep for the stats and matrix
        kernels (ONE definition of pow2 series padding, range/offset/@
        resolution, the slab width, and the kernel argument tuple).
        Returns (args, p, tsids, labels, pinned, start, rng); raises
        TableNotFound for unknown metrics (callers map it to an empty
        vector, Prometheus semantics)."""
        d = self.data_for(sel.metric)
        fieldcol = d.field_column(sel.matchers)
        with TRACER.stage("selection") as st:
            tsids, sel_dev, labels = d.select_series(sel.matchers)
        self._stage_mark("selection", st)
        S = int(sel_dev.shape[0])
        rng = range_ms
        if rng is None:
            rng = int(sel.range_s * 1000) if sel.range_s else self.lookback_ms
        offset_ms = int(sel.offset_s * 1000)
        # @ modifier pins evaluation time: compute ONE step at at_ts (minus
        # offset, per Prometheus), then broadcast across the output grid
        pinned = sel.at_ts is not None
        if pinned:
            start = int(sel.at_ts * 1000) - offset_ms
            num_steps = 1
        else:
            start = self.start_ms - offset_ms
            num_steps = self.num_steps
        with TRACER.stage("sort_layout") as st:
            layout, spacing, max_run = d.sort_layout(fieldcol)
            st.set(value_words=2 if layout.wide else 1)
        self._stage_mark("sort_layout", st)
        p = WindowParams(
            step_ms=self.step_ms,
            num_steps=num_steps,
            range_ms=int(rng),
            num_sel=S,
            total_series=max(d.region.num_series, 1),
            kind=kind,
            slab_w=slab_width(self.step_ms, num_steps, int(rng), spacing,
                              max_run),
            run_bits=search_bits(max_run),
            wide=layout.wide,
        )
        args = (layout, sel_dev, np.int64(start))
        return args, p, tsids, labels, pinned, start, int(rng)

    def _run_window(
        self, sel: VectorSelector, kind: str, range_ms: int | None = None
    ) -> tuple[dict, list[dict]]:
        try:
            prep = self._prep_window(sel, kind, range_ms)
        except TableNotFound:
            # unknown metric = empty vector (Prometheus semantics); the
            # grid must still be recorded — rate/increase read it
            # unconditionally right after (seed bug: AttributeError when
            # the FIRST selector of an evaluator was an unknown metric)
            self._last_window_grid = (self.start_ms, range_ms or 0, False)
            empty = jnp.zeros((0, self.num_steps), jnp.float32)
            return {k: empty for k in self._KIND_KEYS[kind]}, []
        args, p, tsids, labels, pinned, start, rng = prep
        kern = _KERNEL_CACHE.get(p)
        jit_miss = kern is None
        if kern is None:
            kern = self._compiler().get_or_build(
                "promql", p, lambda: _window_kernel(p), persist=True)
            _KERNEL_CACHE[p] = kern
        # an AOT-store hit deserializes the executable — no XLA compile
        # happened, so the first call must not be attributed as one
        # (the promql twin of physical.aot_kernel_call's discipline)
        compiling = jit_miss and not getattr(kern, "aot", False)
        count_dispatch(p, args, len(tsids))
        out = self._timed_kernel(
            "window_kernel", lambda: kern(*args), jit_miss, compiling,
            kind=kind)
        out = {k: v[: len(tsids)] for k, v in out.items()}
        if pinned:
            out = {
                k: jnp.broadcast_to(v, (v.shape[0], self.num_steps))
                for k, v in out.items()
            }
        self._last_window_grid = (start, rng, pinned)
        return out, labels

    def _run_matrix(self, sel: VectorSelector, kind: str,
                    extras: tuple = ()) -> tuple[jnp.ndarray, list[dict]]:
        """Matrix-kernel twin of _run_window for the window functions that
        need per-window order statistics or a sequential scan
        (quantile_over_time / mad_over_time /
        double_exponential_smoothing).  ``extras`` are [num_steps] f32
        parameter vectors (φ / sf, tf)."""
        import dataclasses

        try:
            prep = self._prep_window(sel, kind)
        except TableNotFound:
            return jnp.zeros((0, self.num_steps), jnp.float32), []
        args, p, tsids, labels, pinned, _start, _rng = prep
        num_steps = p.num_steps
        # the sizing pass reads geometry only — share one compiled count
        # kernel across matrix kinds
        ck = dataclasses.replace(p, kind="cnt_max")
        cnt_kern = _KERNEL_CACHE.get(ck)
        if cnt_kern is None:
            cnt_kern = _count_max_kernel(ck)
            _KERNEL_CACHE[ck] = cnt_kern
        # sizing pass + matrix
        count_dispatch(p, args, len(tsids), programs=2)
        cnt_max = int(cnt_kern(*args))
        lmax = max(2, _pow2(cnt_max))
        mk = (p, "matrix", lmax)
        kern = _KERNEL_CACHE.get(mk)
        jit_miss = kern is None
        if kern is None:
            kern = self._compiler().get_or_build(
                "promql", mk, lambda: _matrix_kernel(p, lmax, kind),
                persist=True)
            _KERNEL_CACHE[mk] = kern
        compiling = jit_miss and not getattr(kern, "aot", False)
        ones = jnp.ones(num_steps, jnp.float32)
        a1 = (jnp.broadcast_to(jnp.asarray(extras[0], jnp.float32),
                               (self.num_steps,))[:num_steps]
              if len(extras) > 0 else ones)
        a2 = (jnp.broadcast_to(jnp.asarray(extras[1], jnp.float32),
                               (self.num_steps,))[:num_steps]
              if len(extras) > 1 else ones)
        vals = self._timed_kernel(
            "window_kernel", lambda: kern(*args, a1, a2), False, compiling,
            kind=kind)[: len(tsids)]
        if pinned:
            vals = jnp.broadcast_to(vals, (vals.shape[0], self.num_steps))
        return vals, labels

    # ---- eval -----------------------------------------------------------
    def eval(self, e: PromExpr) -> EvalResult:
        if isinstance(e, NumberLit):
            v = jnp.full((1, self.num_steps), e.value, dtype=jnp.float32)
            return EvalResult(v, [{}], is_scalar=True)
        if isinstance(e, StringLit):
            raise Unsupported("bare string expression")
        if isinstance(e, VectorSelector):
            if e.range_s is not None:
                raise PlanError(f"range vector {e} needs a function")
            out, labels = self._run_window(e, "instant")
            # staleness enforced by the window kernel: value is the last
            # sample within (t - lookback, t]
            vals = out["last"] if labels else jnp.zeros((0, self.num_steps), jnp.float32)
            return EvalResult(vals, labels)
        if isinstance(e, UnaryExpr):
            r = self.eval(e.expr)
            return EvalResult(-r.values if e.op == "-" else r.values, r.labels,
                              r.is_scalar)
        if isinstance(e, FunctionCall):
            return self.eval_function(e)
        if isinstance(e, Aggregation):
            return self.eval_aggregation(e)
        if isinstance(e, BinaryExpr):
            return self.eval_binary(e)
        if isinstance(e, SubqueryExpr):
            raise Unsupported(
                "bare subquery needs an *_over_time function")
        raise Unsupported(f"promql node {type(e).__name__}")

    # ---- functions --------------------------------------------------------
    def eval_function(self, e: FunctionCall) -> EvalResult:
        f = e.func
        simple = {
            "abs": jnp.abs, "ceil": jnp.ceil, "floor": jnp.floor,
            "exp": jnp.exp, "ln": jnp.log, "log2": jnp.log2,
            "log10": jnp.log10, "sqrt": jnp.sqrt, "sgn": jnp.sign,
            "acos": jnp.arccos, "asin": jnp.arcsin, "atan": jnp.arctan,
            "cos": jnp.cos, "sin": jnp.sin, "tan": jnp.tan,
            "cosh": jnp.cosh, "sinh": jnp.sinh, "tanh": jnp.tanh,
            "deg": jnp.degrees, "rad": jnp.radians,
        }
        if f in simple:
            r = self.eval(e.args[0])
            return EvalResult(simple[f](r.values), r.labels, r.is_scalar)
        if f == "round":
            r = self.eval(e.args[0])
            to = 1.0
            if len(e.args) > 1 and isinstance(e.args[1], NumberLit):
                to = e.args[1].value
            return EvalResult(jnp.round(r.values / to) * to, r.labels, r.is_scalar)
        if f in ("clamp", "clamp_min", "clamp_max"):
            r = self.eval(e.args[0])
            v = r.values
            if f == "clamp":
                v = jnp.clip(v, e.args[1].value, e.args[2].value)
            elif f == "clamp_min":
                v = jnp.maximum(v, e.args[1].value)
            else:
                v = jnp.minimum(v, e.args[1].value)
            return EvalResult(v, r.labels)
        if f == "scalar":
            r = self.eval(e.args[0])
            if r.num_series == 1:
                return EvalResult(r.values, [{}], is_scalar=True)
            v = jnp.full((1, self.num_steps), jnp.nan, jnp.float32)
            return EvalResult(v, [{}], is_scalar=True)
        if f == "vector":
            r = self.eval(e.args[0])
            return EvalResult(r.values, [{}])
        if f == "time":
            t = (jnp.asarray(self.steps_ms()) / 1000.0).astype(jnp.float32)
            return EvalResult(t[None, :], [{}], is_scalar=True)
        if f == "timestamp":
            sel = self._selector_arg(e, 0, want_range=False)
            out, labels = self._run_window(sel, "instant")
            # divide in f64: f32 quantizes epoch-ms to ~minutes
            ts = (out["last_ts"].astype(jnp.float64) / 1000.0)
            ts = jnp.where(jnp.isnan(out["last"]), jnp.nan, ts)
            return EvalResult(ts, labels)
        if f == "absent":
            r = self.eval(e.args[0])
            present = jnp.any(~jnp.isnan(r.values), axis=0) if r.num_series else (
                jnp.zeros(self.num_steps, bool)
            )
            v = jnp.where(present, jnp.nan, 1.0).astype(jnp.float32)
            lab = {}
            if isinstance(e.args[0], VectorSelector):
                lab = {
                    m.name: m.value
                    for m in e.args[0].matchers
                    if m.op == "=" and m.name != "__field__"
                }
            return EvalResult(v[None, :], [lab])
        if f in self._SUBQ_REDUCERS:
            sel_i = 1 if f == "quantile_over_time" else 0
            arg = e.args[sel_i] if len(e.args) > sel_i else None
            if isinstance(arg, SubqueryExpr):
                q = (self.eval(e.args[0]).values[0]
                     if f == "quantile_over_time" else None)
                return self._eval_subquery_window(f, arg, q)
        if (f in ("rate", "increase", "delta", "irate", "idelta")
                and e.args and isinstance(e.args[0], SubqueryExpr)):
            return self._eval_subquery_counter(f, e.args[0])
        if f in ("rate", "increase", "delta"):
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "counter")
            start, _rng, pinned = self._last_window_grid
            if pinned:
                range_end = np.full(self.num_steps, start, dtype=np.float64)
            else:
                range_end = start + self.step_ms * np.arange(
                    self.num_steps, dtype=np.float64
                )
            vals = _extrapolated(
                out, sel.range_s, range_end, counter=f != "delta",
                is_rate=f == "rate",
            )
            return EvalResult(vals, labels)
        if f in ("irate", "idelta"):
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "irate")
            vals = _instant_pair(
                f, out["last_ts"], out["prev_ts"],
                out["last_val"], out["prev_val"])
            return EvalResult(vals, labels)
        if f in ("resets", "changes"):
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "counter_rc")
            return EvalResult(out[f], labels)
        if f in ("avg_over_time", "sum_over_time", "count_over_time",
                 "last_over_time", "first_over_time", "stddev_over_time",
                 "stdvar_over_time", "present_over_time"):
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "gauge_window")
            present = ~jnp.isnan(out["last"])
            table = {
                "avg_over_time": out["avg"],
                "sum_over_time": out["sum"],
                "count_over_time": jnp.where(present, out["count"], jnp.nan),
                "last_over_time": out["last"],
                "first_over_time": out["first"],
                "stddev_over_time": jnp.sqrt(out["var"]),
                "stdvar_over_time": out["var"],
                "present_over_time": jnp.where(present, 1.0, jnp.nan),
            }
            return EvalResult(table[f], labels)
        if f in ("min_over_time", "max_over_time"):
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "minmax")
            return EvalResult(out["min" if f == "min_over_time" else "max"], labels)
        if f == "deriv":
            sel = self._selector_arg(e, 0)
            out, labels = self._run_window(sel, "regression")
            return EvalResult(out["slope"], labels)
        if f == "predict_linear":
            sel = self._selector_arg(e, 0)
            horizon = self.eval(e.args[1]).values[0]  # scalar [T]
            out, labels = self._run_window(sel, "regression")
            # regression t is seconds relative to each step's start_ms grid;
            # predict at t_step + horizon
            t_at = (jnp.asarray(self.steps_ms()) - self.start_ms).astype(
                jnp.float32
            ) / 1000.0
            vals = out["intercept"] + out["slope"] * (t_at[None, :] + horizon[None, :])
            return EvalResult(vals, labels)
        if f == "histogram_quantile":
            return self._histogram_quantile(e)
        if f == "label_replace":
            r = self.eval(e.args[0])
            dst, repl, src, regex = (a.value for a in e.args[1:5])
            rx = re.compile(str(regex))
            # Prometheus $1 / ${1} group refs → python \1 / \g<1>
            template = re.sub(r"\$\{(\w+)\}", r"\\g<\1>", str(repl))
            template = re.sub(r"\$(\d+)", r"\\\1", template)
            labels = []
            for lab in r.labels:
                m = rx.fullmatch(str(lab.get(src, "")))
                lab = dict(lab)
                if m is not None:
                    lab[dst] = m.expand(template)
                    if lab[dst] == "":
                        lab.pop(dst, None)
                labels.append(lab)
            return EvalResult(r.values, labels)
        if f == "label_join":
            r = self.eval(e.args[0])
            dst = e.args[1].value
            sep = e.args[2].value
            srcs = [a.value for a in e.args[3:]]
            labels = []
            for lab in r.labels:
                lab = dict(lab)
                lab[dst] = str(sep).join(str(lab.get(s, "")) for s in srcs)
                labels.append(lab)
            return EvalResult(r.values, labels)
        if f == "sort" or f == "sort_desc":
            return self.eval(e.args[0])  # ordering is a presentation concern
        if f == "quantile_over_time":
            if len(e.args) != 2:
                raise PlanError("quantile_over_time(φ, series[range])")
            q = self.eval(e.args[0]).values[0]
            sel = self._selector_arg(e, 1)
            vals, labels = self._run_matrix(sel, "quantile", (q,))
            return EvalResult(vals, labels)
        if f == "mad_over_time":
            sel = self._selector_arg(e, 0)
            vals, labels = self._run_matrix(sel, "mad")
            return EvalResult(vals, labels)
        if f == "double_exponential_smoothing":
            if len(e.args) != 3:
                raise PlanError(
                    "double_exponential_smoothing(series[range], sf, tf)")
            sel = self._selector_arg(e, 0)
            sf = self.eval(e.args[1]).values[0]
            tf = self.eval(e.args[2]).values[0]
            vals, labels = self._run_matrix(sel, "holt", (sf, tf))
            return EvalResult(vals, labels)
        raise Unsupported(f"promql function {f}")

    # *_over_time reducers applicable to a subquery window matrix
    _SUBQ_REDUCERS = {
        "avg_over_time", "sum_over_time", "min_over_time", "max_over_time",
        "count_over_time", "last_over_time", "first_over_time",
        "stddev_over_time", "stdvar_over_time", "present_over_time",
        "quantile_over_time", "mad_over_time",
    }

    def _subquery_matrix(self, sq: SubqueryExpr):
        """Shared window-matrix construction for subquery evaluation:
        inner expr evaluated on the sub-step grid, gathered into
        [S, T, K] windows.  Returns (win, mask, ts_tk [T, K] ms,
        steps [T] ms, labels) or None for an empty inner vector."""
        range_ms = int(sq.range_s * 1000)
        sub_ms = max(int((sq.step_s or self.step_ms / 1000.0) * 1000), 1)
        offset_ms = int(sq.offset_s * 1000)
        end_ms = (self.start_ms - offset_ms
                  + self.step_ms * (self.num_steps - 1))
        lo_ms = self.start_ms - offset_ms - range_ms
        # inner grid: absolute multiples of sub_ms in (lo, end]
        t0 = (lo_ms // sub_ms + 1) * sub_ms
        if t0 > end_ms:
            t0 = end_ms
        inner = PromEvaluator(
            self.db, t0 / 1000.0, end_ms / 1000.0, sub_ms / 1000.0,
            self.lookback_ms / 1000.0)
        res = inner.eval(sq.expr)
        vals = res.values  # [S, TI]
        if vals.shape[0] == 0:
            return None
        ti = vals.shape[1]
        K = range_ms // sub_ms + 1
        steps = (self.start_ms - offset_ms
                 + self.step_ms * np.arange(self.num_steps, dtype=np.int64))
        j_lo = (steps - range_ms - t0) // sub_ms + 1  # first j inside
        k = np.arange(K, dtype=np.int64)
        idx = j_lo[:, None] + k[None, :]  # [T, K]
        ts_tk = t0 + idx * sub_ms
        in_win = (idx >= 0) & (idx < ti) & (ts_tk <= steps[:, None])
        idxc = jnp.asarray(np.clip(idx, 0, max(ti - 1, 0)))
        win = vals[:, idxc]  # [S, T, K]
        m = jnp.asarray(in_win)[None, :, :] & ~jnp.isnan(win)
        return win, m, ts_tk, steps, res.labels

    def _eval_subquery_counter(self, f: str, sq: SubqueryExpr) -> EvalResult:
        """rate/increase/delta/irate/idelta over a subquery matrix: the
        'samples' are the inner evaluations; counter-reset adjustment
        scans the window axis (fori over K — K is small), then the SAME
        _extrapolated as the selector path finishes rate/increase."""
        mat = self._subquery_matrix(sq)
        if mat is None:
            return EvalResult(
                jnp.zeros((0, self.num_steps), jnp.float32), [])
        win, m, ts_tk, steps, labels = mat
        S = win.shape[0]
        K = win.shape[2]
        ks = jnp.arange(K)
        cnt = m.sum(axis=-1)
        first_k = jnp.where(m, ks, K).min(-1)
        last_k = jnp.where(m, ks, -1).max(-1)
        fkc = jnp.clip(first_k, 0, K - 1)
        lkc = jnp.clip(last_k, 0, K - 1)
        fv = jnp.take_along_axis(win, fkc[..., None], -1)[..., 0]
        lv = jnp.take_along_axis(win, lkc[..., None], -1)[..., 0]
        ts_b = jnp.broadcast_to(
            jnp.asarray(ts_tk)[None, :, :], win.shape)
        ft = jnp.take_along_axis(ts_b, fkc[..., None], -1)[..., 0]
        lt = jnp.take_along_axis(ts_b, lkc[..., None], -1)[..., 0]

        if f in ("irate", "idelta"):
            prev_k = jnp.where(m & (ks < last_k[..., None]), ks, -1).max(-1)
            pkc = jnp.clip(prev_k, 0, K - 1)
            pv = jnp.take_along_axis(win, pkc[..., None], -1)[..., 0]
            pt = jnp.take_along_axis(ts_b, pkc[..., None], -1)[..., 0]
            vals = _instant_pair(f, lt, pt, lv, pv, guard=cnt >= 2)
            return EvalResult(vals.astype(jnp.float32), labels)

        def body(k, carry):
            prev, has_prev, dropsum = carry
            v = jax.lax.dynamic_slice_in_dim(win, k, 1, axis=2)[..., 0]
            valid = jax.lax.dynamic_slice_in_dim(m, k, 1, axis=2)[..., 0]
            reset = valid & has_prev & (prev > v)
            dropsum = dropsum + jnp.where(reset, prev, 0.0)
            prev = jnp.where(valid, v, prev)
            has_prev = has_prev | valid
            return prev, has_prev, dropsum

        zeros = jnp.zeros(win.shape[:2], win.dtype)
        _p, _h, drops = jax.lax.fori_loop(
            0, K, body, (zeros, jnp.zeros(win.shape[:2], bool), zeros))
        out = {
            "first_ts": ft, "last_ts": lt,
            "first_val": fv, "count": cnt.astype(jnp.float32),
            "delta_adj": lv - fv + drops,
            "delta_raw": lv - fv,
        }
        vals = _extrapolated(
            out, sq.range_s, steps.astype(np.float64),
            counter=f != "delta", is_rate=f == "rate")
        return EvalResult(vals, labels)

    def _eval_subquery_window(self, f: str, sq: SubqueryExpr,
                              q=None) -> EvalResult:
        """fn_over_time(expr[range:step]) — PromQL subqueries: evaluate
        the inner expression on the sub-step grid covering
        (start − range, end], then reduce each outer step's window of
        inner evaluations (reference src/promql/src/planner.rs subquery
        lowering; Prometheus aligns inner steps to absolute multiples of
        the sub-step)."""
        mat = self._subquery_matrix(sq)
        if mat is None:
            return EvalResult(
                jnp.zeros((0, self.num_steps), jnp.float32), [])
        win, m, _ts_tk, _steps, labels = mat
        K = win.shape[2]
        cnt = m.sum(axis=-1)
        has = cnt > 0
        nan = jnp.float32(jnp.nan)
        z = jnp.where(m, win, 0.0)
        if f == "sum_over_time":
            out = jnp.where(has, z.sum(-1), nan)
        elif f == "count_over_time":
            out = jnp.where(has, cnt.astype(jnp.float32), nan)
        elif f == "present_over_time":
            out = jnp.where(has, 1.0, nan)
        elif f == "avg_over_time":
            out = jnp.where(has, z.sum(-1) / jnp.maximum(cnt, 1), nan)
        elif f in ("stddev_over_time", "stdvar_over_time"):
            mean = z.sum(-1) / jnp.maximum(cnt, 1)
            var = (jnp.where(m, (win - mean[..., None]) ** 2, 0.0).sum(-1)
                   / jnp.maximum(cnt, 1))
            out = jnp.where(
                has, jnp.sqrt(var) if f == "stddev_over_time" else var, nan)
        elif f == "min_over_time":
            out = jnp.where(
                has, jnp.where(m, win, jnp.inf).min(-1), nan)
        elif f == "max_over_time":
            out = jnp.where(
                has, jnp.where(m, win, -jnp.inf).max(-1), nan)
        elif f in ("last_over_time", "first_over_time"):
            # index of the last/first valid sub-evaluation in the window
            ks = jnp.arange(K)
            if f == "last_over_time":
                pick = jnp.where(m, ks, -1).max(-1)
            else:
                pick = jnp.where(m, ks, K).min(-1)
            pickc = jnp.clip(pick, 0, K - 1)
            out = jnp.where(
                has, jnp.take_along_axis(win, pickc[..., None], -1)[..., 0],
                nan)
        elif f in ("quantile_over_time", "mad_over_time"):
            srt = jnp.sort(jnp.where(m, win, jnp.inf), axis=-1)

            def q_of(sorted_w, qq):
                rank = qq * jnp.maximum(cnt - 1, 0).astype(jnp.float32)
                lo_r = jnp.clip(jnp.floor(rank).astype(jnp.int32), 0, K - 1)
                hi_r = jnp.clip(jnp.ceil(rank).astype(jnp.int32), 0, K - 1)
                vlo = jnp.take_along_axis(sorted_w, lo_r[..., None], -1)[..., 0]
                vhi = jnp.take_along_axis(sorted_w, hi_r[..., None], -1)[..., 0]
                return vlo + (vhi - vlo) * (rank - lo_r.astype(jnp.float32))

            if f == "quantile_over_time":
                qv = jnp.broadcast_to(
                    jnp.asarray(q, jnp.float32)[None, :], cnt.shape)
                out = q_of(srt, qv)
                out = jnp.where(qv < 0, -jnp.inf,
                                jnp.where(qv > 1, jnp.inf, out))
            else:
                med = q_of(srt, jnp.float32(0.5))
                dev = jnp.sort(
                    jnp.where(m, jnp.abs(win - med[..., None]), jnp.inf),
                    axis=-1)
                out = q_of(dev, jnp.float32(0.5))
            out = jnp.where(has, out, nan)
        else:  # pragma: no cover — guarded by _SUBQ_REDUCERS
            raise Unsupported(f"{f} over subquery")
        return EvalResult(out.astype(jnp.float32), labels)

    def _selector_arg(self, e: FunctionCall, i: int, want_range: bool = True) -> VectorSelector:
        a = e.args[i]
        if not isinstance(a, VectorSelector):
            raise Unsupported(f"{e.func} needs a selector argument, got {a}")
        if want_range and a.range_s is None:
            raise PlanError(f"{e.func} needs a range vector (e.g. {a}[5m])")
        return a

    # ---- aggregation ------------------------------------------------------
    def _scalar_param(self, param: PromExpr | None, who: str) -> float:
        """Aggregation parameter (k, q): literal or constant scalar expr."""
        if param is None:
            raise PlanError(f"{who} needs a parameter")
        if isinstance(param, NumberLit):
            return float(param.value)
        r = self.eval(param)
        if not r.is_scalar:
            raise Unsupported(f"{who} parameter must be a scalar")
        vals = np.asarray(r.values[0])
        if len(vals) > 1 and not np.allclose(vals, vals[0], equal_nan=True):
            raise Unsupported(f"{who} parameter varying per step")
        v = float(vals[0])
        if np.isnan(v):
            raise PlanError(f"{who} parameter evaluates to NaN")
        return v

    def _group_series(self, e: Aggregation, r: EvalResult):
        """Group-id assignment for an aggregation input — the ONE
        definition of PromQL grouping semantics, with two providers:

        - resident path (input labels still ARE the selector's
          LazySeriesLabels): group ids are computed VECTORIZED from the
          region's dictionary-encoded tag codes (canonical str-level term
          ids per column, mixed-radix combine, first-appearance
          renumbering) and held resident per (selection, grouping) in
          PromLayoutCache — no per-series Python objects at all;
        - host fallback (label-transforming functions ran in between):
          the original dict loop.

        Returns (gid_dev [S] i32, ng, out_labels, row_order_dev [S],
        seg_start np [ng]) where row_order/seg_start give the
        group-contiguous row permutation used by the segment-sorted
        quantile/topk kernels.
        """
        return self._group_series_of(e, r.labels, r.num_series)

    def _group_series_of(self, e: Aggregation, labels, n: int):
        """_group_series over bare (labels, n) — the fused chain
        (compile/fused.py) groups straight off the selection, before any
        EvalResult exists.  Same providers, same caches, one definition."""
        group_key = _group_key(e.grouping, e.without)
        gspec = ("without" if e.without else "by",
                 tuple(sorted(e.grouping or ())))
        if isinstance(labels, LazySeriesLabels) and n == len(labels.tsids):
            payload = self._cached_group(
                labels, gspec, lambda: _series_group_ids(
                    labels.idx, labels.tsids, e.grouping or [], e.without))
            gid_dev, ng, rep_rows, row_order_dev, seg_start = payload
            out_labels = LazyGroupLabels(labels, rep_rows, group_key)
            return gid_dev, ng, out_labels, row_order_dev, seg_start

        groups: dict[tuple, int] = {}
        gids = np.zeros(n, dtype=np.int32)
        out_labels: list[dict] = []
        for i, lab in enumerate(labels):
            k = group_key(lab)
            if k not in groups:
                groups[k] = len(groups)
                out_labels.append(dict(k))
            gids[i] = groups[k]
        ng = len(groups)
        row_order = np.argsort(gids, kind="stable")
        seg_start = np.searchsorted(gids[row_order], np.arange(ng))
        return (jnp.asarray(gids), ng, out_labels, jnp.asarray(row_order),
                seg_start)

    def _cached_group(self, labels: LazySeriesLabels, gspec: tuple, build):
        """A selection's grouping payload from the group-id cache (key:
        the matchers and ``gspec``), ``build()`` on a miss and kept where
        the cache admits it; None where ``build`` finds nothing."""
        cache = labels.cache
        ckey = (labels.matcher_key, gspec)
        payload = None
        if cache is not None:
            payload = cache.lookup("group", labels.region_id, ckey,
                                   labels.generation)
            self.cache_events["group_hit" if payload is not None
                              else "group_miss"] += 1
        if payload is None:
            payload = build()
            if cache is not None and payload is not None:
                nbytes = sum(
                    int(a.nbytes) for a in payload
                    if hasattr(a, "nbytes"))
                if cache.admit(nbytes):
                    cache.store("group", labels.region_id, ckey,
                                labels.generation, payload, nbytes)
                else:
                    self.cache_events["group_reject"] += 1
        return payload

    def _bucket_slots_of(self, e: Aggregation, labels, n: int):
        """``_series_bucket_slots`` of a selection under ``sum by (le,
        …)``, held in the group-id cache beside the plain group ids (key:
        the matchers and the grouping): (slots_dev [S] i32, G, K,
        bounds_dev [K] f32, outer labels, filled slots), or None where the
        labels are not the selection's or no series has a bucket."""
        if not (isinstance(labels, LazySeriesLabels)
                and n == len(labels.tsids)):
            return None
        outer = sorted(k for k in e.grouping if k != "le")

        def build():
            slots = _series_bucket_slots(labels.idx, labels.tsids, outer)
            if slots is None:
                return None
            gids, ng, k, bounds, rep_rows, filled = slots
            return (jnp.asarray(gids), ng, k,
                    jnp.asarray(bounds, jnp.float32), rep_rows, filled)

        payload = self._cached_group(labels, ("histogram", tuple(outer)),
                                     build)
        if payload is None:
            return None
        gid_dev, ng, k, bounds_dev, rep_rows, filled = payload
        return (gid_dev, ng, k, bounds_dev,
                LazyGroupLabels(labels, rep_rows, _group_key(outer, False)),
                filled)

    def eval_aggregation(self, e: Aggregation) -> EvalResult:
        # whole-plan fusion: selection→window→group as ONE device
        # dispatch when the chain matches the fused surface
        # (compile/fused.py); None falls through to the multi-kernel
        # path below
        from greptimedb_tpu.compile.fused import try_fused_aggregation

        fused = try_fused_aggregation(self, e)
        if fused is not None:
            return fused
        r = self.eval(e.expr)
        if r.num_series == 0:
            return r
        with TRACER.stage("group_agg", op=e.op) as st:
            gid_dev, ng, out_labels, row_order_dev, seg_start = (
                self._group_series(e, r))
        self._stage_mark("group_agg", st)
        v = r.values
        S = v.shape[0]
        present = ~jnp.isnan(v)
        # int32 count accumulator: float32 segment sums lose exactness
        # past 2^24 members per group (mirrors PR 1's mesh int-SUM fix)
        cnt = jax.ops.segment_sum(present.astype(jnp.int32), gid_dev,
                                  num_segments=ng)
        fcnt = cnt.astype(jnp.float32)
        has = cnt > 0

        if e.op in ("sum", "avg", "count", "group", "stddev", "stdvar"):
            s = jax.ops.segment_sum(jnp.where(present, v, 0), gid_dev, num_segments=ng)
            if e.op == "sum":
                out = jnp.where(has, s, jnp.nan)
            elif e.op == "avg":
                out = jnp.where(has, s / jnp.maximum(fcnt, 1), jnp.nan)
            elif e.op == "count":
                out = jnp.where(has, fcnt, jnp.nan)
            elif e.op == "group":
                out = jnp.where(has, 1.0, jnp.nan)
            else:
                s2 = jax.ops.segment_sum(
                    jnp.where(present, v * v, 0), gid_dev, num_segments=ng
                )
                mean = s / jnp.maximum(fcnt, 1)
                var = jnp.maximum(s2 / jnp.maximum(fcnt, 1) - mean * mean, 0)
                out = jnp.where(has, var if e.op == "stdvar" else jnp.sqrt(var),
                                jnp.nan)
            return EvalResult(out, out_labels)
        if e.op in ("min", "max"):
            fill = jnp.inf if e.op == "min" else -jnp.inf
            fn = jax.ops.segment_min if e.op == "min" else jax.ops.segment_max
            out = fn(jnp.where(present, v, fill), gid_dev, num_segments=ng)
            return EvalResult(jnp.where(has, out, jnp.nan), out_labels)
        if e.op == "quantile":
            # segment-sorted ranks: ONE device dispatch for all groups —
            # rows permuted group-contiguous, a two-key lexicographic sort
            # orders values within each segment per step (NaNs sort last),
            # then the two straddling order statistics interpolate
            # (Prometheus linear quantile, same rule as quantile_over_time)
            q = self._scalar_param(e.param, "quantile")
            gs = gid_dev[row_order_dev]
            gb = jnp.broadcast_to(gs[:, None], v.shape)
            _, sv = jax.lax.sort((gb, v[row_order_dev]), dimension=0,
                                 num_keys=2)
            base = jnp.asarray(seg_start, dtype=jnp.int32)[:, None]  # [ng,1]
            rank = jnp.float32(q) * jnp.maximum(fcnt - 1, 0)  # [ng, T]
            lo_r = jnp.floor(rank).astype(jnp.int32)
            hi_r = jnp.ceil(rank).astype(jnp.int32)
            vlo = jnp.take_along_axis(sv, jnp.clip(base + lo_r, 0, S - 1), 0)
            vhi = jnp.take_along_axis(sv, jnp.clip(base + hi_r, 0, S - 1), 0)
            out = vlo + (vhi - vlo) * (rank - lo_r.astype(jnp.float32))
            if q < 0:
                out = jnp.full_like(out, -jnp.inf)
            elif q > 1:
                out = jnp.full_like(out, jnp.inf)
            out = jnp.where(has, out, jnp.nan)
            return EvalResult(out.astype(jnp.float32), out_labels)
        if e.op in ("topk", "bottomk"):
            k = int(self._scalar_param(e.param, e.op))
            if k <= 0:
                return EvalResult(jnp.zeros((0, self.num_steps), jnp.float32), [])
            sign = 1.0 if e.op == "topk" else -1.0
            work = jnp.where(present, sign * v, -jnp.inf)
            if ng == 1 and not e.grouping and not e.without:
                kth = -jnp.sort(-work, axis=0)[jnp.minimum(k - 1, v.shape[0] - 1)]
                keep = work >= kth[None, :]
            else:
                # per-group k-th value via ONE segment-sorted dispatch:
                # sort (gid, -work) lexicographically per step, read each
                # group's (min(k, size)-1)-th row, then keep every row at
                # or above its group's threshold (ties kept, as before)
                gs = gid_dev[row_order_dev]
                gb = jnp.broadcast_to(gs[:, None], v.shape)
                _, sw = jax.lax.sort((gb, -work[row_order_dev]), dimension=0,
                                     num_keys=2)
                sizes = np.diff(np.append(seg_start, S))
                kth_row = jnp.asarray(
                    seg_start + np.minimum(k, sizes) - 1, dtype=jnp.int32)
                kth = -jnp.take_along_axis(
                    sw, jnp.broadcast_to(kth_row[:, None], (ng, v.shape[1])),
                    0)
                keep = work >= kth[gid_dev]
            out = jnp.where(keep & present, v, jnp.nan)
            return EvalResult(out, r.labels)
        raise Unsupported(f"aggregation {e.op}")

    # ---- binary ops ---------------------------------------------------------
    def eval_binary(self, e: BinaryExpr) -> EvalResult:
        l = self.eval(e.lhs)
        r = self.eval(e.rhs)
        op = e.op

        # for filter comparisons the surviving sample value comes from the
        # vector side (Prometheus keeps LHS for vector-vector)
        keep_rhs_value = l.is_scalar and not r.is_scalar

        def apply(a, b):
            if op == "+":
                return a + b
            if op == "-":
                return a - b
            if op == "*":
                return a * b
            if op == "/":
                return a / b
            if op == "%":
                return jnp.mod(a, b)
            if op == "^":
                return jnp.power(a, b)
            if op == "atan2":
                return jnp.arctan2(a, b)
            cmp = {
                "==": a == b, "!=": a != b, "<": a < b,
                "<=": a <= b, ">": a > b, ">=": a >= b,
            }[op]
            if e.bool_modifier:
                return jnp.where(jnp.isnan(a) | jnp.isnan(b), jnp.nan,
                                 cmp.astype(jnp.float32))
            return jnp.where(cmp, b if keep_rhs_value else a, jnp.nan)

        if op in ("and", "or", "unless"):
            return self._set_op(e, l, r)

        if l.is_scalar and r.is_scalar:
            return EvalResult(apply(l.values, r.values), [{}], is_scalar=True)
        if l.is_scalar:
            return EvalResult(apply(l.values[0][None, :], r.values), r.labels)
        if r.is_scalar:
            return EvalResult(apply(l.values, r.values[0][None, :]), l.labels)

        li, ri, labels = self._match_series(e, l, r)
        out = apply(l.values[jnp.asarray(li)], r.values[jnp.asarray(ri)])
        return EvalResult(out, labels)

    def _match_key(self, e: BinaryExpr, lab: dict) -> tuple:
        if e.on is not None:
            keys = sorted(e.on)
        else:
            drop = set(e.ignoring or [])
            drop.add("__name__")
            keys = sorted(k for k in lab if k not in drop)
        return tuple((k, str(lab.get(k, ""))) for k in keys)

    def _match_series(self, e: BinaryExpr, l: EvalResult, r: EvalResult):
        rmap: dict[tuple, int] = {}
        for j, lab in enumerate(r.labels):
            k = self._match_key(e, lab)
            if k in rmap:
                raise PlanError(f"many-to-many vector match on {k}")
            rmap[k] = j
        li, ri, labels = [], [], []
        for i, lab in enumerate(l.labels):
            k = self._match_key(e, lab)
            j = rmap.get(k)
            if j is None:
                continue
            li.append(i)
            ri.append(j)
            if e.on is not None:
                labels.append(dict(k))
            else:
                labels.append({kk: vv for kk, vv in lab.items()
                               if kk not in (e.ignoring or [])})
        if not li:
            return [0], [0], []  # empty result
        return li, ri, labels

    def _set_op(self, e: BinaryExpr, l: EvalResult, r: EvalResult) -> EvalResult:
        rkeys = {self._match_key(e, lab) for lab in r.labels}
        if e.op == "and":
            keep = [i for i, lab in enumerate(l.labels)
                    if self._match_key(e, lab) in rkeys]
            if not keep:
                return EvalResult(jnp.zeros((0, self.num_steps), jnp.float32), [])
            idx = jnp.asarray(keep)
            rrows = {self._match_key(e, lab): j for j, lab in enumerate(r.labels)}
            rsel = jnp.asarray([rrows[self._match_key(e, l.labels[i])] for i in keep])
            vals = jnp.where(~jnp.isnan(r.values[rsel]), l.values[idx], jnp.nan)
            return EvalResult(vals, [l.labels[i] for i in keep])
        if e.op == "unless":
            rrows = {self._match_key(e, lab): j for j, lab in enumerate(r.labels)}
            vals_list = []
            labels = []
            for i, lab in enumerate(l.labels):
                j = rrows.get(self._match_key(e, lab))
                if j is None:
                    vals_list.append(l.values[i])
                else:
                    vals_list.append(
                        jnp.where(jnp.isnan(r.values[j]), l.values[i], jnp.nan)
                    )
                labels.append(lab)
            if not labels:
                return EvalResult(jnp.zeros((0, self.num_steps), jnp.float32), [])
            return EvalResult(jnp.stack(vals_list), labels)
        # or: left rows plus right rows whose key is absent on the left
        lkeys = {self._match_key(e, lab) for lab in l.labels}
        extra = [j for j, lab in enumerate(r.labels)
                 if self._match_key(e, lab) not in lkeys]
        vals = l.values
        labels = list(l.labels)
        if extra:
            vals = jnp.concatenate([vals, r.values[jnp.asarray(extra)]], axis=0)
            labels += [r.labels[j] for j in extra]
        return EvalResult(vals, labels)

    # ---- histogram_quantile -------------------------------------------------
    def _histogram_quantile(self, e: FunctionCall) -> EvalResult:
        """``histogram_quantile(φ, buckets)``: ``sum by (le, …)`` over a
        window function of one selector runs as one fused program
        (compile/fused.py ``try_fused_histogram``); anything else is
        evaluated, numbered on the host (``_label_bucket_slots``) and
        folded by one program.  Both fold with ``histogram_fold``."""
        if len(e.args) != 2:
            raise PlanError("histogram_quantile(φ, buckets)")
        q = self._scalar_param(e.args[0], "histogram_quantile")
        from greptimedb_tpu.compile.fused import try_fused_histogram

        fused = try_fused_histogram(self, e, q)
        if fused is not None:
            return fused
        r = self.eval(e.args[1])
        slots = None if r.is_scalar else _label_bucket_slots(r.labels)
        if slots is None:
            return EvalResult(jnp.zeros((0, self.num_steps), jnp.float32), [])
        gids, ng, k, bounds, out_labels, filled = slots
        with TRACER.stage("group_agg", op="histogram_quantile", groups=ng,
                          buckets=k) as st:
            count_histogram_dispatch("unfused", ng, k, filled)
            vals = _series_histogram(r.values, jnp.asarray(gids),
                                     jnp.asarray(bounds, jnp.float32),
                                     jnp.float32(q), ng * k, k)
        self._stage_mark("group_agg", st)
        return EvalResult(vals, out_labels)


def _instant_pair(f: str, last_ts, prev_ts, last_val, prev_val,
                  guard=None) -> jnp.ndarray:
    """irate/idelta from the last two samples — the ONE definition of
    the instant-pair reset rule, shared by the selector kernel path and
    the subquery matrix path (Prometheus instantValue semantics)."""
    dt = (last_ts - prev_ts).astype(jnp.float32) / 1000.0
    dv = last_val - prev_val
    if f == "irate":
        dv = jnp.where(dv < 0, last_val, dv)  # counter reset
    dv = dv.astype(jnp.float32)  # a wide layout's pair is f64 up to here
    ok = dt > 0
    if guard is not None:
        ok = ok & guard
    return jnp.where(ok, dv / dt if f == "irate" else dv, jnp.nan)


def _extrapolated(out: dict, range_s: float, range_end_ms: np.ndarray,
                  counter: bool, is_rate: bool) -> jnp.ndarray:
    """Prometheus extrapolatedRate (reference extrapolate_rate.rs:56)."""
    rng_ms = range_s * 1000.0
    ft = out["first_ts"].astype(jnp.float64)
    lt = out["last_ts"].astype(jnp.float64)
    cnt = out["count"]
    delta = out["delta_adj"] if counter else out["delta_raw"]
    range_end = jnp.asarray(range_end_ms)[None, :]  # [1, T]
    range_start = range_end - rng_ms

    sampled = (lt - ft) / 1000.0
    avg_dur = sampled / jnp.maximum(cnt - 1, 1)
    dur_to_start = (ft - range_start) / 1000.0
    dur_to_end = (range_end - lt) / 1000.0
    threshold = avg_dur * 1.1
    dur_to_start = jnp.where(dur_to_start >= threshold, avg_dur / 2, dur_to_start)
    dur_to_end = jnp.where(dur_to_end >= threshold, avg_dur / 2, dur_to_end)
    if counter:
        fv = out["first_val"].astype(jnp.float64)
        d64 = delta.astype(jnp.float64)
        dur_to_zero = jnp.where(d64 > 0, sampled * (fv / jnp.maximum(d64, 1e-30)),
                                jnp.inf)
        dur_to_start = jnp.minimum(dur_to_start, dur_to_zero)
    factor = (sampled + dur_to_start + dur_to_end) / jnp.maximum(sampled, 1e-30)
    result = delta.astype(jnp.float64) * factor
    if is_rate:
        result = result / range_s
    return jnp.where(cnt >= 2, result.astype(jnp.float32), jnp.nan)


# A rank this close above a bucket's count, as a share of the histogram's
# total, is a tie with it where the next bucket is empty.  Buckets summed
# from rates in float32 carry errors of a few 2^-24 of the total; where
# the exact rank equals a bucket's count (every integer count, one
# extrapolation: the rank is q * total) it would otherwise land above or
# below at random, and with an empty bucket next a whole bucket's width
# apart.  Within this much every side keeps the tie's answer, as
# Prometheus's ``>=`` gives it in exact arithmetic.  Where the next
# bucket is not empty Prometheus's answer has no step there, and the rank
# interpolates into that bucket as Prometheus's does.
RANK_TOLERANCE = 2.0 ** -18


def histogram_fold(counts, bounds, q):
    """Prometheus's ``bucketQuantile`` (promql/quantile.go) for every
    group and step at once: ``counts`` [G, K, T] cumulative bucket counts
    with NaN where a bucket has no sample at a step, ``bounds`` [K]
    ascending and distinct (equal bounds are one slot: their counts were
    summed where the slots were numbered), ``q`` a scalar; [G, T] f32,
    NaN where there is no answer.

    At each step only the buckets present count: the highest must be
    ``+Inf`` and there must be two, else NaN; counts are forced monotonic
    (a running max along K); no observations is NaN; ``b`` is the first
    bucket with count ≥ q · total (within ``RANK_TOLERANCE``) over all
    but the last, found by compares along K; ``b`` the last gives the
    highest finite bound; a rank within the tolerance above ``b``'s
    count interpolates into the next present bucket from ``b``'s bound,
    or is that bound where the next is empty or ``+Inf``; ``b`` the first
    with a bound ≤ 0 that bound; else linear interpolation inside ``b``.  q < 0 is −Inf, q > 1 +Inf,
    NaN NaN wherever a bucket is present.  No gather and no loop: K is a
    histogram's buckets, a few dozen."""
    counts = counts.astype(jnp.float32)
    g, k, t = counts.shape
    q = jnp.asarray(q, jnp.float32)
    neg = jnp.float32(-jnp.inf)
    bound = jnp.broadcast_to(
        jnp.asarray(bounds, jnp.float32).reshape(1, k, 1), counts.shape)
    valid = ~jnp.isnan(counts)
    present = valid.any(axis=1)

    def before(x):  # x at the bucket below each slot, −inf below the first
        return jnp.concatenate([jnp.full((g, 1, t), neg), x[:, :-1]], axis=1)

    def after(x):  # x at the nearest present bucket above each slot
        x = jax.lax.cummin(jnp.where(valid, x, -neg), axis=1, reverse=True)
        return jnp.concatenate([x[:, 1:], jnp.full((g, 1, t), -neg)], axis=1)

    # over the present buckets: the monotonic counts, the bound of each
    # one's predecessor; absent slots carry their predecessor's
    mono = jax.lax.cummax(jnp.where(valid, counts, neg), axis=1)
    below = before(mono)
    below_bound = before(jax.lax.cummax(jnp.where(valid, bound, neg), axis=1))
    total = mono[:, -1]
    ok = (valid[:, -1] & (bound[:, -1] == jnp.inf) & (valid.sum(axis=1) >= 2)
          & (total > 0))
    rank = q * total
    reach = (rank - RANK_TOLERANCE * total)[:, None]
    first = valid & (below == neg)
    hit = (valid & (jnp.arange(k) < k - 1)[None, :, None]
           & (mono >= reach) & (below < reach))

    def at_b(x):
        return jnp.where(hit, x, 0).sum(axis=1)

    lo = at_b(jnp.where(first, 0, below))
    start = at_b(jnp.where(first, 0, below_bound))
    end = at_b(bound)
    top = at_b(mono)
    frac = jnp.minimum((rank - lo) / (top - lo), 1)
    val = start + (end - start) * frac
    val = jnp.where((hit & first & (bound <= 0)).any(axis=1), end, val)
    # the rank within the tolerance above b's count: into the next bucket
    next_top, next_end = at_b(after(mono)), at_b(after(bound))
    runs_on = (next_top > top) & (next_end < -neg)
    past = end + (next_end - end) * jnp.minimum(
        (rank - top) / (next_top - top), 1)
    val = jnp.where(top < rank, jnp.where(runs_on, past, end), val)
    val = jnp.where(hit.any(axis=1), val, below_bound[:, -1])
    special = jnp.where(q < 0, -jnp.inf, jnp.where(q > 1, jnp.inf, jnp.nan))
    inside = (q >= 0) & (q <= 1)
    return jnp.where(inside, jnp.where(ok, val, jnp.nan),
                     jnp.where(present, special, jnp.nan)).astype(jnp.float32)


@named_jit("promql_histogram_quantile", static_argnums=(4, 5))
def _series_histogram(values, slots, bounds, q, n_slots: int, k: int):
    """The unfused fold: [S, T] bucket series, each with its slot
    (o · K + k, the dead slot ``n_slots`` for none), summed into their
    slots as ``sum`` sums them (NaN where no member has a sample), then
    ``histogram_fold``; one program a shape."""
    present = ~jnp.isnan(values)
    cnt = jax.ops.segment_sum(present.astype(jnp.int32), slots,
                              num_segments=n_slots + 1)[:n_slots]
    s = jax.ops.segment_sum(jnp.where(present, values, 0), slots,
                            num_segments=n_slots + 1)[:n_slots]
    counts = jnp.where(cnt > 0, s, jnp.nan)
    return histogram_fold(counts.reshape(n_slots // k, k, -1), bounds, q)


# ---------------------------------------------------------------------------
# TQL entry (called from standalone)
# ---------------------------------------------------------------------------

def execute_tql(db, stmt):
    from greptimedb_tpu.query.engine import QueryResult
    from greptimedb_tpu.query.physical import fetch_host

    with TRACER.stage("parse"):
        expr = parse_promql(stmt.query)
    ev = PromEvaluator(
        db, stmt.start, stmt.end, stmt.step,
        stmt.lookback or DEFAULT_LOOKBACK_S,
    )
    comp = getattr(db, "plan_compiler", None)
    if comp is not None:
        # shape-class usage journal replay context (compile/journal.py):
        # captured lazily, only when this statement builds a NEW kernel
        # class — a fresh process replays the same TQL window to warm it
        comp.set_replay(lambda: {
            "kind": "tql", "query": stmt.query, "start": stmt.start,
            "end": stmt.end, "step": stmt.step, "lookback": stmt.lookback,
            "db": getattr(db, "current_db", None)})
    if stmt.command in ("EXPLAIN",):
        return QueryResult(["plan"], [[f"PromQL: {expr}"]])
    res = ev.eval(expr)
    vals = fetch_host(res.values)  # the result leaves the device
    steps = ev.steps_ms()
    with TRACER.stage("label_decode") as st:
        label_keys = sorted({k for lab in res.labels for k in lab})
        names = label_keys + ["ts", "val"]
        rows = []
        for s, lab in enumerate(res.labels):
            col = vals[s]
            for t in range(len(steps)):
                v = float(col[t])
                if np.isnan(v):
                    continue
                rows.append([str(lab.get(k, "")) for k in label_keys]
                            + [int(steps[t]), v])
    ev._stage_mark("label_decode", st)
    sink = getattr(db, "stage_sink", None)
    if sink is not None:
        # slow-query self-reporting: the TQL stage breakdown rides the
        # same sink the SQL engine's mark() writes into
        sink.update(
            {f"promql_{k}_ms": v for k, v in ev.stage_ms.items()})
        sink["output_rows"] = len(rows)
        if ev.cache_events:
            sink["promql_cache_events"] = dict(ev.cache_events)
    return QueryResult(names, rows)
