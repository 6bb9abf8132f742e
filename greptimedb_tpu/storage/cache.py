"""Device-resident region cache: host columns → HBM tensors, reused across
queries.

The TPU answer to the reference's tiered read cache
(src/mito2/src/cache/: page/vector caches keep decoded batches hot in RAM;
here the hot tier is HBM). A region's merged scan result is canonicalized
once — tags to int32 codes, ts to int64, fields to their device dtype
(a DOUBLE to f32), rows padded to a shape-class bucket — and uploaded;
queries then jit straight over the cached tensors. Invalidation is by
region generation (bumped on every write/flush/compact).

A DOUBLE field whose column holds a magnitude f32 cannot count by one
(|v| ≥ 2^24: a byte counter) keeps what f32 dropped as a second f32
column beside it (``low_word_col``): v = f64(high) + f64(low) to 48
bits, exact for whole numbers under 2^49.  It reaches HBM with the
build, survives an extend and a flush, and is read by the PromQL sort
layout alone (promql/engine.py: the window programs join the words, so
a counter's increase and a reset are judged on all of the value); SQL,
the grid and every other consumer read the f32 column as before.  A column of
small magnitudes (CPU seconds, percentages) has no such companion and
nothing about it changes.

Capacity: simple LRU by bytes; eviction drops device references and lets
JAX free HBM.
"""

from __future__ import annotations

import collections
import threading
import weakref
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.datatypes.batch import pad_rows
from greptimedb_tpu.datatypes.schema import Schema
from greptimedb_tpu.datatypes.types import ConcreteDataType
from greptimedb_tpu.storage.memtable import (
    SEQ, TAGCODE_PREFIX, TSID, tagcode_col,
)
from greptimedb_tpu.storage.region import Region
from greptimedb_tpu.utils.telemetry import REGISTRY

# Registry mirrors of the per-instance cache counters (reference: the
# per-crate lazy_static CACHE_HIT/CACHE_MISS vectors in src/mito2/src/
# metrics.rs).  The instance attributes (hits/misses/...) stay the
# per-cache source of truth for tests and /status; these registry
# counters make the same events SQL-queryable via runtime_metrics and
# scrapeable at /metrics, which is what benchmark/run.py reads.
M_CACHE_EVENTS = REGISTRY.counter(
    "greptime_cache_events_total",
    "Resident-cache events (hit/miss/build/eviction/invalidation/"
    "quota_reject/extend)",
    labels=("cache", "kind", "event"),
)
M_CACHE_BYTES = REGISTRY.gauge(
    "greptime_cache_resident_bytes",
    "Bytes resident in each device cache (HBM for device tensors)",
    labels=("cache",),
)
M_CACHE_ENTRIES = REGISTRY.gauge(
    "greptime_cache_entries",
    "Entries resident in each device cache",
    labels=("cache",),
)


def _export_cache_gauges(name: str, cache) -> None:
    """Point the per-cache bytes/entries gauges at this instance via a
    weakref: scrape-time pulls read live state without keeping a dead
    cache (tests build hundreds of short-lived dbs) alive forever.  The
    newest instance wins the label — one standalone instance per process
    is the served configuration."""
    ref = weakref.ref(cache)
    M_CACHE_BYTES.labels(name).set_function(
        lambda: c._bytes if (c := ref()) is not None else 0.0)
    M_CACHE_ENTRIES.labels(name).set_function(
        lambda: len(c._lru) if (c := ref()) is not None else 0.0)


_DICTS_VERSION = 0  # process-wide monotonic dict-content version


def next_dicts_version() -> int:
    """Shared monotonic version for dictionary-derived compiled constants
    (used by both DeviceTable and GridTable builds)."""
    global _DICTS_VERSION
    _DICTS_VERSION += 1
    return _DICTS_VERSION

# Large columns stream to the device in bounded pieces (storage/scan.py
# stream_to_device), so host staging never holds a second whole copy.


def _to_device(arr: np.ndarray) -> jnp.ndarray:
    """Delegates to the scan pipeline's double-buffered streamer: bounded
    chunks with two dispatches in flight, so host staging overlaps the
    previous chunk's transfer instead of serializing on it."""
    from greptimedb_tpu.storage.scan import stream_to_device

    return stream_to_device(arr)


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceTable:
    """A region's (or shard's) query-ready resident tensors.

    columns: ts (int64), fields (f32/ints), per-tag code columns (int32),
    plus __tsid__ (int32). Sorted by (tsid, ts) — segment ops get
    indices_are_sorted on the series axis for free.
    """

    columns: dict[str, jnp.ndarray]
    row_mask: jnp.ndarray
    num_series: int
    dicts: dict[str, list] = field(default_factory=dict)
    # tag columns whose codes are nondecreasing in row order — unlocks the
    # scatter-free sorted segment reduction in the query executor
    sorted_tags: tuple = ()
    # monotonic per-build version of ``dicts``: kernels that bake dict-
    # derived constants (vector/fulltext) key their cache on it
    dicts_version: int = 0
    # lineage root: the dicts_version of the FULL build this table
    # descends from.  Device-side extends bump dicts_version but keep the
    # root (dictionaries only ever APPEND within a lineage), so
    # incrementally extendable derived state — the fulltext fingerprint
    # matrix — keys on the root and extends by vocabulary tail instead of
    # rebuilding per append
    dicts_root: int = 0

    @property
    def padded_rows(self) -> int:
        return int(self.row_mask.shape[0])

    def nbytes(self) -> int:
        total = self.row_mask.nbytes
        for v in self.columns.values():
            total += v.nbytes
        return total

    def tree_flatten(self):
        names = sorted(self.columns)
        children = tuple(self.columns[n] for n in names) + (self.row_mask,)
        aux = (
            tuple(names),
            self.num_series,
            tuple((k, tuple(v)) for k, v in sorted(self.dicts.items())),
            tuple(self.sorted_tags),
            self.dicts_version,
            self.dicts_root,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, num_series, dict_items, sorted_tags, dver, droot = aux
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1], num_series,
                   {k: list(v) for k, v in dict_items}, sorted_tags, dver,
                   droot)


def _canonical_column(
    schema: Schema, encoders: dict, name: str, arr: np.ndarray,
    dicts: dict[str, list],
) -> np.ndarray:
    """One column of host scan output → device encoding (unpadded).

    The single definition of canonicalization, shared by the full build
    and the incremental extend path so the two can never diverge: tags →
    region dictionary codes (int32); string FIELDs → ad-hoc dictionary
    codes seeded from ``dicts`` (NULL becomes ""); numerics → device
    dtype; internal columns pass through.  ``dicts`` is updated in place.
    """
    if name == TSID:
        return arr.astype(np.int32)
    if schema.has_column(name):
        c = schema.column(name)
        if c.is_tag:
            enc = encoders[name]
            uniq, inv = np.unique(arr.astype(object), return_inverse=True)
            codes = np.fromiter(
                (enc.get(v) for v in uniq), dtype=np.int32, count=len(uniq)
            )
            dicts[name] = enc.values()
            return codes[inv]
        if c.dtype.is_string_like:
            # string FIELD (log lines, json): ad-hoc dictionary — codes
            # live on device, values in dicts for decode
            from greptimedb_tpu.datatypes.batch import DictionaryEncoder

            enc = DictionaryEncoder(dicts.get(name, []))
            # NULL string fields become "" (np.unique cannot order None)
            arr = np.array(["" if v is None else v for v in arr],
                           dtype=object)
            uniq, inv = np.unique(arr, return_inverse=True)
            codes = np.fromiter(
                (enc.get_or_insert(v) for v in uniq), dtype=np.int32,
                count=len(uniq),
            )
            dicts[name] = enc.values()
            return codes[inv]
        return arr.astype(c.dtype.to_device_dtype())
    return arr  # internal numeric column (e.g. __op__)


# from here on an f32 no longer holds every whole number
_F32_WHOLE = float(1 << 24)


def low_word_col(name: str) -> str:
    """The resident column that holds what f32 dropped of DOUBLE field
    ``name`` (absent where the field's magnitudes stay under 2^24)."""
    return f"__lo_{name}__"


def _low_word(schema: Schema, name: str, arr: np.ndarray, high: np.ndarray,
              always: bool = False) -> "np.ndarray | None":
    """f32(v − f64(f32(v))) of a DOUBLE field's host values, or None for
    another kind of column and — unless ``always`` — for one whose
    magnitudes all stay under 2^24.  0 where f32(v) is not finite."""
    if name == TSID or not schema.has_column(name):
        return None
    c = schema.column(name)
    if c.is_tag or c.dtype is not ConcreteDataType.FLOAT64:
        return None
    arr = np.asarray(arr, dtype=np.float64)
    with np.errstate(invalid="ignore"):   # NaN (NULL) compares false
        if not always and not (np.abs(arr) >= _F32_WHOLE).any():
            return None
        return np.where(np.isfinite(high), arr - high.astype(np.float64),
                        0.0).astype(np.float32)


def _pad_value(schema: Schema, name: str, dtype: np.dtype):
    """Padding-row fill for a canonicalized column: poison code -1 for
    tag/string-dict columns, NaN for floats, 0 otherwise."""
    if name != TSID and schema.has_column(name):
        c = schema.column(name)
        if c.is_tag or c.dtype.is_string_like:
            return -1
    return np.nan if np.issubdtype(dtype, np.floating) else 0


def build_device_table(
    region: Region,
    ts_range: tuple[int | None, int | None] = (None, None),
    columns: list[str] | None = None,
) -> DeviceTable:
    """Scan, canonicalize and upload one region's data.

    Real regions scan on the CODE path: string tags arrive as
    ``__tagcode_<name>__`` int32 companions already in region code space
    (storage/sst.py maps each file's dictionary once), so canonicalization
    is a rename — no per-row object array, no re-hash.  Duck-typed views
    (combined/metric/file engines) keep the raw scan + re-encode."""
    if getattr(region, "scan_supports_codes", False):
        host = region.scan_host(ts_range, columns, with_tag_codes=True)
    else:
        host = region.scan_host(ts_range, columns)
    schema = region.schema
    n = len(host[TSID])
    padded = pad_rows(n)

    dev_cols: dict[str, jnp.ndarray] = {}
    host_canon: dict[str, np.ndarray] = {}
    dicts: dict[str, list] = {}
    for name, arr in host.items():
        if name == SEQ:
            continue  # sequences are a storage concern; queries never see them
        if name.startswith(TAGCODE_PREFIX):
            # code-path tag column: already region codes
            name = name[len(TAGCODE_PREFIX):-2]
            vals = arr.astype(np.int32, copy=False)
            dicts[name] = region.encoders[name].values()
        else:
            vals = _canonical_column(schema, region.encoders, name, arr,
                                     dicts)
        out = np.full(padded, _pad_value(schema, name, vals.dtype),
                      dtype=vals.dtype)
        out[:n] = vals
        host_canon[name] = vals
        dev_cols[name] = _to_device(out)
        low = _low_word(schema, name, arr, vals)
        if low is not None:
            out = np.zeros(padded, dtype=np.float32)
            out[:n] = low
            dev_cols[low_word_col(name)] = _to_device(out)
    mask = np.zeros(padded, dtype=bool)
    mask[:n] = True
    # monotone tag detection: rows are (tsid, ts)-sorted; a tag qualifies
    # for sorted segment reductions when its codes are nondecreasing AND
    # bijective with series runs (each code run is exactly one tsid run, so
    # ts — and hence any time bucket — is ascending within every code run).
    # Detection runs on the host copies — reading dev_cols back would pull
    # the whole column off the device again.
    sorted_tags = []
    if n > 0:
        tsid_runs = 1 + int((np.diff(host_canon[TSID]) != 0).sum())
        for c in schema.tag_columns:
            if c.name in host_canon:
                codes = host_canon[c.name]
                d = np.diff(codes)
                if bool((d >= 0).all()) and 1 + int((d != 0).sum()) == tsid_runs:
                    sorted_tags.append(c.name)
    global _DICTS_VERSION
    _DICTS_VERSION += 1
    return DeviceTable(dev_cols, jnp.asarray(mask), region.num_series, dicts,
                       tuple(sorted_tags), _DICTS_VERSION, _DICTS_VERSION)


def _canonical_delta(
    region, chunks: list[dict], dicts: dict[str, list], resident
) -> "tuple[dict[str, np.ndarray] | None, int]":
    """Canonicalize append-log chunks (same rules as build_device_table —
    shared _canonical_column — unpadded).  ``dicts`` holds the resident
    table's dictionaries and is extended in place so codes stay
    consistent across deltas.  A DOUBLE field that has its low word among
    the ``resident`` columns gets the delta's; one that has none and now
    needs it (the delta passes 2^24) cannot be extended: None."""
    schema = region.schema
    host = {
        k: np.concatenate([np.asarray(c[k]) for c in chunks])
        for k in chunks[0]
    }
    dn = len(host[TSID])
    out: dict[str, np.ndarray] = {}
    for name, arr in host.items():
        if name == SEQ or name.startswith(TAGCODE_PREFIX):
            continue  # codes fold into their tag column below
        tc = tagcode_col(name)
        if (tc in host and schema.has_column(name)
                and schema.column(name).is_tag):
            # memtable chunks carry write-time region codes: reuse them
            # instead of re-hashing the raw strings per delta
            out[name] = host[tc].astype(np.int32, copy=False)
            dicts[name] = region.encoders[name].values()
            continue
        out[name] = _canonical_column(schema, region.encoders, name, arr,
                                      dicts)
        wide = low_word_col(name) in resident
        low = _low_word(schema, name, arr, out[name], always=wide)
        if low is not None:
            if not wide:
                return None, dn
            out[low_word_col(name)] = low
    return out, dn


def extend_device_table(
    table: DeviceTable, region, chunks: list[dict], live_rows: int
) -> "tuple[DeviceTable, int] | None":
    """Append new rows to a resident DeviceTable WITHOUT re-uploading the
    base: only the delta crosses host→device; growth beyond the padding
    bucket concatenates on device; the (tsid, ts) sort order every
    consumer relies on is restored by a device-side lexsort + gather
    (HBM-local, no PCIe traffic).

    Correctness precondition (enforced by Region's append log): delta rows
    are PUT-only with timestamps strictly after all resident rows, so no
    dedup/tombstone interaction with the base is possible.

    Returns None where the delta needs a column the resident table was
    built without (a DOUBLE field's low word): the caller rebuilds.
    """
    dicts = dict(table.dicts)
    delta, dn = _canonical_delta(region, chunks, dicts, table.columns)
    if delta is None:
        return None
    n_old = live_rows
    n_new = n_old + dn
    old_padded = table.padded_rows
    new_padded = pad_rows(n_new)
    ts_name = region.schema.time_index.name

    cols: dict[str, jnp.ndarray] = {}
    for name, col in table.columns.items():
        dv = delta.get(name)
        if dv is None:  # column absent from delta (shouldn't happen)
            dv = np.zeros(dn, dtype=np.asarray(col[:1]).dtype)
        if new_padded > old_padded:
            pad_np = np.full(
                new_padded - old_padded,
                _pad_value(region.schema, name, dv.dtype),
                dtype=dv.dtype,
            )
            col = jnp.concatenate([col, jnp.asarray(pad_np)])
        cols[name] = col.at[n_old:n_new].set(jnp.asarray(dv))
    mask = table.row_mask
    if new_padded > old_padded:
        mask = jnp.concatenate(
            [mask, jnp.zeros(new_padded - old_padded, dtype=bool)]
        )
    mask = mask.at[n_old:n_new].set(True)

    # restore global (tsid, ts) order; padding rows pin to the end via the
    # inverted mask as the primary key
    order = jnp.lexsort(
        (cols[ts_name], cols[TSID], (~mask).astype(jnp.int32))
    )
    cols = {k: v[order] for k, v in cols.items()}
    mask = mask[order]

    # sorted-tag monotonicity survives the re-sort only if no new series
    # appeared (tag-per-tsid mapping unchanged); otherwise drop until the
    # next full rebuild re-derives it
    sorted_tags = (
        table.sorted_tags if region.num_series == table.num_series else ()
    )
    global _DICTS_VERSION
    _DICTS_VERSION += 1
    return (
        DeviceTable(cols, mask, region.num_series, dicts, sorted_tags,
                    _DICTS_VERSION, table.dicts_root),
        n_new,
    )


def _append_pos(region) -> "int | None":
    """The region's absolute append-log position (Region.append_pos);
    falls back to the raw list length for duck-typed region-likes that
    predate position trimming."""
    pos = getattr(region, "append_pos", None)
    if pos is not None:
        return pos
    log = getattr(region, "_append_log", None)
    return len(log) if log is not None else None


def _chunks_since(region, pos: int) -> "list | None":
    """Append-log chunks after absolute position ``pos``; None when the
    position predates the region's trimmed window (consumer must rebuild)."""
    f = getattr(region, "append_chunks_since", None)
    if f is not None:
        return f(pos)
    log = getattr(region, "_append_log", None)
    return log[pos:] if log is not None else None


@dataclass
class _Entry:
    # DeviceTable, GridTable, or None (negative grid-eligibility cache)
    table: object
    delta_pos: int | None = None  # consumed append-log position (absolute)
    live_rows: int = 0
    # grid catch-up validity keys (see get_grid): the SST set the table
    # was built from and the region's content-mutation epoch at build time
    sst_ids: frozenset | None = None
    mutation_epoch: int = -1


class RegionCacheManager:
    """LRU of DeviceTables.

    Regions with the incremental protocol (base_version + append log) key
    by base_version; pure time-forward appends EXTEND the resident tensors
    device-side instead of rebuilding (reference analog: the write-through
    cache keeps mito's page cache warm across flushes,
    src/mito2/src/cache/write_cache.rs).  Duck-typed views and restricted
    scans keep generation-keyed full rebuilds.
    """

    def __init__(self, capacity_bytes: int = 8 << 30, mesh=None):
        # delta volume beyond max(min_extend_rows, fraction * resident
        # rows) → full rebuild (restores sorted-tag eligibility and
        # compacts fragmentation); small deltas always extend
        self.rebuild_fraction = 0.25
        self.min_extend_rows = 4096
        self.capacity = capacity_bytes
        # device mesh for series-axis sharding of resident grids (set by
        # GreptimeDB when >1 device is visible); None = single device
        self.mesh = mesh
        # optional DerivedLayoutCache chained into invalidate_region (set
        # by GreptimeDB): every drop/truncate/repartition path that
        # invalidates a region's resident tensors must also drop its
        # derived bucket-major layouts, or they leak device bytes and
        # inflate the layout_cache workload usage
        self.derived_layouts = None
        # optional PromLayoutCache chained the same way: a dropped /
        # truncated / repartitioned region's resident PromQL selections,
        # sort layouts and group-id vectors must free with the region —
        # version checks catch staleness, but only explicit invalidation
        # catches deletion
        self.promql_derived = None
        self._lru: "collections.OrderedDict[tuple, _Entry]" = (
            collections.OrderedDict()
        )
        self._bytes = 0
        # guards _lru/_bytes: scheduler workers (get/get_grid) and
        # ingest-pool workers (extend_hot_tail, auto-create paths) mutate
        # them concurrently — an unguarded OrderedDict iteration would
        # raise "mutated during iteration" mid-query and unguarded
        # read-modify-writes of _bytes drift the accounting _shrink
        # evicts by.  Reentrant: _evict/_shrink run nested under it.
        # Device builds/extends run OUTSIDE it — only dict/counter ops
        # are held.
        self._struct_lock = threading.RLock()
        # serializes ingest-side hot-tail extenders (the ingest pool runs
        # several writers); acquired non-blocking — a contended extend is
        # skipped, the query-time path stays responsible
        self._hot_tail_lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.extends = 0
        _export_cache_gauges("region_device", self)

    def get(
        self,
        region: Region,
        ts_range: tuple[int | None, int | None] = (None, None),
        columns: list[str] | None = None,
    ) -> DeviceTable:
        base_ver = getattr(region, "base_version", None)
        append_log = getattr(region, "_append_log", None)
        incremental = (
            base_ver is not None
            and append_log is not None
            and ts_range == (None, None)
            and columns is None
        )
        version = base_ver if incremental else region.generation
        key = (
            region.region_id,
            version,
            ts_range,
            tuple(columns) if columns else None,
        )
        pos = _append_pos(region) if incremental else None
        entry = self._lru.get(key)
        if entry is not None:
            if not incremental or entry.delta_pos == pos:
                M_CACHE_EVENTS.labels("region_device", "table", "hit").inc()
                with self._struct_lock:
                    self.hits += 1
                    if key in self._lru:
                        self._lru.move_to_end(key)
                return entry.table
            # resident base is current; new append-log chunks extend it
            chunks = _chunks_since(region, entry.delta_pos)
            delta_rows = (sum(len(c[TSID]) for c in chunks)
                          if chunks is not None else None)
            extended = None
            if delta_rows is not None and delta_rows <= max(
                self.min_extend_rows,
                entry.live_rows * self.rebuild_fraction,
            ):
                extended = extend_device_table(
                    entry.table, region, chunks, entry.live_rows
                )
            if extended is not None:
                with self._struct_lock:
                    self.extends += 1
                M_CACHE_EVENTS.labels(
                    "region_device", "table", "extend").inc()
                # whole-entry swap (not field mutation): a concurrent
                # reader holds a self-consistent entry either way
                new_table, new_rows = extended
                with self._struct_lock:
                    if self._lru.get(key) is entry:
                        # bytes delta only when the swap applies — an
                        # entry replaced/evicted meanwhile keeps its own
                        # accounting (an unconditional += would drift
                        # _bytes upward and make _shrink evict live
                        # entries forever after).  delta_pos derives from
                        # the chunks actually applied, NOT a pos read
                        # earlier: a chunk landing between the pos read
                        # and the fetch would be in the table yet
                        # recorded unconsumed, and the next extend would
                        # append its rows a second time.
                        self._bytes += (new_table.nbytes()
                                        - entry.table.nbytes())
                        self._lru[key] = _Entry(
                            new_table,
                            delta_pos=entry.delta_pos + len(chunks),
                            live_rows=new_rows,
                            sst_ids=entry.sst_ids,
                            mutation_epoch=entry.mutation_epoch,
                        )
                        self._lru.move_to_end(key)
                self._shrink()
                return new_table
            # too much drift, trimmed past, or a column the resident table
            # was built without: rebuild
            self._evict(key)

        with self._struct_lock:
            self.misses += 1
        M_CACHE_EVENTS.labels("region_device", "table", "miss").inc()
        table = build_device_table(region, ts_range, columns)
        if incremental and _append_pos(region) != pos:
            # a chunk landed while building: the table may contain rows
            # past ``pos`` (the build reads the live memtable), so caching
            # it at pos would double-apply them on the next extend, and
            # recording the newer pos could silently drop rows the build
            # raced past.  Serve the (self-consistent) table uncached —
            # the next quiet query populates the entry; under sustained
            # ingest extend_hot_tail keeps the grid entries fresh instead.
            return table
        entry = _Entry(
            table,
            delta_pos=pos,
            live_rows=int(np.asarray(table.row_mask).sum()),
        )
        with self._struct_lock:
            # drop stale versions of the same region+range; versions live
            # in two namespaces (base_version for incremental full-table
            # entries, generation for restricted scans), so only compare
            # within the same (range, columns) class
            stale = [
                k for k in self._lru
                if k[0] == key[0] and k[2:] == key[2:] and k[1] != key[1]
            ]
            for k in stale:
                self._evict(k)
            old = self._lru.get(key)
            if old is not None and old.table is not None:
                self._bytes -= old.table.nbytes()  # concurrent double-build
            self._lru[key] = entry
            self._bytes += table.nbytes()
            self._shrink()
        return table

    def peek_table(self, region):
        """The region's resident full-table DeviceTable if one is ALREADY
        resident at the current base version, else None — never builds.
        Consumers that only accelerate when warm (the log-query DSL's
        fingerprint route) use this so a cold table stays on its host
        path instead of paying a device build it didn't ask for.  The
        entry may lag the append log; callers must treat the resident
        dictionaries as a (valid) prefix, not the complete vocabulary."""
        base_ver = getattr(region, "base_version", None)
        if base_ver is None:
            return None
        entry = self._lru.get((region.region_id, base_ver, (None, None),
                               None))
        return entry.table if entry is not None else None

    def get_grid(self, region):
        """Dense-grid resident table for a region (storage/grid.py), or
        None when the region is ineligible (cached negatively per
        base_version so queries don't re-probe every time).  Pure appends
        extend the resident grid device-side; structure changes rebuild."""
        from greptimedb_tpu.storage.grid import (
            build_grid_table, extend_grid_table,
        )

        from greptimedb_tpu.storage.grid import catch_up_grid_table

        base_ver = getattr(region, "base_version", None)
        append_log = getattr(region, "_append_log", None)
        if base_ver is None or append_log is None:
            return None  # duck-typed views (joins, staged scans): row path
        key = (region.region_id, "grid", base_ver)
        pos = _append_pos(region)
        entry = self._lru.get(key)
        if entry is not None:
            if entry.delta_pos == pos:
                M_CACHE_EVENTS.labels("region_device", "grid", "hit").inc()
                with self._struct_lock:
                    self.hits += 1
                    if key in self._lru:
                        self._lru.move_to_end(key)
                return entry.table
            chunks = _chunks_since(region, entry.delta_pos)
            if entry.table is None:
                # negative entry: re-probe only after substantial growth —
                # an ineligible (irregular/sparse) region must not pay a
                # full eligibility scan per query
                if chunks is not None:
                    appended = sum(len(c[TSID]) for c in chunks)
                    if appended <= max(
                            self.min_extend_rows,
                            entry.live_rows * self.rebuild_fraction):
                        return None
            elif chunks is not None:
                with self._struct_lock:
                    self.extends += 1
                M_CACHE_EVENTS.labels("region_device", "grid", "extend").inc()
                extended = extend_grid_table(entry.table, region, chunks,
                                             mesh=self.mesh)
                if extended is not None:
                    # whole-entry swap (not field mutation): a concurrent
                    # reader holds a self-consistent entry either way;
                    # bytes delta only when the swap applies, and
                    # delta_pos derives from the chunks actually applied
                    # (see get)
                    with self._struct_lock:
                        if self._lru.get(key) is entry:
                            self._bytes += (extended.nbytes()
                                            - entry.table.nbytes())
                            self._lru[key] = _Entry(
                                extended,
                                delta_pos=entry.delta_pos + len(chunks),
                                live_rows=entry.live_rows,
                                sst_ids=entry.sst_ids,
                                mutation_epoch=entry.mutation_epoch,
                            )
                            self._lru.move_to_end(key)
                    self._shrink()
                    return extended
            self._evict(key)  # delta does not fit (or trimmed past)

        with self._struct_lock:
            self.misses += 1
        M_CACHE_EVENTS.labels("region_device", "grid", "miss").inc()
        rows_now = region.memtable.num_rows + sum(
            m.num_rows for m in region.sst_files
        )
        cur_ids = frozenset(m.file_id for m in region.sst_files)
        epoch = getattr(region, "mutation_epoch", None)

        # incremental catch-up: a previous base_version's resident grid is
        # still valid row-for-row when only content-PRESERVING structure
        # changes happened (flush: mutation_epoch unchanged, old SST set
        # intact, memtable/append-log empty) — extend it from the new
        # files (reads prune to the not-yet-resident ts range) instead of
        # re-reading the whole region
        with self._struct_lock:
            prev_key = next(
                (k for k in self._lru
                 if k[0] == region.region_id and k[1:2] == ("grid",)), None)
            prev = self._lru.get(prev_key) if prev_key is not None else None
        if prev is not None and epoch is not None:
            if (prev.table is not None and prev.sst_ids is not None
                    and prev.mutation_epoch == epoch
                    and region.memtable.is_empty and not append_log
                    and prev.sst_ids <= cur_ids):
                new_metas = [m for m in region.sst_files
                             if m.file_id not in prev.sst_ids]
                caught = catch_up_grid_table(
                    prev.table, region, new_metas, mesh=self.mesh)
                if caught is not None:
                    M_CACHE_EVENTS.labels(
                        "region_device", "grid", "catch_up").inc()
                    with self._struct_lock:
                        self.extends += 1
                        got = self._lru.pop(prev_key, None)
                        if got is not None and got.table is not None:
                            self._bytes -= got.table.nbytes()
                        if (caught is not prev.table
                                and self.derived_layouts is not None):
                            # dicts_version moved on: the old grid's
                            # derived layouts can never hit again
                            self.derived_layouts.invalidate_region(key[0])
                        old = self._lru.get(key)
                        if old is not None and old.table is not None:
                            self._bytes -= old.table.nbytes()
                        self._lru[key] = _Entry(
                            caught, delta_pos=pos,
                            live_rows=rows_now, sst_ids=cur_ids,
                            mutation_epoch=epoch,
                        )
                        self._bytes += caught.nbytes()
                        self._shrink()
                    return caught

        table = build_grid_table(region, mesh=self.mesh)
        if table is not None and _append_pos(region) != pos:
            # raced an ingest append mid-build (see get's miss path):
            # serve uncached rather than cache a table whose delta_pos
            # cannot be trusted.  Negative (None) entries cache anyway —
            # delta_pos staleness only delays the next eligibility probe.
            return table
        entry = _Entry(table, delta_pos=pos, live_rows=rows_now,
                       sst_ids=cur_ids,
                       mutation_epoch=epoch if epoch is not None else -1)
        with self._struct_lock:
            stale = [
                k for k in self._lru
                if (k[0] == key[0] and k[1:2] == ("grid",)
                    and k[2] != base_ver)
            ]
            for k in stale:
                self._evict(k)
            old = self._lru.get(key)
            if old is not None and old.table is not None:
                self._bytes -= old.table.nbytes()  # concurrent double-build
            self._lru[key] = entry
            if table is not None:
                self._bytes += table.nbytes()
            self._shrink()
        return table

    def get_sharded(self, region):
        """Series-sharded row table (parallel/dist.py ShardedTable) for
        mesh aggregation of irregular/sparse regions that the dense grid
        refuses.  Keyed by generation: any write rebuilds (row order under
        the shard permutation is not extendable in place the way grid
        columns are)."""
        if self.mesh is None:
            return None
        from greptimedb_tpu.parallel.dist import shard_region

        key = (region.region_id, "sharded", region.generation)
        entry = self._lru.get(key)
        if entry is not None:
            M_CACHE_EVENTS.labels("region_device", "sharded", "hit").inc()
            with self._struct_lock:
                self.hits += 1
                if key in self._lru:
                    self._lru.move_to_end(key)
            return entry.table
        with self._struct_lock:
            self.misses += 1
        M_CACHE_EVENTS.labels("region_device", "sharded", "miss").inc()
        table = shard_region(region, self.mesh)
        with self._struct_lock:
            for k in [
                k for k in self._lru
                if k[0] == key[0] and k[1:2] == ("sharded",) and k != key
            ]:
                self._evict(k)
            old = self._lru.get(key)
            if old is not None and old.table is not None:
                self._bytes -= old.table.nbytes()
            self._lru[key] = _Entry(table)
            self._bytes += table.nbytes()
            self._shrink()
        return table

    def install_grid(self, region, table) -> None:
        """Adopt an externally built resident GridTable (snapshot restore:
        storage/grid.py load_grid_snapshot) as the region's current grid
        entry, exactly as if get_grid had built it."""
        key = (region.region_id, "grid", region.base_version)
        rows_now = region.memtable.num_rows + sum(
            m.num_rows for m in region.sst_files
        )
        # same stale-version sweep as get_grid's miss path: entries for
        # other base_versions are dead weight that would count against
        # capacity and could shrink-evict the fresh grid
        with self._struct_lock:
            for k in [
                k for k in self._lru
                if k[0] == key[0] and k[1:2] == ("grid",)
            ]:
                self._evict(k)
            self._lru[key] = _Entry(
                table, delta_pos=_append_pos(region), live_rows=rows_now,
                sst_ids=frozenset(m.file_id for m in region.sst_files),
                mutation_epoch=getattr(region, "mutation_epoch", -1),
            )
            self._bytes += table.nbytes()
            self._shrink()

    def extend_hot_tail(self, region) -> bool:
        """Eager hot-tail append for freshly ACKED ingest rows: when this
        region already has a resident grid at the current base_version,
        scatter the pending append-log delta into its not-yet-covered
        tail right now (ingest-side), so the next query finds the grid
        current instead of paying the extend itself.  Opportunistic —
        the extender lock is taken non-blocking, so contending ingest
        workers skip instead of queueing; a False return means the
        query-time extend/rebuild path (get_grid) remains responsible.
        Small deltas are left to accumulate (one scatter dispatch per
        tiny batch would throttle ingest).

        Publication is a whole-entry swap, never field-wise mutation:
        concurrent readers (scheduler workers in get_grid) hold either
        the old entry or the new one, and both are internally consistent
        (table matches delta_pos) — a torn pair would silently serve a
        grid missing acked rows."""
        from greptimedb_tpu.storage.grid import extend_grid_table
        from greptimedb_tpu.utils.tracing import TRACER

        base_ver = getattr(region, "base_version", None)
        if base_ver is None:
            return False
        key = (region.region_id, "grid", base_ver)
        if not self._hot_tail_lock.acquire(blocking=False):
            return False
        try:
            entry = self._lru.get(key)
            if entry is None or entry.table is None:
                return False
            pos = _append_pos(region)
            if entry.delta_pos == pos:
                return False
            chunks = _chunks_since(region, entry.delta_pos)
            if chunks is None:
                return False  # trimmed past: query path rebuilds
            delta_rows = sum(len(c[TSID]) for c in chunks)
            if delta_rows < self.min_extend_rows:
                return False  # let small batches accumulate
            with TRACER.stage("ingest_grid_tail", region=region.region_id,
                              rows=delta_rows):
                extended = extend_grid_table(entry.table, region, chunks,
                                             mesh=self.mesh)
            if extended is None:
                return False  # off-grid delta: get_grid will evict/rebuild
            M_CACHE_EVENTS.labels("region_device", "grid", "hot_tail").inc()
            with self._struct_lock:
                self.extends += 1
                # not evicted/replaced meanwhile; delta_pos derives from
                # the chunks actually scattered, not the earlier pos read
                # (see get)
                if self._lru.get(key) is entry:
                    self._bytes += extended.nbytes() - entry.table.nbytes()
                    self._lru[key] = _Entry(
                        extended,
                        delta_pos=entry.delta_pos + len(chunks),
                        live_rows=entry.live_rows,
                        sst_ids=entry.sst_ids,
                        mutation_epoch=entry.mutation_epoch,
                    )
            self._shrink()
            return True
        finally:
            self._hot_tail_lock.release()

    def _shrink(self) -> None:
        with self._struct_lock:
            while self._bytes > self.capacity and len(self._lru) > 1:
                self._evict(next(iter(self._lru)))

    def _evict(self, key) -> None:
        with self._struct_lock:
            e = self._lru.pop(key, None)
            if e is not None and e.table is not None:
                self._bytes -= e.table.nbytes()
        if (self.derived_layouts is not None and key[1:2] == ("grid",)):
            # a grid leaving residency (capacity pressure, stale-version
            # sweep, failed extend) strands its derived layouts: the next
            # grid build bumps dicts_version, so they could never hit
            # again — drop them now instead of leaking device bytes
            self.derived_layouts.invalidate_region(key[0])
        if (self.promql_derived is not None
                and key[2:] == ((None, None), None)):
            # same stranding rule for the PromQL derived state: sort and
            # bounds layouts key on the full-table DeviceTable's
            # dicts_version, which the next build bumps — a full-table
            # entry leaving residency makes them permanently unhittable
            self.promql_derived.invalidate_region(key[0])

    def invalidate_region(self, region_id: int) -> None:
        with self._struct_lock:
            for k in [k for k in self._lru if k[0] == region_id]:
                self._evict(k)
        if self.derived_layouts is not None:
            self.derived_layouts.invalidate_region(region_id)
        if self.promql_derived is not None:
            self.promql_derived.invalidate_region(region_id)


@dataclass
class _LayoutEntry:
    version: int  # GridTable.dicts_version the layout was derived from
    arrays: tuple
    nbytes: int


class _ByteLRUCache:
    """Shared machinery for the derived resident caches (SQL bucket-major
    layouts, PromQL evaluation state): an LRU of version-tagged entries
    bounded by bytes, with reject-to-fallback admission through an
    optional WorkloadMemoryManager probe and region-scoped invalidation.
    Subclasses define the key shape and hit/miss bookkeeping; the
    eviction/admission/reclaim semantics exist exactly once here so the
    two caches cannot drift."""

    # registry label ("layout" / "promql"); subclasses override
    metric_cache = "derived"

    def __init__(self, capacity_bytes: int | None, env_var: str):
        import os

        if capacity_bytes is None:
            capacity_bytes = int(os.environ.get(env_var, str(1 << 30)))
        self.capacity = capacity_bytes
        # optional callable(nbytes) -> bool wired by the server to
        # WorkloadMemoryManager.try_admit(<workload>, ...)
        self.memory_probe = None
        self._lru: "collections.OrderedDict[tuple, _LayoutEntry]" = (
            collections.OrderedDict()
        )
        self._bytes = 0
        self.rejects = 0
        self.builds = 0
        self.evictions = 0
        _export_cache_gauges(self.metric_cache, self)

    def _kind_of(self, key: tuple) -> str:
        """Entry kind for registry labels (PromLayoutCache keys carry it)."""
        return "layout"

    @property
    def bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._lru)

    def _lookup_entry(self, key: tuple, version):
        """Arrays for ``key`` at ``version``, or None.  A stale entry
        (older derivation version) is evicted immediately — the version
        bump IS the invalidation."""
        entry = self._lru.get(key)
        if entry is not None and entry.version == version:
            self._lru.move_to_end(key)
            return entry.arrays
        if entry is not None:
            self._evict(key)
        return None

    def admit(self, nbytes: int) -> bool:
        """Reject-to-fallback admission: evict LRU entries to make room,
        then consult the workload memory probe.  False means the caller
        serves from its uncached fallback path."""
        if nbytes > self.capacity:
            self.rejects += 1
            M_CACHE_EVENTS.labels(
                self.metric_cache, "any", "quota_reject").inc()
            return False
        while self._bytes + nbytes > self.capacity and self._lru:
            self._evict(next(iter(self._lru)))
        if self.memory_probe is not None and not self.memory_probe(nbytes):
            self.rejects += 1
            M_CACHE_EVENTS.labels(
                self.metric_cache, "any", "quota_reject").inc()
            return False
        return True

    def _store_entry(self, key: tuple, version, arrays, nbytes: int) -> None:
        if key in self._lru:
            self._evict(key)
        self._lru[key] = _LayoutEntry(version, arrays, nbytes)
        self._bytes += nbytes
        self.builds += 1
        M_CACHE_EVENTS.labels(
            self.metric_cache, self._kind_of(key), "build").inc()

    def reclaim(self, nbytes: int) -> None:
        """WorkloadMemoryManager reclaim hook: free at least ``nbytes``
        by LRU eviction (admission pressure from other workloads)."""
        freed = 0
        while freed < nbytes and self._lru:
            k = next(iter(self._lru))
            freed += self._lru[k].nbytes
            self._evict(k)

    def invalidate_region(self, region_id: int) -> None:
        for k in [k for k in self._lru if k[0] == region_id]:
            M_CACHE_EVENTS.labels(
                self.metric_cache, self._kind_of(k), "invalidation").inc()
            self._evict(k)

    def _evict(self, key) -> None:
        e = self._lru.pop(key, None)
        if e is not None:
            self._bytes -= e.nbytes
            self.evictions += 1
            M_CACHE_EVENTS.labels(
                self.metric_cache, self._kind_of(key), "eviction").inc()


class PromLayoutCache(_ByteLRUCache):
    """Resident derived state for the PromQL evaluation hot path — the
    PromQL twin of DerivedLayoutCache, holding three kinds of entries:

    - ``selection``: per (region, matcher set) the matched tsid vector and
      its padded device copy, so repeated evaluations skip the inverted-
      index walk AND the O(series) label-dict materialization (labels are
      decoded lazily, only for output groups);
    - ``sort``: per (region, field column) the composite (tsid, ts)-key
      sort of the resident table — ts/val presorted once on device with
      the per-series row pointer, its densest spacing and longest run,
      reused by every window kernel instead of re-sorting (or searching)
      the full table inside each eval;
    - ``group``: per (selection, by/without grouping) the device group-id
      vector + segment layout computed from the region's dictionary-
      encoded tag codes, replacing the per-eval Python loop over label
      dicts.

    Invalidation follows PR 1's generation discipline: every entry stores
    the version it was derived from (region ``generation`` for
    selection/group, resident-table ``dicts_version`` for sort — both bump
    on every ingest/flush/compaction) and a mismatch at lookup evicts and
    rebuilds.  Capacity is LRU by bytes; ``admit`` consults the optional
    WorkloadMemoryManager probe with reject-to-fallback — a rejected build
    is served uncached from the identical code path, so results are
    bit-exact either way.
    """

    KINDS = ("selection", "sort", "group")
    metric_cache = "promql"

    def _kind_of(self, key: tuple) -> str:
        return key[1]

    def __init__(self, capacity_bytes: int | None = None, mesh=None):
        super().__init__(capacity_bytes, "GREPTIME_PROMQL_CACHE_BYTES")
        # series-axis mesh (parallel/dist.py promql_row_shardings): resident
        # sort layouts are placed sharded when a multi-device mesh exists
        self.mesh = mesh
        self.hits = dict.fromkeys(self.KINDS, 0)
        self.misses = dict.fromkeys(self.KINDS, 0)

    def lookup(self, kind: str, region_id: int, key: tuple, version):
        """Payload for (kind, region, key) at ``version``, or None (same
        contract as DerivedLayoutCache.lookup)."""
        payload = self._lookup_entry((region_id, kind, key), version)
        self.hits[kind] += payload is not None
        self.misses[kind] += payload is None
        M_CACHE_EVENTS.labels(
            "promql", kind, "hit" if payload is not None else "miss").inc()
        return payload

    def store(self, kind: str, region_id: int, key: tuple, version,
              payload, nbytes: int) -> None:
        self._store_entry((region_id, kind, key), version, payload, nbytes)

    def stats(self) -> dict:
        """Flat counters for the bench JSON line / status endpoints."""
        out = {"bytes": self._bytes, "entries": len(self._lru),
               "rejects": self.rejects, "builds": self.builds,
               "evictions": self.evictions}
        for kind in self.KINDS:
            out[f"{kind}_hits"] = self.hits[kind]
            out[f"{kind}_misses"] = self.misses[kind]
        return out

class DerivedLayoutCache(_ByteLRUCache):
    """Resident derived layouts for the aligned-window range-aggregation
    path: per (region, step class) the bucket-major reduction of the
    resident grid — the ``[S, nb, r]`` reshape contracted once on device
    into per-(series, bucket) partial sums ``[C, S, NB]`` and validity
    counts ``[S, NB]`` — reused across warm queries so the per-query
    aligned-window work drops to a bucket-axis slice plus the tiny
    series-axis merge (the "pay the transpose once" pattern of tensor-
    runtime query engines, arXiv:2203.01877).

    Invalidation is by GridTable.dicts_version (bumped on every grid
    build AND device-side append extension, which in turn follow the
    region's ingest/flush/compaction generation bumps): a version
    mismatch evicts the stale entry and rebuilds.  Capacity is LRU by
    bytes; ``admit`` additionally consults an optional
    WorkloadMemoryManager probe so the extra resident copy can never OOM
    the device — rejected builds fall back to the dynamic-slice kernel.
    """

    metric_cache = "layout"

    def __init__(self, capacity_bytes: int | None = None):
        super().__init__(capacity_bytes, "GREPTIME_LAYOUT_CACHE_BYTES")
        self.hits = 0
        self.misses = 0

    def lookup(self, region_id: int, step_class: tuple, version: int):
        """Arrays for (region, step class) at ``version``, or None."""
        arrays = self._lookup_entry((region_id, step_class), version)
        self.hits += arrays is not None
        self.misses += arrays is None
        M_CACHE_EVENTS.labels(
            "layout", "layout",
            "hit" if arrays is not None else "miss").inc()
        return arrays

    def store(self, region_id: int, step_class: tuple, version: int,
              arrays: tuple, nbytes: int) -> None:
        self._store_entry((region_id, step_class), version, arrays, nbytes)
