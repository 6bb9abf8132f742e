"""Dense time-grid resident layout: [series, time, field] tensors.

The TPU-first answer to the reference's two hot-loop layouts — mito2's
(tsid, ts)-sorted row batches (src/mito2/src/read/seq_scan.rs) and the
PromQL RangeArray dictionary-range view (src/promql/src/range_array.rs:65).
Metric data is (near-)regularly sampled, so instead of sorting rows and
scatter-reducing group aggregates, the region materializes a dense
``values[series, timestep, field]`` tensor plus a ``valid[series,
timestep]`` mask.  Aggregation by (tags × time bucket) then lowers to
reshape + reduce — no scatter, no gather, no sort — which is the shape
XLA:TPU tiles perfectly onto the VPU/MXU and which even a single CPU core
executes at memory bandwidth (SURVEY.md §5.7: "blockwise windowed
evaluation replaces RangeArray with gather-free rolling windows").

Eligibility is decided per region build: timestamps must share a coarse
enough GCD step (regular sampling), the dense grid must fit the byte
budget, and occupancy must clear a floor.  Irregular/sparse data keeps the
row-oriented DeviceTable path (storage/cache.py) — the grid is a second
resident representation, not a replacement.

Incremental protocol mirrors the DeviceTable one: pure time-forward
appends scatter into the padded tail of the resident tensors device-side;
structure changes (flush/compaction/upsert) rebuild.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.storage.durability import SstCorruption
from greptimedb_tpu.storage.memtable import OP, OP_DELETE, SEQ, TSID
from greptimedb_tpu.storage.object_store import _fsync_dir

# padding granularity: each distinct (Spad, Tpad) is a compile shape class.
# T gets coarse alignment (appends grow it constantly); S changes rarely.
_T_ALIGN = 2048
_S_ALIGN = 256
_MIN_DENSITY = float(os.environ.get("GREPTIME_GRID_MIN_DENSITY", "0.1"))
_BUDGET = int(os.environ.get("GREPTIME_GRID_BUDGET_BYTES", str(6 << 30)))


def _pad_to(n: int, align: int) -> int:
    """Small sizes get pow2 buckets, larger ones align to ``align``."""
    if n <= 0:
        return align if align < 64 else 64
    if n < align:
        return 1 << max(6, (n - 1).bit_length())
    return -(-n // align) * align


def _to_device_rows(arr: np.ndarray, sharding=None) -> jnp.ndarray:
    """Chunked host→device upload with double buffering — the scan
    pipeline's shared streamer (storage/scan.py): bounded pieces with
    two dispatches in flight, reshaped on device (free — same layout).
    With a sharding the array lands distributed across the mesh in one
    placement."""
    from greptimedb_tpu.storage.scan import stream_to_device

    return stream_to_device(arr, sharding)


@jax.tree_util.register_pytree_node_class
@dataclass
class GridTable:
    """One region's dense-grid resident tensors.

    ts of grid point t = ``ts0 + t * step`` for t < ``nt``; padding points
    (t >= nt) and padding series (s >= num_series) have valid=False.
    """

    values: jnp.ndarray              # [C, Spad, Tpad] float32 — field-major
    # planes keep the time axis contiguous, so per-bucket reductions and
    # rolling windows vectorize along memory order on both CPU and TPU
    valid: jnp.ndarray               # [Spad, Tpad] bool
    tag_codes: dict[str, jnp.ndarray]  # per-tag [Spad] int32 (pad = -1)
    ts0: int
    step: int
    nt: int                          # live timesteps
    num_series: int                  # live series
    field_names: tuple               # C order (float FIELD columns)
    dicts: dict[str, list] = field(default_factory=dict)
    # per-field "finite everywhere written" (no NaN *or* ±inf): count()
    # reuses the shared validity reduction, and sums may ride the
    # mask-free weighted reduce (inf would break its 0-weight products)
    no_nan: tuple = ()
    dicts_version: int = 0
    # owning region: derived-layout cache entries key on it so a rebuilt
    # grid (new dicts_version) REPLACES the region's stale layouts instead
    # of leaking them until LRU pressure
    region_id: int = -1

    @property
    def spad(self) -> int:
        return int(self.valid.shape[0])

    @property
    def tpad(self) -> int:
        return int(self.valid.shape[1])

    def nbytes(self) -> int:
        total = self.values.nbytes + self.valid.nbytes
        for v in self.tag_codes.values():
            total += v.nbytes
        return total

    def tree_flatten(self):
        names = sorted(self.tag_codes)
        children = (self.values, self.valid) + tuple(
            self.tag_codes[n] for n in names
        )
        aux = (
            tuple(names), self.ts0, self.step, self.nt, self.num_series,
            self.field_names,
            tuple((k, tuple(v)) for k, v in sorted(self.dicts.items())),
            self.no_nan, self.dicts_version, self.region_id,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (names, ts0, step, nt, ns, fields, dict_items, no_nan, dver,
         rid) = aux
        values, valid = children[0], children[1]
        tags = dict(zip(names, children[2:]))
        return cls(values, valid, tags, ts0, step, nt, ns, fields,
                   {k: list(v) for k, v in dict_items}, no_nan, dver, rid)


def grid_float_fields(schema) -> list[str]:
    return [c.name for c in schema.field_columns if c.dtype.is_float]


def _series_tag_matrix(region, spad: int) -> dict[str, np.ndarray]:
    """Per-tag code arrays indexed by tsid, padded with the poison code -1."""
    tags = region.tag_names
    s = region.num_series
    out = {name: np.full(spad, -1, dtype=np.int32) for name in tags}
    for key, tsid in region._series.items():
        for j, name in enumerate(tags):
            out[name][tsid] = key[j]
    return out


def _gather_parts(region, fields: list[str]):
    """Region parts (SSTs then memtable chunks) in last-write-wins order.

    SSTs sort by seq_max: flush emits monotonically increasing sequence
    ranges, and TWCS-compacted files never share (series, ts) keys with
    files of other time windows, so per-key ordering reduces to per-file
    ordering.  Memtable chunks follow in append order.

    Decodes run concurrently on the scan pipeline's bounded pool with
    scan-driven readahead.  (Catch-up builds read their own ts-restricted
    slice in catch_up_grid_table — this is the full-region gather.)
    """
    from greptimedb_tpu.storage.scan import (
        estimate_staging_bytes, prefetch_store, read_parts,
    )
    from greptimedb_tpu.storage.sst import read_sst

    ts_name = region.ts_name
    want = [ts_name, TSID, SEQ, OP] + fields
    attempts = 0
    while True:
        metas = sorted(region.sst_files, key=lambda m: m.seq_max)
        prefetch_store(region.store, metas)
        est = estimate_staging_bytes(metas, len(want))
        try:
            parts = read_parts(
                [
                    (lambda m=m: read_sst(region.store, m, region.schema,
                                          columns=want))
                    for m in metas
                ],
                memory=getattr(region, "memory", None), est_bytes=est,
            )
            break
        except SstCorruption as e:
            # verified read failed: quarantine/repair, retry over the
            # refreshed live set (the grid build must never ingest
            # corrupt pages, and must keep building around a lost file)
            attempts += 1
            if attempts > 16:
                raise
            region._handle_sst_corruption(e)
    for chunk in region.memtable.snapshot_chunks():
        # within-chunk duplicates resolve by scatter order (later row wins),
        # matching keep-max-seq: rows in a chunk share one sequence and
        # arrive in insert order
        parts.append(chunk)
    return parts


def infer_grid_step(parts, ts_name: str, ts0: int) -> int:
    """GCD of (ts - ts0) across all rows — one vectorized pass, no sort."""
    g = np.int64(0)
    for p in parts:
        ts = p[ts_name]
        if len(ts):
            g = np.gcd(g, np.gcd.reduce(ts.astype(np.int64) - ts0))
    return int(g)


def grid_shardings(mesh, spad: int):
    """NamedShardings splitting the series axis across the mesh, or None
    when the padded series count does not tile the mesh.  The aggregate
    kernel (query/physical.py) is pure jnp over these arrays, so GSPMD
    partitions it automatically — per-shard bucket partials with XLA-
    inserted all-reduces over ICI at the tiny [groups, buckets] merge
    (the MergeScanExec fan-out/merge of the reference,
    src/query/src/dist_plan/merge_scan.rs:210,335, as compiler-inserted
    collectives instead of a Flight shuffle)."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    d = mesh.devices.size
    if d <= 1 or spad % d != 0:
        return None
    axis = mesh.axis_names[0]
    return {
        "values": NamedSharding(mesh, P(None, axis, None)),
        "valid": NamedSharding(mesh, P(axis, None)),
        "tags": NamedSharding(mesh, P(axis)),
    }


def build_grid_table(region, budget_bytes: int | None = None, mesh=None):
    """Attempt the dense-grid build; returns None when ineligible
    (irregular sampling, too sparse, over budget, stringly fields only).
    With a mesh, the resident tensors shard on the series axis."""
    fields = grid_float_fields(region.schema)
    if not fields or region.schema.time_index is None:
        return None
    if region.options.append_mode:
        # append mode preserves duplicate (series, ts) rows; the grid is
        # keyed by (series, timestep) and would silently dedup them
        return None
    bounds = region.ts_bounds()
    if bounds is None:
        return None  # empty region: nothing to accelerate
    ts0, ts_max = bounds
    s = region.num_series
    if s == 0:
        return None
    budget = budget_bytes if budget_bytes is not None else _BUDGET
    c = len(fields)
    ts_name = region.ts_name

    parts = _gather_parts(region, fields)
    total_rows = sum(len(p[TSID]) for p in parts)
    if total_rows == 0:
        return None
    step = infer_grid_step(parts, ts_name, ts0)
    if step <= 0:
        step = 1  # single distinct timestamp
    nt = (ts_max - ts0) // step + 1
    spad = _pad_to(s, _S_ALIGN)
    tpad = _pad_to(nt, _T_ALIGN)
    grid_bytes = spad * tpad * (4 * c + 1)
    if grid_bytes > budget:
        return None
    if total_rows / max(s * nt, 1) < _MIN_DENSITY:
        return None

    # zero-fill, not NaN: ``valid`` is the sole source of truth for cell
    # liveness, so never-written cells contribute +0 to sums and the hot
    # aggregate kernel can lower to a plain (mask-free) einsum/matmul —
    # MXU-shaped on TPU, ~3x fewer bytes on CPU (no where() temp).  Cells
    # holding a *written* NaN (tombstone fields, real NaN data) keep the
    # NaN and clear ``no_nan``, which routes queries to the masked path.
    values = np.zeros((c, spad, tpad), dtype=np.float32)
    valid = np.zeros((spad, tpad), dtype=bool)
    no_nan = [True] * c
    for p in parts:
        tsid = p[TSID].astype(np.int64)
        if not len(tsid):
            continue
        tidx = (p[ts_name].astype(np.int64) - ts0) // step
        op = p[OP]
        dels = op == OP_DELETE
        any_dels = bool(dels.any())
        for ci, name in enumerate(fields):
            col = p[name]
            if col.dtype != np.float32:
                col = col.astype(np.float32)
            if any_dels:
                # tombstones must land as 0.0 whatever their field payload
                # (schema DEFAULTs fill deleted rows with non-zero values):
                # the mask-free sum fast path relies on invalid cells
                # contributing exactly +0
                col = np.where(dels, np.float32(0.0), col)
            # no_nan really means "finite everywhere written": written NaN
            # breaks count-by-validity, and written ±inf would turn the
            # fast path's inf*0 weight products into NaN — either routes
            # the column to the masked kernel path
            if no_nan[ci] and not bool(np.isfinite(col).all()):
                no_nan[ci] = False
            values[ci][tsid, tidx] = col
        valid[tsid, tidx] = ~dels
    tag_codes = _series_tag_matrix(region, spad)
    dicts = {name: region.encoders[name].values() for name in region.tag_names}
    from greptimedb_tpu.storage.cache import next_dicts_version

    sh = grid_shardings(mesh, spad)
    return GridTable(
        values=_to_device_rows(values, sh and sh["values"]),
        valid=_to_device_rows(valid, sh and sh["valid"]),
        tag_codes={
            k: _to_device_rows(np.asarray(v), sh and sh["tags"])
            for k, v in tag_codes.items()
        },
        ts0=int(ts0),
        step=int(step),
        nt=int(nt),
        num_series=s,
        field_names=tuple(fields),
        dicts=dicts,
        no_nan=tuple(no_nan),
        dicts_version=next_dicts_version(),
        region_id=int(getattr(region, "region_id", -1)),
    )


def _region_fingerprint(region) -> dict:
    """Cheap identity of a region's resident data: SST set + memtable
    volume.  A snapshot built from the same fingerprint maps to identical
    grid tensors, so re-opening processes (bench re-runs, restarts) can
    mmap the host tensors instead of re-scanning every SST."""
    return {
        "ssts": sorted(
            (m.file_id, int(m.seq_max), int(m.num_rows))
            for m in region.sst_files
        ),
        "memtable_rows": int(region.memtable.num_rows),
        "num_series": int(region.num_series),
        "fields": grid_float_fields(region.schema),
    }


def save_grid_snapshot(table: GridTable, region, path: str) -> None:
    """Persist the dense host tensors next to the region data (mito2's
    write-through file cache idea, src/mito2/src/cache/write_cache.rs:1,
    applied to the resident layout): np arrays + a json manifest."""
    import json

    os.makedirs(path, exist_ok=True)
    np.save(os.path.join(path, "values.npy"), np.asarray(table.values))
    np.save(os.path.join(path, "valid.npy"), np.asarray(table.valid))
    np.savez(os.path.join(path, "tags.npz"),
             **{k: np.asarray(v) for k, v in table.tag_codes.items()})
    meta = {
        "ts0": table.ts0, "step": table.step, "nt": table.nt,
        "num_series": table.num_series,
        "field_names": list(table.field_names),
        "dicts": {k: list(v) for k, v in table.dicts.items()},
        "no_nan": list(table.no_nan),
        "fingerprint": _region_fingerprint(region),
    }
    tmp = os.path.join(path, "meta.json.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(path, "meta.json"))
    # rename durability: the directory entry must hit disk too, or a
    # power loss can resurrect the old meta.json against new .npy tensors
    # (fingerprint mismatch is caught, but the snapshot is silently lost)
    _fsync_dir(path)


def load_grid_snapshot(path: str, region, mesh=None):
    """Rebuild a resident GridTable from a snapshot, verifying the region
    fingerprint still matches; returns None on any mismatch/corruption
    (caller falls back to the SST scan build)."""
    import json

    from greptimedb_tpu.storage.cache import next_dicts_version

    try:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        fp = _region_fingerprint(region)
        saved = meta["fingerprint"]
        saved["ssts"] = [tuple(s) for s in saved["ssts"]]
        if saved != {**fp, "ssts": list(fp["ssts"])}:
            return None
        # the restored tag codes are decoded against the region's CURRENT
        # encoders at query time — a different code assignment (WAL
        # replay order, rebuilt dictionaries) must refuse the snapshot
        if {k: list(v) for k, v in meta["dicts"].items()} != {
            name: list(region.encoders[name].values())
            for name in region.tag_names
        }:
            return None
        values = np.load(os.path.join(path, "values.npy"), mmap_mode="r")
        valid = np.load(os.path.join(path, "valid.npy"), mmap_mode="r")
        tags = np.load(os.path.join(path, "tags.npz"))
    except Exception:  # noqa: BLE001 — any corruption (incl. BadZipFile
        # from a truncated .npz) must mean "no snapshot", never a crash
        return None
    sh = grid_shardings(mesh, int(valid.shape[0]))
    return GridTable(
        values=_to_device_rows(values, sh and sh["values"]),
        valid=_to_device_rows(valid, sh and sh["valid"]),
        tag_codes={
            k: _to_device_rows(np.asarray(tags[k]), sh and sh["tags"])
            for k in tags.files
        },
        ts0=int(meta["ts0"]),
        step=int(meta["step"]),
        nt=int(meta["nt"]),
        num_series=int(meta["num_series"]),
        field_names=tuple(meta["field_names"]),
        dicts={k: list(v) for k, v in meta["dicts"].items()},
        no_nan=tuple(meta["no_nan"]),
        dicts_version=next_dicts_version(),
        region_id=int(getattr(region, "region_id", -1)),
    )


def _grow_time_axis(values, valid, tpad: int, new_nt: int, spad: int,
                    c: int):
    """Grow the padded time axis (new cells zero-valued / invalid) so
    sustained time-forward ingest extends the resident grid in amortized
    O(1) per appended step instead of falling off a fixed-``tpad`` cliff
    into a full rebuild every linger window.  Doubles by default; falls
    back to an exact fit near the budget.  Returns ``(values, valid)``
    or None when even the exact fit exceeds the grid budget."""
    tpad2 = _pad_to(max(new_nt, 2 * tpad), _T_ALIGN)
    if spad * tpad2 * (4 * c + 1) > _BUDGET:
        tpad2 = _pad_to(new_nt, _T_ALIGN)
        if spad * tpad2 * (4 * c + 1) > _BUDGET:
            return None
    grow = tpad2 - tpad
    return (
        jnp.pad(values, ((0, 0), (0, 0), (0, grow))),
        jnp.pad(valid, ((0, 0), (0, grow))),
    )


def extend_grid_table(table: GridTable, region, chunks, mesh=None):  # gl: warm-path(host)
    """Scatter pure-append chunks into the resident grid device-side.

    Returns the extended GridTable, or None when the delta does not fit
    the resident shape/step (caller rebuilds).  Precondition (enforced by
    Region's append log): chunks are PUT-only with strictly newer
    timestamps, so no resident cell is overwritten — only new cells are
    set."""
    ts_name = region.ts_name
    fields = table.field_names
    new_series = region.num_series
    if new_series > table.spad:
        return None
    tsid = np.concatenate([c[TSID] for c in chunks]).astype(np.int64)
    if not len(tsid):
        return table
    ts = np.concatenate(
        [np.asarray(c[ts_name], dtype=np.int64) for c in chunks]
    )
    rel = ts - table.ts0
    step = table.step
    if step <= 0 or bool((rel % step != 0).any()):
        return None  # off-grid timestamps: sampling changed
    tidx = rel // step
    new_nt = int(tidx.max()) + 1
    values, valid = table.values, table.valid
    if new_nt > table.tpad:
        grown = _grow_time_axis(values, valid, table.tpad, new_nt,
                                table.spad, len(fields))
        if grown is None:
            return None
        values, valid = grown
    cols = []
    no_nan = list(table.no_nan)
    for ci, name in enumerate(fields):
        col = np.concatenate(
            [np.asarray(c[name], dtype=np.float32) for c in chunks]
        )
        if no_nan[ci] and not bool(np.isfinite(col).all()):
            no_nan[ci] = False
        cols.append(col)
    delta = np.stack(cols, axis=0)  # [C, n]
    values = values.at[
        :, jnp.asarray(tsid), jnp.asarray(tidx)
    ].set(jnp.asarray(delta))
    valid = valid.at[jnp.asarray(tsid), jnp.asarray(tidx)].set(True)
    tag_codes = table.tag_codes
    if new_series > table.num_series:
        host_tags = _series_tag_matrix(region, table.spad)
        sh = grid_shardings(mesh, table.spad)
        tag_codes = {
            k: _to_device_rows(v, sh and sh["tags"])
            for k, v in host_tags.items()
        }
    from greptimedb_tpu.storage.cache import next_dicts_version

    return GridTable(
        values=values,
        valid=valid,
        tag_codes=tag_codes,
        ts0=table.ts0,
        step=step,
        nt=max(table.nt, new_nt),
        num_series=new_series,
        field_names=fields,
        dicts={name: region.encoders[name].values()
               for name in region.tag_names},
        no_nan=tuple(no_nan),
        dicts_version=next_dicts_version(),
        region_id=table.region_id,
    )


def catch_up_grid_table(table: GridTable, region, new_metas, mesh=None):
    """Incremental grid build: extend a resident grid with freshly FLUSHED
    SSTs instead of re-reading the whole region.

    Only rows strictly after the resident coverage are read — the
    resident max timestamp bounds a ``ts_range`` that read_sst turns into
    Parquet row-group pruning, so a flushed file whose rows are already
    resident (they arrived via the append-log extend path) costs a footer
    read, not a full decode.  New cells scatter into the resident tensors
    device-side, per part in sequence order (keep-max-seq).

    Returns the extended GridTable, the SAME table when the new files
    carry nothing beyond the resident coverage, or None when the delta
    does not fit the resident shape/step (caller rebuilds).  Safety
    preconditions — no content-mutating structure change since the build
    (``Region.mutation_epoch`` unchanged), old SST set intact, memtable
    and append log empty — are enforced by the cache manager
    (storage/cache.py get_grid).
    """
    from greptimedb_tpu.storage.scan import (
        estimate_staging_bytes, prefetch_store, read_parts,
    )
    from greptimedb_tpu.storage.sst import read_sst

    fields = table.field_names
    if tuple(grid_float_fields(region.schema)) != tuple(fields):
        return None
    if region.num_series > table.spad:
        return None
    step = table.step
    if step <= 0:
        return None
    ts_name = region.ts_name
    lo = table.ts0 + (table.nt - 1) * step + 1  # strictly after resident
    want = [ts_name, TSID, SEQ, OP] + list(fields)
    metas = [
        m for m in sorted(new_metas, key=lambda m: m.seq_max)
        if m.ts_max >= lo
    ]
    prefetch_store(region.store, metas)
    est = estimate_staging_bytes(metas, len(want), (lo, None))
    try:
        parts = read_parts(
            [
                (lambda m=m: read_sst(region.store, m, region.schema,
                                      (lo, None), columns=want))
                for m in metas
            ],
            memory=getattr(region, "memory", None), est_bytes=est,
        )
    except SstCorruption as e:
        # quarantine/repair changes the SST set out from under this
        # incremental pass — hand back None so the cache does a full
        # (verified, corruption-retrying) rebuild instead
        region._handle_sst_corruption(e)
        return None
    parts = [p for p in parts if len(p[TSID])]
    if not parts:
        return table  # fully resident already (flush of consumed appends)
    all_ts = np.concatenate(
        [p[ts_name].astype(np.int64) for p in parts])
    rel = all_ts - table.ts0
    if bool((rel % step != 0).any()):
        return None  # off-grid timestamps: sampling changed
    new_nt = int(rel.max()) // step + 1
    values, valid = table.values, table.valid
    if new_nt > table.tpad:
        grown = _grow_time_axis(values, valid, table.tpad, new_nt,
                                table.spad, len(fields))
        if grown is None:
            return None
        values, valid = grown
    no_nan = list(table.no_nan)
    for p in parts:
        tsid = p[TSID].astype(np.int64)
        tidx = (p[ts_name].astype(np.int64) - table.ts0) // step
        op = p[OP]
        dels = op == OP_DELETE
        any_dels = bool(dels.any())
        cols = []
        for ci, name in enumerate(fields):
            col = p[name]
            if col.dtype != np.float32:
                col = col.astype(np.float32)
            if any_dels:
                col = np.where(dels, np.float32(0.0), col)
            if no_nan[ci] and not bool(np.isfinite(col).all()):
                no_nan[ci] = False
            cols.append(col)
        delta = np.stack(cols, axis=0)  # [C, n]
        ji, jj = jnp.asarray(tsid), jnp.asarray(tidx)
        values = values.at[:, ji, jj].set(jnp.asarray(delta))
        valid = valid.at[ji, jj].set(jnp.asarray(~dels))
    tag_codes = table.tag_codes
    if region.num_series > table.num_series:
        host_tags = _series_tag_matrix(region, table.spad)
        sh = grid_shardings(mesh, table.spad)
        tag_codes = {
            k: _to_device_rows(v, sh and sh["tags"])
            for k, v in host_tags.items()
        }
    from greptimedb_tpu.storage.cache import next_dicts_version

    return GridTable(
        values=values,
        valid=valid,
        tag_codes=tag_codes,
        ts0=table.ts0,
        step=step,
        nt=max(table.nt, new_nt),
        num_series=region.num_series,
        field_names=fields,
        dicts={name: region.encoders[name].values()
               for name in region.tag_names},
        no_nan=tuple(no_nan),
        dicts_version=next_dicts_version(),
        region_id=table.region_id,
    )
