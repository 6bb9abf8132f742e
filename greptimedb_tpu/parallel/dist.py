"""Mesh sharding and collective aggregation: the distributed query core.

Maps the reference's distributed read path (SURVEY.md §3.2: MergeScanExec
fans sub-plans out to regions over Flight, merges partial results on the
frontend) onto a jax Mesh: each device holds one shard of the series axis,
computes the pushed-down partial aggregate locally (the commutativity
split, reference dist_plan/commutativity.rs — sum/count/min/max commute;
avg decomposes into sum+count), and the merge is a psum/pmin/pmax over ICI
instead of a network shuffle.

Scales to multi-host by construction: shard_map over a Mesh spanning DCN
uses the same program; only the mesh axis assignment changes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

try:  # jax >= 0.6 moved shard_map to the public namespace
    from jax import shard_map as _shard_map_mod

    shard_map = _shard_map_mod  # type: ignore[assignment]
except ImportError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map  # type: ignore

from greptimedb_tpu.errors import InvalidArguments, Unsupported
from greptimedb_tpu.ops.segment import combine_keys
from greptimedb_tpu.ops.time import bucket_index
from greptimedb_tpu.storage.memtable import TSID
from greptimedb_tpu.utils.telemetry import REGISTRY
from greptimedb_tpu.utils.tracing import TRACER

SHARD_AXIS = "shard"

# Wall time of the collective exchange phase (shard_map partials + ICI
# psum/pmin/pmax), labelled by mesh width and compile-vs-steady-state —
# the mesh twin of the xla_compile / device_execute stages of
# query/physical.py timed_kernel_call.
M_MESH_COLLECTIVE = REGISTRY.histogram(
    "greptime_mesh_collective_seconds",
    "Mesh collective-exchange wall time (shard_map + ICI reductions)",
    labels=("devices", "phase"),
)


def create_mesh(num_devices: int | None = None, axis: str = SHARD_AXIS) -> Mesh:
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise InvalidArguments(
                f"requested {num_devices} devices, have {len(devices)}"
            )
        devices = devices[:num_devices]
    return Mesh(np.array(devices), (axis,))


@dataclass
class ShardedTable:
    """Row-sharded columnar table: global arrays of shape [D * rows_per_shard]
    laid out so shard d owns rows [d*R, (d+1)*R); device-sharded on axis 0."""

    columns: dict[str, jnp.ndarray]
    row_mask: jnp.ndarray
    mesh: Mesh
    rows_per_shard: int
    num_series: int

    @property
    def num_shards(self) -> int:
        return self.mesh.devices.size

    def nbytes(self) -> int:
        total = int(self.row_mask.size * self.row_mask.dtype.itemsize)
        for a in self.columns.values():
            total += int(a.size * a.dtype.itemsize)
        return total


def shard_table(
    host_columns: dict[str, np.ndarray],
    mesh: Mesh,
    *,
    device_dtypes: dict[str, np.dtype] | None = None,
    shard_of_series: np.ndarray | None = None,
) -> ShardedTable:
    """Split rows across mesh shards by series (tsid % D by default, or an
    explicit series→shard map from a PartitionRule), pad shards equally,
    and place with a NamedSharding so each device holds exactly its rows.
    """
    d = mesh.devices.size
    tsid = np.asarray(host_columns[TSID], dtype=np.int64)
    n = len(tsid)
    if shard_of_series is not None:
        shard = shard_of_series[tsid]
    else:
        shard = tsid % d
    order = np.lexsort((tsid, shard))
    counts = np.bincount(shard, minlength=d)
    per = int(counts.max()) if n else 1
    per = 1 << (per - 1).bit_length() if per > 1 else 1  # pow2 shape class

    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    cols_out: dict[str, jnp.ndarray] = {}
    mask = np.zeros((d, per), dtype=bool)
    offsets = np.zeros(d + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    for name, arr in host_columns.items():
        arr = arr[order]
        dt = (device_dtypes or {}).get(name, arr.dtype)
        if np.issubdtype(np.dtype(dt), np.floating):
            buf = np.full((d, per), np.nan, dtype=dt)
        else:
            buf = np.zeros((d, per), dtype=dt)
        for s in range(d):
            seg = arr[offsets[s]:offsets[s + 1]]
            buf[s, : len(seg)] = seg
        cols_out[name] = jax.device_put(buf.reshape(d * per), sharding)
    for s in range(d):
        mask[s, : counts[s]] = True
    num_series = int(tsid.max()) + 1 if n else 0
    return ShardedTable(
        columns=cols_out,
        row_mask=jax.device_put(mask.reshape(d * per), sharding),
        mesh=mesh,
        rows_per_shard=per,
        num_series=num_series,
    )


def bucket_major_shardings(mesh, spad: int):
    """NamedShardings for the derived bucket-major partial tensors
    (storage/cache.py DerivedLayoutCache): per-(series, bucket) sums
    ``[C, S, NB]`` and counts ``[S, NB]`` split on the series axis,
    matching grid_shardings (storage/grid.py) so the mesh grid's resident
    layout variant stays device-local — the per-query aligned-window
    kernel then runs SPMD with one tiny XLA-inserted collective at the
    [groups, buckets] merge, keeping parity with single-device results.
    Returns None when the padded series count does not tile the mesh."""
    if mesh is None:
        return None
    d = mesh.devices.size
    if d <= 1 or spad % d != 0:
        return None
    axis = mesh.axis_names[0]
    return {
        "sums": NamedSharding(mesh, P(None, axis, None)),
        "cnts": NamedSharding(mesh, P(axis, None)),
    }


def flow_state_shardings(mesh):
    """NamedShardings for the flow runtime's resident ``[G, W]`` partial
    matrices (flow/device.py): the GROUP axis splits across the mesh —
    group ids are assigned densely, so placement is contiguous-range by
    group hash-order, mirroring bucket_major_shardings' series split.
    The fold kernel's scatter/segment program then runs SPMD under GSPMD
    (chunk arrays replicate; XLA inserts the collectives at the
    affected-slot gather feeding the sink upsert).  Returns None on a
    single device; the caller also keeps the replicated placement while
    the padded group count does not tile the mesh."""
    if mesh is None:
        return None
    d = mesh.devices.size
    if d <= 1:
        return None
    axis = mesh.axis_names[0]
    return {
        "state": NamedSharding(mesh, P(axis, None)),
        "ndev": d,
    }


def promql_row_shardings(mesh, n: int):
    """NamedShardings for the resident PromQL sort-layout arrays
    (promql/engine.py _build_sort_layout) and padded selection vectors:
    the leading axis — (tsid, ts)-sorted rows, or the pow2-padded selected
    series — splits across the mesh so the per-eval window kernels
    (searchsorted boundaries, reset-adjusted cumsums, segment folds) run
    SPMD under GSPMD with XLA-inserted collectives, mirroring
    bucket_major_shardings for the SQL aligned-window path.  Returns None
    when the axis does not tile the mesh (caller keeps the replicated
    placement)."""
    if mesh is None:
        return None
    d = mesh.devices.size
    if d <= 1 or n % d != 0:
        return None
    axis = mesh.axis_names[0]
    return {"rows": NamedSharding(mesh, P(axis))}


# key spec: ("tag", column, card) | ("time", ts_column, step, start, nbuckets)
# agg spec: (output_name, op, column) with op in sum/count/min/max/mean
_MERGE = {
    "sum": lambda x, ax: jax.lax.psum(x, ax),
    "count": lambda x, ax: jax.lax.psum(x, ax),
    "min": lambda x, ax: jax.lax.pmin(x, ax),
    "max": lambda x, ax: jax.lax.pmax(x, ax),
}


class DistAggExecutor:
    """Sharded dense-grid group-by: local segment partials + ICI collectives.

    The single-device twin lives in query/physical.py; this one runs the
    same math under shard_map so each device only touches its own rows.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._cache: dict[tuple, object] = {}

    def aggregate(
        self,
        table: ShardedTable,
        key_specs: list[tuple],
        agg_specs: list[tuple],
        *,
        ts_column: str | None = None,
        where_fn=None,
        where_cols: tuple = (),
        where_key=None,
        time_range: tuple = (None, None),
    ) -> dict[str, np.ndarray]:
        """``agg_specs``: (out, op, col) with op in sum/count/min/max/mean
        plus first/last (value at extreme ``ts_column``).  ``where_fn``
        (compiled over ``where_cols``) and ``time_range`` filter rows
        inside the shard — the pushed-down WHERE of the partial plan."""
        cards = []
        for spec in key_specs:
            if spec[0] == "tag":
                cards.append(int(spec[2]))
            elif spec[0] == "time":
                cards.append(int(spec[4]))
            else:
                raise Unsupported(f"dist key {spec[0]}")
        grid = 1
        for c in cards:
            grid *= c
        tr_flags = (time_range[0] is not None, time_range[1] is not None)
        # rolling windows must reuse one compiled kernel: the range bounds
        # are TRACED arguments; the WHERE keys by its expression text (a
        # fresh compile_device closure per query must still cache-hit)
        key = (tuple(key_specs), tuple(agg_specs), grid,
               table.rows_per_shard, ts_column, where_key, tr_flags)
        kern = self._cache.get(key)
        jit_miss = kern is None
        if kern is None:
            kern = self._build(key_specs, agg_specs, cards, grid,
                               ts_column, where_fn, where_cols, tr_flags)
            self._cache[key] = kern
        names = self._col_names(key_specs, agg_specs, ts_column, where_cols)
        args = [table.columns[n] for n in names]
        lo = np.int64(time_range[0] if time_range[0] is not None else 0)
        hi = np.int64(time_range[1] if time_range[1] is not None else 0)
        # attribute device time to the collective exchange: the shard_map
        # program IS the collective phase of the query (local partials +
        # XLA-inserted psum/pmin/pmax over ICI), so its wall time — split
        # compile vs steady-state like the single-device kernels — lands
        # in the registry and, under a tracer, in a "collectives" span
        import time as _time

        t0 = _time.perf_counter()
        with TRACER.stage("collectives", devices=self.mesh.devices.size,
                          phase="compile" if jit_miss else "execute"):
            out = kern(table.row_mask, lo, hi, *args)
            out = {k: np.asarray(v) for k, v in out.items()}
        M_MESH_COLLECTIVE.labels(
            str(self.mesh.devices.size),
            "compile" if jit_miss else "execute",
        ).observe(_time.perf_counter() - t0)
        return out

    @staticmethod
    def _col_names(key_specs, agg_specs, ts_column=None, where_cols=()):
        names = ({s[2] for s in agg_specs if s[2]}
                 | {s[1] for s in key_specs if s[0] == "tag"}
                 | {s[1] for s in key_specs if s[0] == "time"}
                 | set(where_cols))
        if ts_column:  # first/last picks and the time-range filter
            names.add(ts_column)
        return sorted(names)

    def _build(self, key_specs, agg_specs, cards, grid, ts_column=None,
               where_fn=None, where_cols=(), tr_flags=(False, False)):
        names = self._col_names(key_specs, agg_specs, ts_column, where_cols)
        name_idx = {n: i for i, n in enumerate(names)}
        mesh = self.mesh

        i64 = jnp.iinfo(jnp.int64)

        def local(mask, lo, hi, *cols):
            env = {n: cols[name_idx[n]] for n in names}
            # pushed-down filters (the partial plan's WHERE + time range;
            # lo/hi are traced so rolling windows share one kernel)
            if where_fn is not None:
                mask = mask & jnp.broadcast_to(where_fn(env), mask.shape)
            if ts_column is not None and any(tr_flags):
                ts_arr = env[ts_column]
                if tr_flags[0]:
                    mask = mask & (ts_arr >= lo)
                if tr_flags[1]:
                    mask = mask & (ts_arr < hi)
            codes = []
            for spec in key_specs:
                if spec[0] == "tag":
                    codes.append(env[spec[1]].astype(jnp.int64))
                else:
                    _kind, ts_col, step, start, nb = spec
                    codes.append(bucket_index(env[ts_col], step, start))
            if codes:
                gid, _tot = combine_keys(codes, cards)
            else:  # global aggregate: every row in the one group
                gid = jnp.zeros(mask.shape, dtype=jnp.int64)
            valid = mask & (gid >= 0)
            ids = jnp.where(valid, gid, grid).astype(jnp.int32)
            ns = grid + 1
            out = {}
            cnt_cache: dict[str, jnp.ndarray] = {}

            def count_of(col_name, v, m):
                c = cnt_cache.get(col_name)
                if c is None:
                    c = jax.ops.segment_sum(
                        m.astype(jnp.int64), ids, num_segments=ns
                    )[:grid]
                    c = jax.lax.psum(c, SHARD_AXIS)
                    cnt_cache[col_name] = c
                return c

            # sketch specs carry a 4th config element: (alias, "udd", col,
            # (gamma, bucket_limit))
            spec_extra = {s[0]: s[3] for s in agg_specs if len(s) > 3}
            for spec_t in agg_specs:
                out_name, op, col = spec_t[0], spec_t[1], spec_t[2]
                if op == "count":
                    v = env[col] if col else jnp.zeros(mask.shape, jnp.float32)
                    m = valid & (
                        ~jnp.isnan(v) if col and jnp.issubdtype(v.dtype, jnp.floating)
                        else jnp.ones(mask.shape, bool)
                    )
                    out[out_name] = count_of(col or "*", v, m)
                    continue
                v = env[col]
                is_f = jnp.issubdtype(v.dtype, jnp.floating)
                m = valid & (~jnp.isnan(v) if is_f else jnp.ones(mask.shape, bool))
                if op == "sum" and not is_f:
                    # int64 totals stay int64-exact (a NaN fill would
                    # promote to float and lose precision above 2^53,
                    # diverging from single-device segment_reduce);
                    # empty groups are NULLed host-side via the count,
                    # matching physical.py's __cnt_all__ convention
                    part = jax.ops.segment_sum(
                        jnp.where(m, v.astype(jnp.int64), 0), ids,
                        num_segments=ns,
                    )[:grid]
                    out[out_name] = jax.lax.psum(part, SHARD_AXIS)
                elif op in ("sum", "mean"):
                    part = jax.ops.segment_sum(
                        jnp.where(m, v, 0).astype(jnp.float32), ids, num_segments=ns
                    )[:grid]
                    total = jax.lax.psum(part, SHARD_AXIS)
                    if op == "sum":
                        # all-NULL groups: SUM is NULL, not 0 (matches
                        # the single-device segment_reduce semantics)
                        cnt = count_of(col, v, m)
                        out[out_name] = jnp.where(cnt > 0, total, jnp.nan)
                    else:
                        cnt = count_of(col, v, m)
                        out[out_name] = jnp.where(
                            cnt > 0, total / jnp.maximum(cnt, 1), jnp.nan
                        )
                elif op in ("min", "max"):
                    fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
                    if is_f:
                        fill = jnp.inf if op == "min" else -jnp.inf
                        vv = jnp.where(m, v, fill).astype(jnp.float32)
                    else:
                        # int64 stays exact: pick-pair companion
                        # timestamps (min(ts)/max(ts)) merge bit-exact,
                        # matching the Flight path's int semantics
                        fill = i64.max if op == "min" else i64.min
                        vv = jnp.where(m, v.astype(jnp.int64), fill)
                    part = fn(vv, ids, num_segments=ns)[:grid]
                    merged = _MERGE[op](part, SHARD_AXIS)
                    cnt = count_of(col, v, m)
                    if is_f:
                        out[out_name] = jnp.where(cnt > 0, merged, jnp.nan)
                    else:
                        out[out_name] = jnp.where(cnt > 0, merged, 0)
                elif op == "hll":
                    # HLL registers are a commutative max-fold: local
                    # [grid, M] register grid, then ONE pmax over ICI —
                    # the sketch IS the exchange format (ops/sketch.py)
                    from greptimedb_tpu.ops.sketch import hll_fold

                    regs = hll_fold(v, ids, grid, m)
                    out[out_name] = jax.lax.pmax(regs, SHARD_AXIS)
                elif op == "udd":
                    # UDDSketch needs the GLOBAL per-group key span to pick
                    # one collapse factor before bucketing, so the fold
                    # interleaves collectives: pmin/pmax the key extremes,
                    # then the SHARED bucketing (ops/sketch.py
                    # udd_bucket_counts — one definition of the collapse
                    # convention) and a psum of the counts
                    from greptimedb_tpu.ops.sketch import (
                        udd_bucket_counts, udd_key_extremes, udd_keys,
                    )

                    gamma, nb = spec_extra[out_name]
                    kk, okm = udd_keys(v, m, gamma)
                    kmin_l, kmax_l = udd_key_extremes(kk, okm, gid, grid)
                    kmin_g = jax.lax.pmin(kmin_l, SHARD_AXIS)
                    kmax_g = jax.lax.pmax(kmax_l, SHARD_AXIS)
                    cnts, cc = udd_bucket_counts(
                        kk, okm, gid, grid, nb, kmin_g, kmax_g)
                    cnts = jax.lax.psum(cnts, SHARD_AXIS)
                    out[out_name] = jnp.concatenate(
                        [cnts, kmin_g[:, None], cc[:, None]], axis=1)
                elif op in ("first", "last"):
                    # value at the extreme timestamp: local pick, then a
                    # ts-extreme collective and a winner-selection pmax —
                    # the mesh twin of rpc/partial.py's pick-pair merge
                    from greptimedb_tpu.ops.segment import (
                        segment_first_last,
                    )

                    vv = (v if is_f
                          else v.astype(jnp.int64))  # ints stay exact
                    ext_ts, val = segment_first_last(
                        env[ts_column], vv, ids, grid,
                        m, last=(op == "last"),
                    )
                    local_has = jax.ops.segment_sum(
                        m.astype(jnp.int32), ids, num_segments=ns
                    )[:grid] > 0
                    if op == "last":
                        sent = jnp.where(local_has, ext_ts, i64.min)
                        g_ts = jax.lax.pmax(sent, SHARD_AXIS)
                    else:
                        sent = jnp.where(local_has, ext_ts, i64.max)
                        g_ts = jax.lax.pmin(sent, SHARD_AXIS)
                    win = local_has & (sent == g_ts)
                    cand_fill = -jnp.inf if is_f else i64.min
                    merged = jax.lax.pmax(
                        jnp.where(win, val, cand_fill), SHARD_AXIS
                    )
                    cnt = count_of(col, v, m)
                    out[out_name] = jnp.where(
                        cnt > 0, merged, jnp.nan if is_f else 0
                    )
                else:
                    raise Unsupported(f"dist agg {op}")
            out["__count__"] = count_of(
                "*", jnp.zeros(mask.shape, jnp.float32),
                valid,
            )
            return out

        smapped = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(), P()) + (P(SHARD_AXIS),) * len(names),
            out_specs=P(),
        )
        return jax.jit(smapped)


def execute_select_on_mesh(
    executor: DistAggExecutor,
    table: ShardedTable,
    sel,
    ctx,
    ts_bounds: tuple[int, int],
):
    """Run a partial-decomposable Select on the mesh executor, finished by
    the SHARED merge definition (rpc/partial.py merge_partials) — ONE
    commutativity split for both the cross-process Flight exchange and
    the ICI collective exchange (round-3 verdict #7; reference
    src/query/src/dist_plan/commutativity.rs:116).

    Returns (column_names, rows) unordered, or None when the query is not
    mesh-decomposable (caller falls back to single-device / SQL text).
    Expr group keys are supported when they reference tag columns only:
    the mesh aggregates at (tag-combo x bucket) granularity and the host
    fold through merge_partials collapses combos sharing one expr value.
    """
    from greptimedb_tpu.query.ast import Column, Star
    from greptimedb_tpu.query.exprs import compile_device, eval_host
    from greptimedb_tpu.query.planner import plan_select, referenced_columns
    from greptimedb_tpu.rpc.partial import merge_partials, split_partial

    ts_name = (ctx.schema.time_index.name
               if ctx.schema.time_index is not None else None)
    if ts_bounds is None:  # empty region (ts_bounds() -> None)
        ts_bounds = (0, 0)
    pplan = split_partial(sel, ts_column=ts_name)
    if pplan is None:
        return None
    psel = pplan.partial_select
    try:
        plan = plan_select(sel, ctx)
    except Exception:  # noqa: BLE001 — planner rejection = not mesh-able
        return None
    gk_by_str = {str(k.expr): k for k in plan.group_keys}
    tag_names = {c.name for c in ctx.schema.tag_columns}

    ops_map = {"sum": "sum", "count": "count", "min": "min", "max": "max",
               "first_value": "first", "last_value": "last"}
    tag_cols: list[str] = []
    time_spec = None
    key_exprs: list[tuple] = []  # (alias, expr, kind, extra)
    agg_specs: list[tuple] = []
    for it in psel.items:
        alias = it.alias
        if alias in pplan.key_cols:
            gk = gk_by_str.get(str(it.expr))
            if gk is None:
                return None
            if gk.kind == "tag":
                if gk.column not in tag_cols:
                    tag_cols.append(gk.column)
                key_exprs.append((alias, it.expr, "tag", gk.column))
            elif gk.kind == "time":
                if time_spec is not None or ts_name is None:
                    return None  # one time key on the dense bucket axis
                lo, hi = plan.time_range
                data_lo, data_hi = ts_bounds
                lo = data_lo if lo is None else max(lo, data_lo)
                hi = data_hi + 1 if hi is None else min(hi, data_hi + 1)
                if hi <= lo:
                    hi = lo + 1
                step = gk.step or 1
                start = gk.origin + ((lo - gk.origin) // step) * step
                nb = max(1, -(-(hi - start) // step))
                time_spec = (ts_name, step, start, nb)
                key_exprs.append((alias, it.expr, "time", None))
            else:
                refs: set = set()
                referenced_columns(it.expr, ctx, refs)
                if not refs <= tag_names:
                    return None  # field-expr keys: no dense bound
                for c in sorted(refs):
                    if c not in tag_cols:
                        tag_cols.append(c)
                key_exprs.append((alias, it.expr, "expr", tuple(sorted(refs))))
        else:
            fc = it.expr
            fname = getattr(fc, "name", None)
            # sketch partials (split_partial's _SKETCH_PARTIALS): the mesh
            # folds HLL registers / UDD buckets with collectives and the
            # host fold serializes states for the shared merge
            if fname == "hll":
                if (len(fc.args) != 1
                        or not isinstance(fc.args[0], Column)):
                    return None
                col = ctx.resolve(fc.args[0].name)
                if col in tag_names:
                    return None
                agg_specs.append((alias, "hll", col))
                continue
            if fname == "uddsketch_state":
                from greptimedb_tpu.ops.sketch import udd_gamma
                from greptimedb_tpu.query.ast import Literal as _Lit

                if (len(fc.args) != 3
                        or not isinstance(fc.args[0], _Lit)
                        or not isinstance(fc.args[1], _Lit)
                        or not isinstance(fc.args[2], Column)):
                    return None
                try:
                    # SAME clamp as physical.py _compile_sketch_agg: mesh
                    # and single-device states must carry identical
                    # (γ, nb) configs or merge_udd_states refuses them
                    nb = max(8, min(int(fc.args[0].value), 4096))
                    gamma = udd_gamma(float(fc.args[1].value))
                except (ValueError, TypeError):
                    return None  # single-device path raises the PlanError
                col = ctx.resolve(fc.args[2].name)
                if col in tag_names:
                    return None
                agg_specs.append((alias, "udd", col, (gamma, nb)))
                continue
            op = ops_map.get(fname)
            if op is None:
                return None
            if not fc.args or isinstance(fc.args[0], Star):
                col = None
                if op != "count":
                    return None
            elif isinstance(fc.args[0], Column):
                col = ctx.resolve(fc.args[0].name)
                if col in tag_names:
                    # aggregating a dictionary-encoded tag would emit raw
                    # codes (same guard as query/physical.py:805-811)
                    return None
            else:
                return None  # computed agg args: single-device path
            agg_specs.append((alias, op, col))

    cards = [max(len(ctx.encoders[c]), 1) for c in tag_cols]
    key_specs: list[tuple] = [
        ("tag", c, card) for c, card in zip(tag_cols, cards)
    ]
    if time_spec is not None:
        key_specs.append(("time",) + time_spec)
        cards.append(time_spec[3])
    from greptimedb_tpu.query.physical import DENSE_LIMIT

    total_groups = 1
    for c in cards:
        total_groups *= c
    if total_groups > DENSE_LIMIT:
        # same cap as the single-device dense path (physical.py): an
        # unbounded bucket grid (e.g. GROUP BY raw ts, step=1) would
        # allocate [grid]-sized buffers per aggregate
        return None

    where_fn, where_cols = None, ()
    if plan.where is not None:
        refs = set()
        referenced_columns(plan.where, ctx, refs)
        try:
            where_fn = compile_device(plan.where, ctx)
        except Exception:  # noqa: BLE001
            return None
        where_cols = tuple(ctx.resolve(c) for c in sorted(refs))
    needs_ts = (
        ts_name is not None
        and (plan.time_range != (None, None)
             or any(s[1] in ("first", "last") for s in agg_specs))
    )
    needed = executor._col_names(
        key_specs, agg_specs, ts_name if needs_ts else None, where_cols)
    if not set(needed) <= set(table.columns):
        return None  # e.g. string FIELD columns dropped by shard_region
    # the where closure bakes dictionary codes at compile time, so the
    # kernel cache must key on (table, expr text, dictionary versions) —
    # a new tag value recompiles instead of hitting a stale predicate
    dict_ver = tuple(
        len(ctx.encoders[c.name]) for c in ctx.schema.tag_columns)
    out = executor.aggregate(
        table, key_specs, agg_specs,
        ts_column=ts_name if needs_ts else None,
        where_fn=where_fn, where_cols=where_cols,
        where_key=(sel.table, str(plan.where), dict_ver)
        if plan.where is not None else (sel.table, None, dict_ver),
        time_range=plan.time_range,
    )

    # ---- host fold through the shared merge ---------------------------
    cnt = out["__count__"]
    keep = np.nonzero(cnt > 0)[0]
    if not key_exprs and len(keep) == 0:
        # SQL: a global aggregate returns exactly one row even when zero
        # rows matched (count()=0, other aggregates NULL) — same special
        # case as the single-device kernel (query/physical.py)
        part0: dict[str, list] = {}
        for spec_t in agg_specs:
            part0[spec_t[0]] = [0 if spec_t[1] == "count" else None]
        return merge_partials(pplan, [part0])
    comps = (np.unravel_index(keep, tuple(cards)) if cards
             else (np.zeros(len(keep), dtype=np.int64),))
    env_host: dict[str, np.ndarray] = {}
    for i, c in enumerate(tag_cols):
        decoded = np.asarray(ctx.encoders[c].values(), dtype=object)
        env_host[c] = decoded[comps[i]]
    part: dict[str, list] = {}
    for alias, expr, kind, extra in key_exprs:
        if kind == "tag":
            part[alias] = env_host[extra].tolist()
        elif kind == "time":
            _tsn, step, start, _nb = time_spec
            part[alias] = (start + comps[-1].astype(np.int64) * step).tolist()
        else:
            v = eval_host(expr, dict(env_host), len(keep))
            arr = np.asarray(v, dtype=object)
            if arr.ndim == 0:
                arr = np.full(len(keep), arr.item(), dtype=object)
            part[alias] = arr.tolist()
    for spec_t in agg_specs:
        alias, aop = spec_t[0], spec_t[1]
        vals = np.asarray(out[alias])[keep]
        if aop == "hll":
            from greptimedb_tpu.ops import sketch as sk

            part[alias] = [sk.encode_hll(r) for r in vals]
        elif aop == "udd":
            from greptimedb_tpu.ops import sketch as sk

            gamma, nb = spec_t[3]
            part[alias] = [sk.encode_udd(r, gamma, nb) for r in vals]
        elif vals.dtype.kind == "f":
            part[alias] = [None if v != v else float(v) for v in vals]
        else:
            part[alias] = vals.tolist()
    return merge_partials(pplan, [part])


def shard_region(region, mesh, ts_range: tuple = (None, None)) -> ShardedTable:
    """ShardedTable from a region's host scan, tags dictionary-encoded to
    device codes (the convention compile_device expects).  String FIELD
    columns are dropped — the mesh aggregates numerics; a query touching
    them is not mesh-decomposable anyway."""
    cols = region.scan_host(ts_range)
    tagset = {c.name for c in region.schema.tag_columns}
    out: dict[str, np.ndarray] = {}
    for name, arr in cols.items():
        if name in tagset and arr.dtype.kind in ("O", "U", "S"):
            out[name] = region.encoders[name].encode(arr).astype(np.int32)
        elif arr.dtype.kind == "O":
            continue
        else:
            out[name] = arr
    return shard_table(out, mesh)
