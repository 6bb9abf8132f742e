"""Physical execution: SelectPlan → jitted XLA kernel → host result columns.

The TPU replacement for DataFusion's physical operators (SURVEY.md §7.1
"physical plan = XLA computation"): one fused jit program per (plan
fingerprint, shape class) computes WHERE mask → group ids → segment
aggregates entirely on device; the host then shapes the (small) result:
decode tag codes, HAVING, ORDER BY, LIMIT, final projections.

Group-by strategies (ops/segment.py): dense key grid when every key is a
tag or time bucket and the grid fits; otherwise iterative sort-ranking,
collision-free, still static-shape.
"""

from __future__ import annotations

import dataclasses as _dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from greptimedb_tpu.errors import ExecutionError, PlanError, Unsupported
from greptimedb_tpu.ops.masks import compact_rows, valid_mask
from greptimedb_tpu.ops.segment import (
    combine_keys, compact_groups, segment_distinct_count, segment_first_last,
    segment_reduce, segmented_sum_scan, sorted_segment_reduce,
)
from greptimedb_tpu.compile import named_jit
from greptimedb_tpu.ops.time import bucket_index
from greptimedb_tpu.query.ast import Column, Expr, FuncCall, Star
from greptimedb_tpu.query.exprs import compile_device, eval_host
from greptimedb_tpu.query.planner import GroupKey, SelectPlan, referenced_columns
from greptimedb_tpu.storage.cache import DeviceTable
from greptimedb_tpu.storage.memtable import TSID
from greptimedb_tpu.utils.telemetry import REGISTRY
from greptimedb_tpu.utils.tracing import TRACER

DENSE_LIMIT = 1 << 22

# diagnostics: counts every aggregate dispatch (including kernel-cache
# hits) by which segment strategy it used; tests assert coverage.
# "grid_bm" counts grid dispatches served from the resident bucket-major
# derived layout (a subset of "grid").  "dispatches" counts every
# timed_kernel_call — the per-query twin is metrics["device_dispatches"],
# which EXPLAIN ANALYZE surfaces so the whole-plan-fusion contract (ONE
# device dispatch per warm query class) is pinned, not assumed.
DISPATCH_STATS = {"sorted": 0, "scatter": 0, "grid": 0, "grid_bm": 0,
                  "grid_batch": 0, "dispatches": 0}


@_dataclasses.dataclass
class _GridGeom:
    """Plan→grid geometry produced by Executor._grid_prologue: everything
    the grid kernels need beyond the plan itself.  Shared by the solo
    path and the cross-query stacked dispatch so window math has exactly
    one definition."""

    specs: list
    where_fn: object
    where_series: bool
    ts_name: str
    tag_keys: list
    has_time: bool
    r: int
    pad_left: int
    nb: int
    nbw: int
    w_raw: int
    pad_l: int
    pad_r: int
    step_q: int
    bts0: int
    b_lo: int
    s0: int
    aligned: bool
    lo: int | None
    hi: int | None
    cards_tag: list
    ngt: int
    dict_ver: tuple
    tag_order: tuple

_GRID_OPS = {"avg": "mean", "mean": "mean", "sum": "sum", "count": "count",
             "min": "min", "max": "max"}


def timed_kernel_call(call, miss: bool, metrics: dict | None):
    """Invoke a compiled kernel with device-phase accounting
    (arXiv:2203.01877's planning/compile/execute separation).

    The compile phase (jit-cache ``miss``) is always its own stage,
    ``xla_compile`` — it happens once per kernel class and its cost
    dwarfs the timer.  The steady-state device wait is split out here,
    as stage ``device_execute``, only for a caller that asked for the
    split (``metrics``: EXPLAIN ANALYZE, the slow-query sink); otherwise
    the dispatch stays async and the wait is ``fetch_host``'s
    ``device_wait``.  The tracer never adds a sync: a traced run must
    do what an untraced one does.
    """
    DISPATCH_STATS["dispatches"] += 1
    if metrics is not None:
        metrics["device_dispatches"] = metrics.get("device_dispatches", 0) + 1
    if miss:
        with TRACER.stage("xla_compile") as st:
            out = call()
        if metrics is not None:
            metrics["jit_cache"] = "miss"
            metrics["xla_build_ms"] = round(st.seconds * 1000, 3)
    else:
        out = call()
        if metrics is not None:
            metrics["jit_cache"] = "hit"
    if metrics is not None:
        with TRACER.stage("device_execute") as st:
            out = jax.block_until_ready(out)
        metrics["device_wait_ms"] = round(
            metrics.get("device_wait_ms", 0.0) + st.seconds * 1000, 3)
    return out


def fetch_host(out: dict) -> dict:
    """THE one place a kernel's result leaves the device: wait for it,
    then copy every output to numpy.  The executor's ``execute*``
    methods stop before it and hand back ``(out, finish)``, so that the
    wait is a stage of its own, ``device_wait``, beside ``execute`` (the
    host side of the dispatch) and ``materialize`` (``finish`` and the
    result rows) and inside neither."""
    with TRACER.stage("device_wait"):
        # gl: allow[GL-H001] -- THE one host materialization per dispatch; finish() operates on these numpy arrays
        return {k: np.asarray(v)
                for k, v in jax.block_until_ready(out).items()}


def aot_kernel_call(kernel, call, miss: bool, metrics: dict | None):
    """timed_kernel_call for compiler-routed kernels: an AOT-store hit
    (compile/service.py) skips XLA compilation entirely, so its first
    invocation must not be timed — or reported — as a compile."""
    aot = miss and getattr(kernel, "aot", False)
    out = timed_kernel_call(call, miss and not aot, metrics)
    if aot and metrics is not None:
        metrics["jit_cache"] = "aot"
    return out


def aligned_layout_eligible(specs, aligned, has_time, where_fn,
                            where_series) -> bool:
    """Eligibility for the resident bucket-major layout (see
    Executor._aligned_layout): a bucket-aligned time window, a WHERE that
    is absent or tag-only, and aggregates that reduce to plain per-bucket
    sums/counts over finite stored columns."""
    return bool(
        aligned
        and has_time
        and (where_fn is None or where_series)
        and all(
            (op == "count" and (fn is None or nn))
            or (op in ("sum", "mean") and nn and ci is not None)
            for _name, op, fn, nn, ci in specs
        )
    )


def grid_plan_candidate(plan) -> bool:
    """Cheap pre-build eligibility for the dense-grid executor: structure
    and referenced columns only (grid step/shape checks need the built
    grid and happen in execute_grid).  Called BEFORE the provider builds a
    grid, so an obviously ineligible plan never pays the build."""
    from greptimedb_tpu.storage.grid import grid_float_fields

    ctx = plan.ctx
    if not plan.is_agg:
        return False
    time_keys = 0
    for k in plan.group_keys:
        if k.kind == "time":
            time_keys += 1
        elif k.kind != "tag":
            return False
    if time_keys > 1:
        return False
    ts = ctx.schema.time_index
    if ts is None:
        return False
    gridcols = set(grid_float_fields(ctx.schema))
    tags = {c.name for c in ctx.schema.tag_columns}
    ok_refs = gridcols | tags | {ts.name}
    for agg in plan.aggs:
        op = _GRID_OPS.get(agg.name)
        if op is None or agg.distinct:
            return False
        if not agg.args or isinstance(agg.args[0], Star):
            if agg.name != "count":
                return False
            continue
        if len(agg.args) > 1:
            return False
        refs: set = set()
        try:
            referenced_columns(agg.args[0], ctx, refs)
        except Exception:  # noqa: BLE001
            return False
        # tag refs inside numeric aggregates would aggregate dictionary
        # codes; the row path rejects them too — fall back for parity
        if not refs <= ok_refs or (refs & tags):
            return False
    if plan.where is not None:
        refs = set()
        try:
            referenced_columns(plan.where, ctx, refs)
        except Exception:  # noqa: BLE001
            return False
        if not refs <= ok_refs:
            return False
    return True

_I64_MAX = np.int64(np.iinfo(np.int64).max)
_I64_MIN = np.int64(np.iinfo(np.int64).min)


def _vec_fingerprint(plan, table) -> int:
    """Vector-search and full-text kernels bake dictionary-derived
    constants into the compiled program — key them on the table's
    monotonic dicts_version (O(1)) so a rebuilt/extended table never
    reuses a kernel compiled against stale dictionaries."""
    fp = plan.fingerprint()
    if ("vec_" not in fp and "matches" not in fp and "_merge" not in fp
            and "'" not in fp):
        # the quote check is conservative: ANY string literal in the plan
        # may have compiled against a string-FIELD dictionary (LIKE/=
        # over table_dicts) — version-key those too
        return 0
    return getattr(table, "dicts_version", 0)


def decode_codes(values: list, raw: np.ndarray, null=None) -> np.ndarray:
    """Dictionary codes → values (object array); out-of-range/poisoned
    codes become ``null``.  The one decode path for tag and string-field
    group keys."""
    lookup = np.array(list(values) + [null], dtype=object)
    codes = raw.astype(np.int64)
    codes = np.where((codes < 0) | (codes >= len(values)), len(values), codes)
    return lookup[codes]


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def _series_group_ids(tag_codes, tag_cols, cards_tag, ngt, spad):
    """Series → dense tag-group ids, poison codes (-1 pads, unknown)
    routed to the overflow segment ``ngt``.  The ONE routing shared by
    the dynamic-slice and bucket-major grid kernels so the two layouts
    can never disagree on grouping."""
    if tag_cols:
        codes = [tag_codes[c] for c in tag_cols]
        gid_s, _tot = combine_keys(codes, cards_tag)
    else:
        gid_s = jnp.zeros(spad, dtype=jnp.int64)
    return jnp.where(
        (gid_s >= 0) & (gid_s < ngt), gid_s, ngt
    ).astype(jnp.int32)


def _grid_key_outputs(tag_cols, cards_tag, ngt, nb, bts0, step_q, has_time):
    """__comps__/__bts__ materialization: arithmetic decomposition over
    the (tags…, bucket) grid — replicated, no gather.  Shared by both
    grid kernels (one definition of the flatten order)."""
    from greptimedb_tpu.ops.segment import decompose_keys

    ng = ngt * nb
    comps = decompose_keys(
        jnp.arange(ng, dtype=jnp.int64), list(cards_tag) + [nb]
    )
    out = {
        "__comps__": jnp.stack(comps[:-1]) if tag_cols else (
            jnp.zeros((0, ng), dtype=jnp.int32)
        ),
    }
    if has_time:
        out["__bts__"] = bts0 + comps[-1].astype(jnp.int64) * step_q
    return out


class Executor:
    """Caches jitted kernels by (fingerprint, shape-class) keys."""

    def __init__(self):
        self._cache: dict[tuple, object] = {}
        # decoded sketch-merge vocab matrices by (agg, column, dicts
        # version): repeat queries must not re-decode/re-upload thousands
        # of stored states per execution
        self._sketch_cache: dict[tuple, object] = {}
        # resident bucket-major partials per (region, step class): the
        # aligned-window range path reuses them across warm queries
        # instead of re-running the dynamic-slice window copy + gemv
        from greptimedb_tpu.storage.cache import DerivedLayoutCache

        self.layout_cache = DerivedLayoutCache()
        # resident fulltext fingerprint matrices + verified-vocabulary
        # memos (fulltext/resident.py): text predicates over dictionary-
        # encoded columns prefilter on device and verify only candidates
        from greptimedb_tpu.fulltext.resident import FulltextIndexCache

        self.fulltext_cache = FulltextIndexCache()
        # query-compiler subsystem (compile/): every kernel-cache miss
        # below routes through it — shape-class classification + usage
        # journal always; persistent AOT load/persist once the server
        # configures a store (standalone.py).  Unconfigured it is
        # memory-only and adds one dict/hash per BUILD (never per query).
        from greptimedb_tpu.compile.service import PlanCompiler

        self.compiler = PlanCompiler()

    def _fulltext_provider(self, plan, table):
        """ctx.fulltext for one execution, or None (knob off / table
        without dictionary lineage) — the compiler then walks
        dictionaries host-side exactly as before."""
        from greptimedb_tpu.fulltext import enabled
        from greptimedb_tpu.fulltext.resident import FulltextProvider

        if not enabled() or getattr(table, "dicts_root", 0) == 0:
            return None
        return FulltextProvider(self.fulltext_cache,
                                getattr(plan, "table", None) or "?", table)

    # ------------------------------------------------------------------
    def execute(
        self,
        plan: SelectPlan,
        table: DeviceTable,
        ts_bounds: tuple[int, int],
        metrics: dict | None = None,
    ) -> tuple[dict, object]:
        """Dispatch the device part.  Returns ``(out, finish)``: the
        kernel's result, still on the device, and the function that
        turns ``fetch_host(out)`` into (host env of result columns,
        nrows)."""
        if plan.is_agg:
            return self._execute_agg(plan, table, ts_bounds, metrics=metrics)
        return self._execute_raw(plan, table)

    # ---- aggregate path ----------------------------------------------
    def _time_key_params(
        self, key: GroupKey, plan: SelectPlan, ts_bounds: tuple[int, int]
    ) -> tuple[int, int, int]:
        lo, hi = plan.time_range
        data_lo, data_hi = ts_bounds
        lo = data_lo if lo is None else max(lo, data_lo)
        hi = data_hi + 1 if hi is None else min(hi, data_hi + 1)
        if hi <= lo:
            hi = lo + 1
        step = key.step or 1
        origin = key.origin
        start = origin + ((lo - origin) // step) * step
        nb = max(1, -(-(hi - start) // step))
        return step, start, _pow2(nb)

    def _execute_agg(  # gl: warm-path
        self, plan: SelectPlan, table: DeviceTable,
        ts_bounds: tuple[int, int], metrics: dict | None = None,
    ) -> tuple[dict, object]:
        ctx = plan.ctx
        ctx.table_dicts = table.dicts  # vector search / string-dict exprs
        ctx.table_dicts_version = getattr(table, "dicts_version", 0)
        ctx.fulltext = self._fulltext_provider(plan, table)
        ctx.sketch_table = plan.table
        ts_name = ctx.schema.time_index.name if ctx.schema.time_index else None

        key_specs: list[tuple] = []
        dense_ok = True
        cards: list[int] = []
        for k in plan.group_keys:
            if k.kind == "tag":
                card = _pow2(max(len(ctx.encoders[k.column]), 1))
                key_specs.append(("tag", k.column, card))
                cards.append(card)
            elif k.kind == "time":
                step, start, nb = self._time_key_params(k, plan, ts_bounds)
                key_specs.append(("time", (step, start, nb)))
                cards.append(nb)
            else:
                key_specs.append(("expr", compile_device(k.expr, ctx)))
                dense_ok = False
        grid = 1
        for c in cards:
            grid *= c
        if key_specs and (not dense_ok or grid > DENSE_LIMIT):
            dense_ok = False

        # sorted fast path (scatter-free reductions): exactly one tag key,
        # whose codes are monotone+bijective with series runs in the resident
        # layout, plus only time keys — then the row-major (tag, time...)
        # combined id is nondecreasing in row order
        tag_keys = [s for s in key_specs if s[0] == "tag"]
        time_keys = [s for s in key_specs if s[0] == "time"]
        sorted_eligible = bool(
            dense_ok
            and key_specs
            and len(tag_keys) <= 1
            and len(tag_keys) + len(time_keys) == len(key_specs)
            and all(s[1] in getattr(table, "sorted_tags", ()) for s in tag_keys)
        )
        if sorted_eligible and not tag_keys and len(ctx.schema.tag_columns) > 0:
            # pure time bucketing over multi-series data: ts not globally
            # sorted across series — scatter path
            sorted_eligible = False
        # GREPTIME_SORTED_SEGMENTS: auto (default) takes the scatter form
        # on EVERY backend.  The sorted form's associative scan is what
        # the TPU compiler cannot build at table size (ROADMAP A5 has the
        # compile seconds), so no backend is handed it unasked; "force"
        # keeps it reachable for A/B runs and the tests of its kernels,
        # "off" pins scatter.
        mode = os.environ.get("GREPTIME_SORTED_SEGMENTS", "auto")
        if mode == "force":
            use_sorted = sorted_eligible
        elif mode in ("auto", "off"):
            use_sorted = False
        else:
            raise PlanError(
                f"GREPTIME_SORTED_SEGMENTS must be auto|force|off, got {mode!r}"
            )
        DISPATCH_STATS["sorted" if use_sorted else "scatter"] += 1

        where_fn = compile_device(plan.where, ctx) if plan.where is not None else None
        lo, hi = plan.time_range

        seg_fn = sorted_segment_reduce if use_sorted else segment_reduce
        # batchable aggregates (sum/avg/count over plain float columns)
        # compute in ONE wide [N, C] segment pass instead of C narrow ones —
        # the TSBS double-groupby runs 10 avg() columns, so this cuts the
        # dominant scatter/cumsum passes ~10x
        batched: list[tuple[str, str, str]] = []  # (out_name, op, column)
        agg_specs = []
        sketch_codecs: dict[str, tuple] = {}
        for agg in plan.aggs:
            op = {"avg": "mean", "mean": "mean", "sum": "sum",
                  "count": "count"}.get(agg.name)
            col = None
            if (
                op is not None
                and not agg.distinct
                and len(agg.args) == 1
                and isinstance(agg.args[0], Column)
            ):
                try:
                    cs = ctx.schema.column(ctx.resolve(agg.args[0].name))
                    # float columns only: the wide pass accumulates in f32,
                    # which would break exact int64 sums
                    if cs.dtype.is_float and not cs.is_tag:
                        col = cs.name
                except Exception:  # noqa: BLE001
                    col = None
            if col is not None:
                batched.append((str(agg), op, col))
            else:
                fn = self._compile_agg(agg, ctx, ts_name, seg_fn)
                agg_specs.append((str(agg), fn))
                # sketch aggregates come back as [groups, width] grids;
                # the codec comes off the compiled fn so fold and
                # serialization can never disagree on (γ, nb)
                if agg.name in ("hll", "hll_merge"):
                    sketch_codecs[str(agg)] = ("hll",)
                elif agg.name == "uddsketch_state":
                    sketch_codecs[str(agg)] = ("udd",) + fn._udd_meta
                elif agg.name == "uddsketch_merge":
                    sketch_codecs[str(agg)] = (
                        "udd_merge",) + fn._udd_merge_meta

        padded = table.padded_rows
        num_groups = (
            grid if (dense_ok and key_specs) else (1 if not key_specs else padded)
        )
        dict_ver = tuple(len(ctx.encoders[c.name]) for c in ctx.schema.tag_columns)
        # time bounds and bucket-grid origins are TRACED kernel arguments,
        # not closure constants: a rolling window (every dashboard refresh,
        # every TSBS query) must reuse the compiled program, not recompile.
        # Shape-bearing parts (step, pow2 bucket count) stay in the key.
        cache_key = (
            plan.fingerprint(), padded, tuple(cards), dense_ok, num_groups,
            dict_ver, use_sorted, _vec_fingerprint(plan, table),
            tuple((spec[1][0], spec[1][2]) if spec[0] == "time" else spec[0:2]
                  for spec in key_specs if spec[0] != "expr"),
        )
        kernel = self._cache.get(cache_key)
        jit_miss = kernel is None
        if kernel is None:
            # never AOT-persisted: the DeviceTable pytree's aux bakes the
            # dictionary contents AND dicts_version (bumped on every
            # rebuild) into the executable's arg signature, so a
            # serialized executable could never be re-entered where jit
            # correctly RETRACES — these classes are classified/journaled
            # but served by plain jit
            kernel = self.compiler.get_or_build(
                "sql", cache_key,
                lambda: self._build_agg_kernel(
                    key_specs, dense_ok, num_groups, cards, where_fn,
                    agg_specs, ts_name, use_sorted, batched,
                ),
                persist=False, metrics=metrics)
            self._cache[cache_key] = kernel
        ts_lo = np.int64(lo) if lo is not None else _I64_MIN
        ts_hi = np.int64(hi) if hi is not None else _I64_MAX
        starts = tuple(np.int64(spec[1][1])
                       for spec in key_specs if spec[0] == "time")
        out = aot_kernel_call(
            kernel, lambda: kernel(table, ts_lo, ts_hi, starts), jit_miss,
            metrics)
        return out, lambda host: self._agg_env(
            plan, table, agg_specs, sketch_codecs, batched, host)

    @staticmethod
    def _agg_env(plan: SelectPlan, table: DeviceTable, agg_specs,
                 sketch_codecs: dict, batched, out: dict) -> tuple[dict, int]:
        """Kernel outputs (on the host) → result env of the row-path
        aggregate: the ``finish`` of ``_execute_agg``."""
        ctx = plan.ctx
        gmask = out.pop("__gmask__").astype(bool)
        cnt_all_g = out.pop("__cnt_all__", None)
        n = int(gmask.sum())
        env: dict[str, np.ndarray] = {}
        for i, k in enumerate(plan.group_keys):
            raw = out[f"__key{i}__"][gmask]
            if k.kind == "tag":
                col = decode_codes(ctx.encoders[k.column].values(), raw)
            else:
                col = raw
                # string-FIELD group keys come back as the DeviceTable's
                # ad-hoc dictionary codes — decode, never leak codes
                if isinstance(k.expr, Column):
                    try:
                        cs = ctx.schema.column(ctx.resolve(k.expr.name))
                    except Exception:  # noqa: BLE001
                        cs = None
                    if (
                        cs is not None and not cs.is_tag
                        and cs.dtype.is_string_like
                        and cs.name in table.dicts
                    ):
                        col = decode_codes(table.dicts[cs.name], raw)
            env[k.name] = col
            env[str(k.expr)] = col
        for name, _ in agg_specs:
            v = out[name][gmask]
            codec = sketch_codecs.get(name)
            if codec is not None:
                from greptimedb_tpu.ops import sketch as sk

                if codec[0] == "hll":
                    # gl: allow[GL-H001] -- sketch wire-encode epilogue over already-host group rows (O(groups), post-materialization)
                    v = np.array([sk.encode_hll(r) for r in v], dtype=object)
                elif codec[0] == "udd":
                    # gl: allow[GL-H001] -- same sketch epilogue, host side
                    v = np.array(
                        [sk.encode_udd(r, codec[1], codec[2]) for r in v],
                        dtype=object)
                else:  # udd_merge: [counts..., cfg_min, cfg_max] per group
                    configs, kmin_all, width, c_star = codec[1:5]
                    rows = []
                    for r in v:
                        cmin, cmax = int(r[-2]), int(r[-1])
                        if cmax < 0:  # no valid state rows in the group
                            rows.append(None)
                            continue
                        if cmin != cmax:
                            raise ExecutionError(
                                "uddsketch_merge: selected rows mix sketch"
                                " gamma configs (error_rate)")
                        sparse = {kmin_all + i: int(c)
                                  for i, c in enumerate(r[:width]) if c}
                        rows.append(sk.encode_udd_doc(
                            sparse, configs[cmin], c_star, width))
                    v = np.array(rows, dtype=object)  # gl: allow[GL-H001] -- sketch epilogue, host side
            env[name] = v
        for name, _op, _col in batched:
            env[name] = out[name][gmask]
        if cnt_all_g is not None and int(cnt_all_g[0]) == 0:
            # zero-row global aggregate: every non-count aggregate is
            # NULL; float paths already carry NaN, but int aggregates
            # (sum/min/max/first/last over int columns) came back as
            # 0/sentinel fills — NULL them here
            for agg in plan.aggs:
                if agg.name not in ("count", "count_distinct",
                                    "approx_distinct"):
                    env[str(agg)] = np.array([None], dtype=object)  # gl: allow[GL-H001] -- host NULL fill, O(aggregates)
        return env, n

    # ---- dense time-grid path -----------------------------------------
    def execute_grid(
        self, plan: SelectPlan, grid, ts_bounds: tuple[int, int],
        metrics: dict | None = None,
    ) -> tuple[dict, object] | None:
        """Aggregate over a GridTable: reshape+reduce per time bucket, then
        a tiny series-axis segment merge — no row scatter at any scale.
        Returns ``(out, finish)`` as ``execute`` does.

        Returns None when this plan/grid combination is ineligible (query
        bucket not a multiple of the grid step, unsupported agg shape…);
        the caller falls back to the row-oriented DeviceTable path.

        Reference counterpart: RangeSelectExec + the hash aggregate
        (src/query/src/range_select/plan.rs:273) — here the time bucketing
        is a tensor reshape because the data layout already IS the range
        grid (SURVEY.md §5.7, §7.1)."""
        g = self._grid_prologue(plan, grid, ts_bounds)
        if g is None:
            return None
        return self._execute_grid_geom(plan, grid, g, metrics)

    def _grid_prologue(self, plan: SelectPlan, grid,
                       ts_bounds: tuple[int, int]):
        """Plan→grid geometry shared by the solo path and the cross-query
        stacked dispatch (execute_grid_batch): agg specs, WHERE shape,
        time-bucket geometry and window slicing.  Returns None when the
        plan/grid combination is ineligible for the grid path; otherwise
        a _GridGeom whose fields feed either kernel family."""
        ctx = plan.ctx
        ts_name = ctx.schema.time_index.name
        tag_keys = [k for k in plan.group_keys if k.kind == "tag"]
        time_keys = [k for k in plan.group_keys if k.kind == "time"]
        if len(time_keys) > 1:
            return None
        gridcols = set(grid.field_names)

        # agg specs: (out_name, op, arg_fn|None, no_nan_plain, plain_ci)
        # plain_ci is the grid field index when the argument is exactly
        # one stored column — the bucket-major layout path addresses the
        # resident partial sums by it
        specs: list[tuple] = []
        try:
            for agg in plan.aggs:
                op = _GRID_OPS.get(agg.name)
                if op is None or agg.distinct:
                    return None
                if not agg.args or isinstance(agg.args[0], Star):
                    specs.append((str(agg), "count", None, True, None))
                    continue
                arg = agg.args[0]
                refs: set = set()
                referenced_columns(arg, ctx, refs)
                if not refs <= gridcols | {ts_name}:
                    return None
                no_nan_plain = False
                plain_ci = None
                if isinstance(arg, Column):
                    real = ctx.resolve(arg.name)
                    if real in gridcols:
                        ci = grid.field_names.index(real)
                        plain_ci = ci
                        no_nan_plain = bool(
                            grid.no_nan[ci] if ci < len(grid.no_nan) else False
                        )
                specs.append(
                    (str(agg), op, compile_device(arg, ctx), no_nan_plain,
                     plain_ci)
                )
            where_fn = None
            where_series = False
            if plan.where is not None:
                refs = set()
                referenced_columns(plan.where, ctx, refs)
                tags = {c.name for c in ctx.schema.tag_columns}
                if not refs <= gridcols | tags | {ts_name}:
                    return None
                # tag-only predicates reduce to a per-series [S] mask that
                # multiplies the already-reduced [S, NB] partials — the
                # big [S, T] reduce itself stays mask-free
                where_series = refs <= tags
                where_fn = compile_device(plan.where, ctx)
        except (PlanError, Unsupported):
            return None

        # time-bucket geometry: R grid points per query bucket, left pad
        # so every R-block lies in exactly one bucket (pad_left static per
        # (start, step) alignment class; rolling windows keep it constant)
        g_step = grid.step
        lo, hi = plan.time_range
        if time_keys:
            step_q, start, _nb = self._time_key_params(
                time_keys[0], plan, ts_bounds
            )
            if g_step <= 0 or step_q % g_step != 0:
                return None
            r = step_q // g_step
            q = (grid.ts0 - start) // g_step  # python floor division: exact
            pad_left = int(q % r)
            nb = -(-(pad_left + grid.tpad) // r)
            bts0 = np.int64(start + (q // r) * step_q)
        else:
            r = grid.tpad
            pad_left = 0
            nb = 1
            step_q = 0
            bts0 = np.int64(0)

        # window slicing: restrict the reduce to the buckets the query's
        # time range touches.  The slice START is a traced argument (so
        # rolling windows reuse one compiled kernel); the slice WIDTH is
        # static per window-length class.  Only an in-bounds, bucket-
        # aligned slice qualifies — otherwise the kernel pads the full
        # axis exactly as before.
        b_lo = 0
        s0 = 0
        aligned = False
        nbw, w_raw, pad_l, pad_r = nb, grid.tpad, pad_left, (
            nb * r - pad_left - grid.tpad
        )
        if time_keys and lo is not None and hi is not None and step_q > 0:
            cand_lo = max(0, int((lo - int(bts0)) // step_q))
            cand_hi = min(nb, int(-(-(hi - int(bts0)) // step_q)))
            if cand_hi <= cand_lo:
                cand_hi = cand_lo + 1
            raw0 = cand_lo * r - pad_left
            raw1 = (cand_hi - cand_lo) * r + raw0
            if raw0 >= 0 and raw1 <= grid.tpad:
                b_lo, s0 = cand_lo, raw0
                nbw, w_raw = cand_hi - cand_lo, raw1 - raw0
                pad_l = pad_r = 0
                # bucket-ALIGNED window (the TSBS/dashboard shape: range
                # endpoints on bucket boundaries): the ts-range indicator
                # is all-ones over the slice, so the bucket reduce lowers
                # to a pure [.., nb, r] @ ones[r] contraction — XLA:CPU's
                # gemv loop runs it ~6x faster than the broadcast-multiply
                # einsum (measured 182 ms vs 1130 ms on the 10-column
                # TSBS window; round-4 verdict item 8).  Alignment is a
                # static kernel-class property: rolling windows advance
                # by whole buckets and stay in this class.
                aligned = (
                    lo == int(bts0) + cand_lo * step_q
                    and hi == int(bts0) + cand_hi * step_q
                )

        cards_tag = [
            _pow2(max(len(ctx.encoders[k.column]), 1)) for k in tag_keys
        ]
        ngt = 1
        for c in cards_tag:
            ngt *= c
        if ngt * nbw > DENSE_LIMIT:
            return None
        if r >= (1 << 24):
            # per-(series, bucket) counts ride an f32 einsum, exact only
            # below 2^24; absurdly wide buckets take the row path
            return None

        dict_ver = tuple(
            len(ctx.encoders[c.name]) for c in ctx.schema.tag_columns
        )
        tag_order = tuple(sorted(grid.tag_codes))
        return _GridGeom(
            specs=specs, where_fn=where_fn, where_series=where_series,
            ts_name=ts_name, tag_keys=tag_keys, has_time=bool(time_keys),
            r=r, pad_left=pad_left, nb=nb, nbw=nbw, w_raw=w_raw,
            pad_l=pad_l, pad_r=pad_r, step_q=step_q, bts0=int(bts0),
            b_lo=b_lo, s0=s0, aligned=aligned, lo=lo, hi=hi,
            cards_tag=cards_tag, ngt=ngt, dict_ver=dict_ver,
            tag_order=tag_order,
        )

    def _execute_grid_geom(  # gl: warm-path
        self, plan: SelectPlan, grid, g: "_GridGeom",
        metrics: dict | None,
    ) -> tuple[dict, object]:
        ctx = plan.ctx
        specs = g.specs
        where_fn, where_series = g.where_fn, g.where_series
        ts_name = g.ts_name
        tag_keys, cards_tag = g.tag_keys, g.cards_tag
        r, pad_left, nb, nbw = g.r, g.pad_left, g.nb, g.nbw
        w_raw, pad_l, pad_r = g.w_raw, g.pad_l, g.pad_r
        step_q, bts0, b_lo, s0 = g.step_q, g.bts0, g.b_lo, g.s0
        aligned, lo, hi = g.aligned, g.lo, g.hi
        dict_ver, tag_order = g.dict_ver, g.tag_order
        g_step = grid.step
        DISPATCH_STATS["grid"] += 1

        # resident bucket-major layout: ALIGNED windows whose aggregates
        # all resolve to the per-(series, bucket) partials skip the
        # dynamic-slice window copy + gemv entirely — per-query work is a
        # bucket-axis slice of the cached [C, S, NB] sums plus the tiny
        # series-axis merge (storage/cache.py DerivedLayoutCache)
        out = None
        layout = self._aligned_layout(
            grid, r, pad_left, nb, specs, aligned, g.has_time,
            where_fn, where_series, metrics,
        )
        if layout is not None:
            DISPATCH_STATS["grid_bm"] += 1
            bm_key = (
                "grid_bm", plan.fingerprint(), grid.spad,
                grid.field_names, r, nbw, nb, step_q, tuple(cards_tag),
                dict_ver, tag_order, where_series,
            )
            kernel = self._cache.get(bm_key)
            jit_miss = kernel is None
            if kernel is None:
                kernel = self.compiler.get_or_build(
                    "sql", bm_key,
                    lambda: self._build_bm_kernel(
                        tag_order, [k.column for k in tag_keys], cards_tag,
                        nbw, step_q,
                        where_fn if where_series else None,
                        [(name, op, ci) for name, op, _fn, _nn, ci in specs],
                    ),
                    metrics=metrics)
                self._cache[bm_key] = kernel
            out = aot_kernel_call(
                kernel, lambda: kernel(
                    layout[0], layout[1],
                    tuple(grid.tag_codes[t] for t in tag_order),
                    np.int32(b_lo), np.int64(int(bts0) + b_lo * step_q),
                ), jit_miss, metrics)
        if out is None:
            cache_key = (
                "grid", plan.fingerprint(), grid.spad, grid.tpad,
                grid.field_names, grid.ts0, g_step, r, nbw, w_raw, pad_l,
                pad_r, tuple(cards_tag), dict_ver, grid.no_nan,
                g.has_time, tag_order, where_series, aligned,
            )
            kernel = self._cache.get(cache_key)
            jit_miss = kernel is None
            if kernel is None:
                kernel = self.compiler.get_or_build(
                    "sql", cache_key,
                    lambda: self._build_grid_kernel(
                        grid.field_names, ts_name, tag_order,
                        [k.column for k in tag_keys], cards_tag,
                        g.has_time, r, nbw, w_raw, pad_l, pad_r, step_q,
                        where_fn, where_series, specs, grid.ts0, g_step,
                        aligned,
                    ),
                    metrics=metrics)
                self._cache[cache_key] = kernel
            ts_lo = np.int64(lo) if lo is not None else _I64_MIN
            ts_hi = np.int64(hi) if hi is not None else _I64_MAX
            out = aot_kernel_call(
                kernel, lambda: kernel(
                    grid.values, grid.valid,
                    tuple(grid.tag_codes[t] for t in tag_order),
                    ts_lo, ts_hi, np.int64(int(bts0) + b_lo * step_q),
                    np.int32(s0),
                ), jit_miss, metrics)
        return out, lambda host: self._grid_env(plan, specs, host)

    @staticmethod
    def _grid_env(plan: SelectPlan, specs, out: dict) -> tuple[dict, int]:
        """Kernel outputs → host result env: one definition shared by the
        solo grid path and the stacked batch dispatch, so a batched
        member's result shaping can never diverge from solo."""
        ctx = plan.ctx
        gmask = out.pop("__gmask__").astype(bool)
        n = int(gmask.sum())
        env: dict[str, np.ndarray] = {}
        # internal flatten order: tag keys (in appearance order) then the
        # time bucket; emit per original plan key index
        comps_src = out["__comps__"]
        tag_pos = 0
        for i, k in enumerate(plan.group_keys):
            if k.kind == "tag":
                raw = comps_src[tag_pos][gmask]
                col = decode_codes(ctx.encoders[k.column].values(), raw)
                tag_pos += 1
            else:
                raw = out["__bts__"][gmask]
                col = raw
            env[k.name] = col
            env[str(k.expr)] = col
        for name, _op, _fn, _nn, _ci in specs:
            env[name] = out[name][gmask]
        return env, n

    # ---- cross-query stacked dispatch ---------------------------------
    def execute_grid_batch(  # gl: warm-path
        self, plans: list[SelectPlan], grid, ts_bounds: tuple[int, int],
        metrics: dict | None = None,
    ) -> tuple[dict, object] | None:
        """Stack N concurrent warm queries over the SAME (region, shape
        class) into one device dispatch: the bucket-major kernel vmapped
        over its per-window traced arguments (b_lo, bts0).  Eligibility
        is deliberately the tightest warm shape — bucket-aligned windows
        whose WHERE is absent (members fingerprint-identical) or
        tag-only (members identical up to the tag predicate, each
        member's filter entering as a traced per-series mask), identical
        window geometry, resident bucket-major layout available —
        everything else returns None and the scheduler falls back to
        solo execution.
        Data Path Fusion's observation (arXiv 2605.10511): once per-query
        kernels are cached, stacking shape-compatible work into one
        dispatch is the remaining multiplier.

        Bit-exactness contract: the stacked kernel is jit(vmap(fn)) of
        the SAME fn the solo path jits; vmap maps the batch axis over
        slice+segment ops whose reduction dims are unbatched, so each
        member's floats are identical to its solo run.

        Returns ``(out, finish)``; ``finish(fetch_host(out))`` gives one
        (env, nrows) a member."""
        if len(plans) < 2:
            return None
        geoms: list[_GridGeom] = []
        for p in plans:
            if p.sliding is not None:
                return None
            g = self._grid_prologue(p, grid, ts_bounds)
            if g is None:
                return None
            geoms.append(g)
        g0 = geoms[0]
        fp0 = plans[0].fingerprint()

        def plan_sig(p: SelectPlan):
            # where-independent plan identity: table, group keys and agg
            # output names — everything the vmapped kernel's output
            # contract and the host result shaping depend on.  The WHERE
            # itself may differ per member in tag-filtered mode.
            return (
                p.table,
                tuple((k.kind, str(k.expr), k.name) for k in p.group_keys),
                tuple(map(str, p.aggs)),
            )

        def sig(g: _GridGeom):
            return (
                g.aligned, g.has_time, g.where_fn is None, g.where_series,
                g.r, g.pad_left,
                g.nb, g.nbw, g.step_q, tuple(g.cards_tag), g.tag_order,
                g.dict_ver,
                tuple((name, op, ci, nn)
                      for name, op, _fn, nn, ci in g.specs),
            )

        sig0 = sig(g0)
        if not (g0.aligned and g0.has_time):
            return None
        # two batchable WHERE modes: absent (the original PR-7 surface:
        # members fingerprint-identical) and tag-only (the where_series
        # extension: members agree on everything EXCEPT the tag
        # predicate, which rides in as a per-member traced [S] mask —
        # filtered dashboard panels over different hosts coalesce too)
        if g0.where_fn is None:
            filtered = False
        elif g0.where_series:
            filtered = True
        else:
            return None
        psig0 = plan_sig(plans[0])
        # gl: allow[GL-H002] -- O(batch members) compatibility probe, bounded by max_batch
        for p, g in zip(plans[1:], geoms[1:]):
            if sig(g) != sig0:
                return None
            if (plan_sig(p) != psig0) if filtered else (
                    p.fingerprint() != fp0):
                return None
        layout = self._aligned_layout(
            grid, g0.r, g0.pad_left, g0.nb, g0.specs, True, True,
            None, False, metrics,
        )
        if layout is None:
            return None
        tag_arrays = tuple(grid.tag_codes[t] for t in g0.tag_order)
        smfs = None
        if filtered:
            # per-member [S] series masks from each member's OWN where_fn
            # (tiny cached kernels, one [S]-sized dispatch per distinct
            # filter); the expensive window reduce stays ONE stacked
            # dispatch over the traced mask stack
            smfs = jnp.stack([
                self._series_mask(p, g, grid, tag_arrays)
                for p, g in zip(plans, geoms)])

        n = len(plans)
        # pow2-pad the stack (duplicating the leader's window) so the
        # compiled-program population stays logarithmic in batch size
        npad = _pow2(n)
        # gl: allow[GL-H001] -- O(batch members) window-argument stack, host ints
        b_los = np.array(
            [g.b_lo for g in geoms] + [g0.b_lo] * (npad - n), np.int32)
        bts0s = np.array(  # gl: allow[GL-H001] -- same O(batch) stack
            [g.bts0 + g.b_lo * g.step_q for g in geoms]
            + [g0.bts0 + g0.b_lo * g0.step_q] * (npad - n), np.int64)
        if smfs is not None and npad > n:
            # pad the mask stack like the window arguments (leader twin)
            smfs = jnp.concatenate(
                [smfs, jnp.broadcast_to(
                    smfs[:1], (npad - n,) + smfs.shape[1:])])
        vkey = (
            "grid_bm_vmap", psig0 if filtered else fp0, grid.spad,
            grid.field_names, g0.r,
            g0.nbw, g0.nb, g0.step_q, tuple(g0.cards_tag), g0.dict_ver,
            g0.tag_order, npad, filtered,
        )
        kernel = self._cache.get(vkey)
        jit_miss = kernel is None
        if kernel is None:
            in_axes = ((None, None, None, 0, 0, 0) if filtered
                       else (None, None, None, 0, 0))
            kernel = self.compiler.get_or_build(
                "sql", vkey,
                lambda: named_jit("sql_grid_batch")(jax.vmap(
                    self._bm_kernel_fn(
                        g0.tag_order, [k.column for k in g0.tag_keys],
                        g0.cards_tag, g0.nbw, g0.step_q, None,
                        [(name, op, ci)
                         for name, op, _fn, _nn, ci in g0.specs],
                        take_smf=filtered,
                    ), in_axes=in_axes)),
                metrics=metrics)
            self._cache[vkey] = kernel
        DISPATCH_STATS["grid"] += n
        DISPATCH_STATS["grid_bm"] += n
        DISPATCH_STATS["grid_batch"] += 1
        call_args = (layout[0], layout[1], tag_arrays, b_los, bts0s)
        if filtered:
            call_args = call_args + (smfs,)
        out = aot_kernel_call(
            kernel, lambda: kernel(*call_args), jit_miss, metrics)
        if metrics is not None:
            metrics["batched"] = n
            metrics["layout"] = "bucket_major_stacked"

        def finish(out_np: dict) -> list:
            results = []
            for i, (p, g) in enumerate(zip(plans, geoms)):
                out_i = {k: v[i] for k, v in out_np.items()}
                results.append(self._grid_env(p, g.specs, out_i))
            return results

        return out, finish

    def _series_mask(self, plan, g: "_GridGeom", grid, tag_arrays):
        """Per-series WHERE mask [spad] f32 for one stacked-batch member:
        the member's own compiled tag predicate evaluated by a tiny
        cached kernel over the grid's tag codes — the exact
        ``broadcast_to(where_fn(env), (spad,)).astype(f32)`` expression
        the solo bm kernel computes inline, so a batched member's floats
        are identical to its solo run."""
        mkey = ("bm_smf", plan.fingerprint(), grid.spad, g.dict_ver,
                g.tag_order)
        fn = self._cache.get(mkey)
        if fn is None:
            where_fn = g.where_fn
            tag_order = g.tag_order
            spad = grid.spad

            @named_jit("sql_grid_series_mask")
            def fn(tag_arrays):
                env_s = dict(zip(tag_order, tag_arrays))
                return jnp.broadcast_to(
                    where_fn(env_s), (spad,)).astype(jnp.float32)

            self._cache[mkey] = fn
        return fn(tag_arrays)

    # ---- resident bucket-major layout (aligned windows) ---------------
    def _aligned_layout(
        self, grid, r, pad_left, nb, specs, aligned, has_time,
        where_fn, where_series, metrics,
    ):
        """Per-(series, bucket) partial arrays for the aligned-window
        path, from the DerivedLayoutCache (built on miss, admission
        permitting).  Returns (sums [C, S, NB], cnts [S, NB]) or None —
        None routes the query to the dynamic-slice kernel.

        Eligibility mirrors exactly the subset whose per-query math is
        window-independent: a bucket-aligned time window (every bucket
        fully covered by the ts range), aggregates that reduce to plain
        per-bucket sums/counts over finite stored columns, and a WHERE
        that is absent or tag-only (applied AFTER the bucket reduce).
        Everything else falls back, so the two layouts can never diverge
        semantically."""
        if metrics is not None:
            metrics["layout"] = "dynamic_slice"
        if not aligned_layout_eligible(
                specs, aligned, has_time, where_fn, where_series):
            return None
        step_class = (r, pad_left, nb)
        arrays = self.layout_cache.lookup(
            grid.region_id, step_class, grid.dicts_version
        )
        state = "hit"
        if arrays is None:
            est = (len(grid.field_names) + 1) * grid.spad * nb * 4
            if not self.layout_cache.admit(est):
                # over budget even after LRU reclaim: dynamic-slice path
                # (correct, just slower) rather than risking device OOM
                if metrics is not None:
                    metrics["layout_cache"] = "reject"
                return None
            arrays = self._bucket_major_partials(grid, r, pad_left, nb)
            self.layout_cache.store(
                grid.region_id, step_class, grid.dicts_version, arrays,
                sum(int(a.nbytes) for a in arrays),
            )
            state = "miss"
        if metrics is not None:
            metrics["layout"] = "bucket_major"
            metrics["layout_cache"] = state
        return arrays

    def _bucket_major_partials(self, grid, r, pad_left, nb):
        """Materialize the [S, nb, r] bucket-major reshape of the grid
        once on device and contract it to per-(series, bucket) partials:
        sums [C, S, NB] and validity counts [S, NB] (f32 — exact below
        2^24, guarded by the r-width check in execute_grid).  The
        contraction is the same ``reshape @ ones[r]`` the dynamic-slice
        kernel runs per window, over identical r-element blocks, so the
        per-bucket f32 results are bit-identical.  Mesh grids keep the
        partials sharded on the series axis (parallel/dist.py
        bucket_major_shardings)."""
        c = len(grid.field_names)
        spad, tpad = grid.spad, grid.tpad
        # resolve the partial shardings BEFORE the builder-cache lookup:
        # the jitted closure bakes them in, so a dimensionally-identical
        # grid under a DIFFERENT sharding (or none) must not reuse it —
        # the key carries the mesh identity.  A mesh grid whose shardings
        # cannot be built raises: the partials must never land whole on
        # the first device in silence
        shardings = None
        sh_key = None
        sh = grid.values.sharding
        if isinstance(sh, jax.sharding.NamedSharding):
            from greptimedb_tpu.parallel.dist import bucket_major_shardings

            shardings = bucket_major_shardings(sh.mesh, spad)
            if shardings is not None:
                sh_key = (
                    tuple(sh.mesh.axis_names),
                    tuple(d.id for d in sh.mesh.devices.flat),
                )
        key = ("bm_build", c, spad, tpad, r, pad_left, nb, sh_key)
        build = self._cache.get(key)
        if build is None:
            pad_rt = nb * r - pad_left - tpad

            def build_fn(values, valid):
                def padlast(x):
                    if pad_left == 0 and pad_rt == 0:
                        return x
                    widths = [(0, 0)] * (x.ndim - 1) + [(pad_left, pad_rt)]
                    return jnp.pad(x, widths)

                ones_r = jnp.ones((r,), jnp.float32)
                sums = padlast(values).reshape(c, spad, nb, r) @ ones_r
                cnts = padlast(
                    valid.astype(jnp.float32)
                ).reshape(spad, nb, r) @ ones_r
                if shardings is not None:
                    sums = jax.lax.with_sharding_constraint(
                        sums, shardings["sums"])
                    cnts = jax.lax.with_sharding_constraint(
                        cnts, shardings["cnts"])
                return sums, cnts

            build = self.compiler.get_or_build(
                "sql", key,
                lambda: named_jit("sql_grid_bm_build")(build_fn))
            self._cache[key] = build
        sums, cnts = build(grid.values, grid.valid)
        sums.block_until_ready()
        return (sums, cnts)

    def _bm_kernel_fn(  # gl: warm-path
        self, tag_order, tag_cols, cards_tag, nbw, step_q, where_fn,
        bm_specs, take_smf: bool = False,
    ):
        """Aligned-window kernel over the resident bucket-major partials:
        slice the window's buckets (traced start, static width — rolling
        windows reuse one compiled program), apply the tag-only WHERE as
        a per-series multiplier, merge the series axis into tag groups.
        Output contract matches _build_grid_kernel exactly (__gmask__/
        __comps__/__bts__ + one array per aggregate) so the host-side
        result shaping is shared.  Returned UNJITTED: the solo path jits
        it directly; the cross-query stacked dispatch jits vmap of the
        SAME function over (b_lo, bts0) — one program source, so batched
        and solo math can only differ by XLA's batching rule, which maps
        the window axis without touching any reduction order (the
        bit-exactness contract tests/test_scheduler.py pins)."""
        ngt = 1
        for c in cards_tag:
            ngt *= c
        nb = nbw

        def kernel(sums, cnts, tag_arrays, b_lo, bts0, *rest):
            spad = cnts.shape[0]
            tag_codes = dict(zip(tag_order, tag_arrays))
            s_w = jax.lax.dynamic_slice_in_dim(sums, b_lo, nbw, axis=2)
            c_w = jax.lax.dynamic_slice_in_dim(cnts, b_lo, nbw, axis=1)
            smf = None
            if take_smf:
                # stacked dispatch over tag-filtered windows: each
                # member's per-series WHERE mask arrives as a TRACED
                # [spad] f32 argument (computed by _series_mask from the
                # member's own where_fn), applied exactly where the
                # closure-captured mask is in the solo kernel — the
                # float math per member is identical to its solo run
                smf = rest[0]
                c_w = c_w * smf[:, None]
            elif where_fn is not None:
                env_s = {t: codes for t, codes in tag_codes.items()}
                smf = jnp.broadcast_to(
                    where_fn(env_s), (spad,)
                ).astype(jnp.float32)
                c_w = c_w * smf[:, None]
            ids = _series_group_ids(tag_codes, tag_cols, cards_tag, ngt,
                                    spad)

            def gseg(x):
                return jax.ops.segment_sum(x, ids, num_segments=ngt + 1)[:ngt]

            cnt_all = gseg(c_w.astype(jnp.int64))  # [ngt, NB]
            out = {}
            for name, op, ci in bm_specs:
                if op == "count":
                    out[name] = cnt_all.reshape(-1)
                    continue
                sb = s_w[ci]
                if smf is not None:
                    sb = sb * smf[:, None]
                sg = gseg(sb)
                if op == "sum":
                    out[name] = jnp.where(
                        cnt_all > 0, sg, jnp.nan).reshape(-1)
                else:  # mean
                    out[name] = jnp.where(
                        cnt_all > 0,
                        sg / jnp.maximum(cnt_all, 1).astype(jnp.float32),
                        jnp.nan,
                    ).reshape(-1)
            out["__gmask__"] = (cnt_all > 0).reshape(-1)
            out.update(_grid_key_outputs(
                tag_cols, cards_tag, ngt, nb, bts0, step_q, True))
            return out

        return kernel

    def _build_bm_kernel(self, *args):
        return named_jit("sql_grid_bm")(self._bm_kernel_fn(*args))

    def _build_grid_kernel(  # gl: warm-path
        self, field_names, ts_name, tag_order, tag_cols, cards_tag, has_time,
        r, nbw, w_raw, pad_l, pad_r, step_q, where_fn, where_series, specs,
        ts0, g_step, aligned=False,
    ):
        """Kernel over the sliced query window [s0, s0 + w_raw).

        Two structural wins over the old full-axis masked reduce:
        (1) the reduce reads only the window's buckets — a dynamic slice
        with traced start / static width, so rolling windows reuse one
        compiled kernel; (2) zero-filled invalid cells (storage/grid.py)
        mean the values plane is read exactly once with NO elementwise
        mask in the common case (plain no-NaN columns, tag-only or absent
        WHERE) — the ts-range indicator rides a tiny [NB, R] weight
        matrix whose broadcast multiply fuses into the reduce for ~free
        (measured: masked where() path 526 ms vs 155 ms pure on the TSBS
        window; this formulation hits ~same-as-pure)."""
        ngt = 1
        for c in cards_tag:
            ngt *= c
        nb = nbw

        @named_jit("sql_grid")
        def kernel(values, valid, tag_arrays, ts_lo, ts_hi, bts0, s0):
            # raw arrays, not the GridTable pytree: the pytree's aux data
            # (nt, dicts, …) changes on every append extension and would
            # force a retrace; the arrays' shapes are the real shape class
            spad = valid.shape[0]
            tag_codes = dict(zip(tag_order, tag_arrays))

            def sl(x):
                return jax.lax.dynamic_slice_in_dim(
                    x, s0, w_raw, axis=x.ndim - 1
                )

            valid_w = sl(valid)
            ts_axis = ts0 + (
                s0.astype(jnp.int64) + jnp.arange(w_raw, dtype=jnp.int64)
            ) * g_step
            env = {
                name: sl(values[ci])  # [S, W] plane, time contiguous
                for ci, name in enumerate(field_names)
            }
            for tname, codes in tag_codes.items():
                env[tname] = codes[:, None]
            env[ts_name] = ts_axis[None, :]
            tmask = (ts_axis >= ts_lo) & (ts_axis < ts_hi)  # [W]

            def padlast(x, fill):
                if pad_l == 0 and pad_r == 0:
                    return x
                widths = [(0, 0)] * (x.ndim - 1) + [(pad_l, pad_r)]
                return jnp.pad(x, widths, constant_values=fill)

            # per-timestep weights in bucket layout (tiny): w4 carries the
            # ts-range indicator; ones4 is pure bucket structure for paths
            # whose elementwise mask already includes the range
            w4 = padlast(tmask.astype(jnp.float32), 0.0).reshape(nb, r)
            ones4 = padlast(
                jnp.ones((w_raw,), jnp.float32), 0.0
            ).reshape(nb, r)

            ones_r = jnp.ones((r,), jnp.float32)

            def bdot(x, w):
                """[S, W] → [S, NB] f32: weighted bucket reduction.

                Aligned windows (no pad, ts-range indicator all-ones so
                every weight matrix is all-ones): a pure [S, nb, r] @
                ones[r] contraction — XLA:CPU lowers it to a gemv loop
                ~6x faster than the broadcast-multiply form (182 ms vs
                1130 ms on the 10-column TSBS window).  Unaligned/padded
                windows keep the broadcast multiply, which fuses into the
                reduce (a dot_general with a PER-BUCKET weight matrix is
                the slow case — measured 4158 ms as einsum csbr,br→csb)."""
                if aligned:
                    return x.astype(jnp.float32).reshape(
                        x.shape[0], nb, r) @ ones_r
                xp = padlast(x.astype(jnp.float32), 0.0)
                return (xp.reshape(x.shape[0], nb, r) * w).sum(axis=-1)

            # tag-only WHERE: one [S] mask multiplied into the reduced
            # [S, NB] partials — the big reduce stays mask-free
            smf = None
            elementwise = False
            if where_fn is not None:
                if where_series:
                    env_s = {t: c for t, c in tag_codes.items()}
                    smf = jnp.broadcast_to(
                        where_fn(env_s), (spad,)
                    ).astype(jnp.float32)
                else:
                    elementwise = True

            v2 = None

            def get_v2():
                """Elementwise liveness mask [S, W]; built only for paths
                that cannot ride the mask-free einsum (WHERE touching
                fields/ts, NaN-bearing columns, min/max)."""
                nonlocal v2
                if v2 is None:
                    m = valid_w & tmask[None, :]
                    if elementwise:
                        m = m & jnp.broadcast_to(where_fn(env), m.shape)
                    elif smf is not None:
                        m = m & (smf > 0)[:, None]
                    v2 = m
                return v2

            # series → tag-group ids (poison -1 → routed to segment ngt)
            ids = _series_group_ids(tag_codes, tag_cols, cards_tag, ngt,
                                    spad)

            def gseg(x, segf=jax.ops.segment_sum):
                """[S, NB] → [ngt, NB]: series-axis merge (tiny)."""
                return segf(x, ids, num_segments=ngt + 1)[:ngt]

            # shared count: per-(series, bucket) counts are ≤ R < 2^24 so
            # the f32 einsum is exact; the series merge runs in int64
            if elementwise:
                cnt_all_sb = bdot(get_v2(), ones4)
            else:
                cnt_all_sb = bdot(valid_w, w4)
                if smf is not None:
                    cnt_all_sb = cnt_all_sb * smf[:, None]
            cnt_all = gseg(cnt_all_sb.astype(jnp.int64))  # [ngt, NB]

            out = {}
            cnts: dict[str, jnp.ndarray] = {}
            sums: dict[str, jnp.ndarray] = {}
            min_items, max_items, cnt_items = [], [], []
            for name, op, arg_fn, no_nan_plain, _ci in specs:
                if op == "count" and (arg_fn is None or no_nan_plain):
                    continue  # resolves to the shared cnt_all
                x = jnp.broadcast_to(
                    jnp.asarray(arg_fn(env), dtype=jnp.float32),
                    (spad, w_raw),
                )
                if op in ("sum", "mean"):
                    if no_nan_plain and not elementwise:
                        # fast path: zero-filled invalid cells contribute
                        # +0 — raw plane straight into the einsum
                        sb = bdot(x, w4)
                        if smf is not None:
                            sb = sb * smf[:, None]
                    else:
                        m = get_v2() if no_nan_plain else (
                            get_v2() & ~jnp.isnan(x)
                        )
                        sb = bdot(jnp.where(m, x, 0.0), ones4)
                        if not no_nan_plain:
                            cnt_items.append((name, m))
                    sums[name] = gseg(sb)
                else:
                    m = get_v2() if no_nan_plain else (
                        get_v2() & ~jnp.isnan(x)
                    )
                    if op == "min":
                        min_items.append((name, x, m))
                    elif op == "max":
                        max_items.append((name, x, m))
                    if not no_nan_plain:
                        cnt_items.append((name, m))

            for name, m in cnt_items:
                cnts[name] = gseg(bdot(m, ones4).astype(jnp.int64))

            def breduce(x, fill, mode):
                xp = padlast(x, fill).reshape(x.shape[:-1] + (nb, r))
                return xp.min(axis=-1) if mode == "min" else xp.max(axis=-1)

            for items, mode, fill, segf in (
                (min_items, "min", jnp.inf, jax.ops.segment_min),
                (max_items, "max", -jnp.inf, jax.ops.segment_max),
            ):
                for name, x, m in items:
                    red = breduce(jnp.where(m, x, fill), fill, mode)
                    merged = gseg(red, segf)
                    c = cnts.get(name, cnt_all)
                    out[name] = jnp.where(c > 0, merged, jnp.nan).reshape(-1)

            for name, op, arg_fn, no_nan_plain, _ci in specs:
                if name in out:
                    continue  # min/max already materialized
                if op == "count":
                    c = cnt_all if (arg_fn is None or no_nan_plain) else (
                        cnts[name]
                    )
                    out[name] = c.reshape(-1)
                elif op == "sum":
                    # SQL: SUM over zero rows is NULL (global aggregates;
                    # grouped empties are gmask-filtered anyway)
                    c = cnt_all if no_nan_plain else cnts[name]
                    out[name] = jnp.where(
                        c > 0, sums[name], jnp.nan).reshape(-1)
                else:  # mean
                    c = cnt_all if no_nan_plain else cnts[name]
                    out[name] = jnp.where(
                        c > 0,
                        sums[name] / jnp.maximum(c, 1).astype(jnp.float32),
                        jnp.nan,
                    ).reshape(-1)

            if not tag_cols and not has_time:
                # global aggregate: SQL returns exactly one row even when
                # zero rows matched (count()=0, min/max=NULL)
                out["__gmask__"] = jnp.ones(1, dtype=bool)
            else:
                out["__gmask__"] = (cnt_all > 0).reshape(-1)
            out.update(_grid_key_outputs(
                tag_cols, cards_tag, ngt, nb, bts0, step_q, has_time))
            return out

        return kernel

    def _compile_agg(self, agg: FuncCall, ctx, ts_name: str | None,
                     seg_fn=segment_reduce):
        name = agg.name
        if name in ("hll", "uddsketch_state", "hll_merge",
                    "uddsketch_merge"):
            return self._compile_sketch_agg(agg, ctx)
        if name == "approx_distinct":
            # exact on device: sort-unique segment count is fast on TPU,
            # so the "approximation" can afford to be exact
            if not agg.args or isinstance(agg.args[0], Star):
                raise PlanError("approx_distinct needs a column argument")
            arg_fn = compile_device(agg.args[0], ctx)
            return lambda env, gid, ng, mask: segment_distinct_count(
                arg_fn(env), gid, ng, mask
            )
        if agg.distinct or name == "count_distinct":
            if name not in ("count", "count_distinct"):
                raise Unsupported(f"DISTINCT is only supported for count()"
                                  f", got {name}")
            if not agg.args or isinstance(agg.args[0], Star):
                raise PlanError("count(DISTINCT) needs a column argument")
            if len(agg.args) > 1:
                raise Unsupported(
                    "count(DISTINCT a, b): multi-column distinct"
                )
            arg = agg.args[0]
            # string/tag columns are dictionary codes on device — distinct
            # over codes IS distinct over values (dictionaries are
            # bijective), so no special-casing needed
            arg_fn = compile_device(arg, ctx)
            return lambda env, gid, ng, mask: segment_distinct_count(
                arg_fn(env), gid, ng, mask
            )
        if name == "count" and (not agg.args or isinstance(agg.args[0], Star)):
            def fn(env, gid, ng, mask):
                ones = jnp.ones(mask.shape, dtype=jnp.int32)
                return seg_fn(ones, gid, ng, "count", mask)
            return fn
        if not agg.args:
            raise PlanError(f"{name}() needs an argument")
        arg = agg.args[0]
        if isinstance(arg, Column) and name != "count":
            try:
                col_schema = ctx.schema.column(ctx.resolve(arg.name))
            except Exception:  # noqa: BLE001
                col_schema = None
            if col_schema is not None and (
                col_schema.is_tag or col_schema.dtype.is_string_like
            ):
                # string columns (tags AND fields) are dictionary codes on
                # device; numeric aggregation would aggregate codes,
                # lexicographic min/max needs a sorted dictionary, and
                # first/last_value would return undecoded codes
                raise Unsupported(f"{name}() over string column {arg.name}")
        arg_fn = compile_device(arg, ctx)
        if name == "count":
            return lambda env, gid, ng, mask: seg_fn(
                arg_fn(env), gid, ng, "count", mask
            )
        if name in ("sum", "min", "max"):
            return lambda env, gid, ng, mask, op=name: seg_fn(
                arg_fn(env), gid, ng, op, mask
            )
        if name in ("avg", "mean"):
            return lambda env, gid, ng, mask: seg_fn(
                arg_fn(env), gid, ng, "mean", mask
            )
        if name in ("first_value", "last_value"):
            if ts_name is None:
                raise PlanError(f"{name} needs a time index")
            last = name == "last_value"

            def fn(env, gid, ng, mask, last=last):
                _ts, val = segment_first_last(
                    env[ts_name], arg_fn(env), gid, ng, mask, last=last
                )
                return val

            return fn
        if name in ("stddev", "stddev_pop", "var", "var_pop"):
            pop = name.endswith("_pop")

            def fn(env, gid, ng, mask, pop=pop, std=name.startswith("std")):
                v = arg_fn(env)
                m = seg_fn(v, gid, ng, "mean", mask)
                cnt = seg_fn(v, gid, ng, "count", mask)
                centered = (v - m[jnp.clip(gid, 0, ng - 1)]) ** 2
                ss = seg_fn(centered, gid, ng, "sum", mask)
                denom = cnt if pop else jnp.maximum(cnt - 1, 1)
                var = jnp.where(cnt > (0 if pop else 1), ss / denom, jnp.nan)
                return jnp.sqrt(var) if std else var

            return fn
        raise Unsupported(f"aggregate {name}")

    def _compile_sketch_agg(self, agg: FuncCall, ctx):
        """hll/uddsketch_state fold raw rows into [groups, width] sketch
        grids on device; the *_merge variants decode every DISTINCT
        stored state into a dense vocab matrix at build time (the vector
        -search dictionary trick) and reduce those (ops/sketch.py)."""
        from greptimedb_tpu.ops import sketch as sk
        from greptimedb_tpu.query.ast import Literal

        name = agg.name
        if name == "hll":
            if len(agg.args) != 1:
                raise PlanError("hll(column)")
            arg_fn = compile_device(agg.args[0], ctx)
            return lambda env, gid, ng, mask: sk.hll_fold(
                arg_fn(env), gid, ng, mask)
        if name == "uddsketch_state":
            if (len(agg.args) != 3
                    or not isinstance(agg.args[0], Literal)
                    or not isinstance(agg.args[1], Literal)):
                raise PlanError(
                    "uddsketch_state(bucket_limit, error_rate, column)")
            try:
                nb = max(8, min(int(agg.args[0].value), 4096))
                gamma = sk.udd_gamma(float(agg.args[1].value))
            except (ValueError, TypeError) as e:
                raise PlanError(
                    f"uddsketch_state(bucket_limit, error_rate, column):"
                    f" {e}")
            arg_fn = compile_device(agg.args[2], ctx)

            def sfn(env, gid, ng, mask, gamma=gamma, nb=nb):
                return sk.udd_fold(arg_fn(env), gid, ng, mask, gamma, nb)

            sfn._udd_meta = (gamma, nb)  # the ONE (γ, nb) for encoding
            return sfn
        # merge variants: the argument is a string column of stored states
        arg = agg.args[0] if agg.args else None
        if not isinstance(arg, Column):
            raise PlanError(f"{name}(state_column)")
        col = ctx.resolve(arg.name)
        # keyed by (agg, column, table); only the NEWEST dicts version is
        # kept — the version counter is process-wide monotonic, so stale
        # matrices can never hit again (table in the key is belt-and-
        # suspenders against any future per-table versioning)
        ckey = (str(agg), col, getattr(ctx, "sketch_table", None))
        ver = getattr(ctx, "table_dicts_version", 0)
        cached = self._sketch_cache.get(ckey)
        if cached is not None and cached[0] == ver:
            return cached[1]
        vocab = list(getattr(ctx, "table_dicts", {}).get(col, []))
        if name == "hll_merge":
            mat = np.zeros((max(len(vocab), 1), sk.HLL_M), dtype=np.int32)
            for i, s in enumerate(vocab):
                regs = sk.decode_hll(s)
                if regs is not None:
                    mat[i] = regs
            dev = jnp.asarray(mat)
            fn = lambda env, gid, ng, mask: sk.hll_merge_fold(  # noqa: E731
                env[col], dev, gid, ng, mask)
            self._sketch_cache[ckey] = (ver, fn)
            return fn
        # uddsketch_merge: state keys are absolute base-γ-derived bucket
        # indices, so states merge regardless of their per-group offsets;
        # only the BASE γ must agree (differing collapse factors merge by
        # re-collapsing to the coarsest, exactly UDDSketch's operation).
        # Each vocab row gets a config (base γ) id and the kernel folds
        # per-group config min/max, so only queries whose SELECTED rows
        # actually mix base γ fail — at result time, not per vocabulary.
        metas = [sk.decode_udd(s) for s in vocab]
        configs: list[float] = []
        cfg_ids = np.full(max(len(vocab), 1), -1, dtype=np.int32)
        for i, m in enumerate(metas):
            if m is None:
                continue
            gb = round(m[1], 12)
            if gb not in configs:
                configs.append(gb)
            cfg_ids[i] = configs.index(gb)
        c_star = max((m[2] for m in metas if m is not None), default=1)
        # the combined key range may exceed the grid even at c_star:
        # re-collapse globally (more doubling) until it fits — never
        # clamp counts into an edge bucket
        base_lo = min(((min(m[4]) - 1) * m[2] + 1
                       for m in metas if m is not None and m[4]), default=0)
        base_hi = max((max(m[4]) * m[2]
                       for m in metas if m is not None and m[4]), default=0)
        while (base_hi - base_lo + 1) / c_star > 4096:
            c_star *= 2
        # re-express every state's keys in c_star units (upper-edge rule)
        all_keys: list[int] = []
        rekeyed: list[dict[int, int] | None] = []
        for m in metas:
            if m is None:
                rekeyed.append(None)
                continue
            _g, _gb, c, _nb, counts = m
            conv: dict[int, int] = {}
            for k, cnt in counts.items():
                kk = -((-k * c) // c_star)  # ceil(k*c / c_star)
                conv[kk] = conv.get(kk, 0) + cnt
            rekeyed.append(conv)
            all_keys.extend(conv.keys())
        kmin_all = min(all_keys) if all_keys else 0
        width = min(max(all_keys) - kmin_all + 1, 4097) if all_keys else 8
        mat = np.zeros((max(len(vocab), 1), width), dtype=np.int64)
        for i, conv in enumerate(rekeyed):
            if conv is None:
                continue
            for k, cnt in conv.items():
                mat[i, min(max(k - kmin_all, 0), width - 1)] += cnt
        dev = jnp.asarray(mat)
        dev_cfg = jnp.asarray(cfg_ids)

        def fn(env, gid, ng, mask):
            return sk.udd_merge_fold(env[col], dev, dev_cfg, gid, ng, mask)

        fn._udd_merge_meta = (configs, kmin_all, width, c_star)
        self._sketch_cache[ckey] = (ver, fn)
        return fn

    def _build_agg_kernel(  # gl: warm-path
        self, key_specs, dense_ok, num_groups, cards, where_fn, agg_specs,
        ts_name, use_sorted=False, batched=(),
    ):
        # map key_specs index -> ordinal into the traced time_starts tuple
        time_ordinal = {
            i: t for t, i in enumerate(
                i for i, s in enumerate(key_specs) if s[0] == "time"
            )
        }

        @named_jit("sql_rows_sorted" if use_sorted else "sql_rows_scatter")
        def kernel(table: DeviceTable, ts_lo, ts_hi, time_starts):
            env = dict(table.columns)
            pad_mask = table.row_mask  # padding rows, pre-WHERE
            mask = table.row_mask
            if ts_name is not None:
                # ts_lo/ts_hi are traced (sentinel min/max when unbounded):
                # a moving window re-runs this same compiled program
                mask = mask & (env[ts_name] >= ts_lo) & (env[ts_name] < ts_hi)
            if where_fn is not None:
                mask = mask & where_fn(env)

            n = mask.shape[0]
            if not key_specs:
                gid = jnp.zeros(n, dtype=jnp.int32)
                ng = 1
                gmask_init = None
            elif dense_ok:
                # sorted path combines tag-major (tag runs are series runs,
                # ts ascends within each) so the combined id is sorted
                order = (
                    sorted(range(len(key_specs)),
                           key=lambda i: 0 if key_specs[i][0] == "tag" else 1)
                    if use_sorted else range(len(key_specs))
                )
                codes = []
                ordered_cards = []
                for i in order:
                    spec = key_specs[i]
                    if spec[0] == "tag":
                        codes.append(env[spec[1]])
                    else:
                        step, _start, nb = spec[1]
                        idx = bucket_index(
                            env[ts_name], step, time_starts[time_ordinal[i]]
                        )
                        if use_sorted:
                            # WHERE-excluded rows clamp (keeps ids sorted and
                            # they are mask-neutral); PADDING rows must still
                            # poison — they trail, and clamping them to bucket
                            # 0 would break sortedness and corrupt the min/max
                            # scan's end-of-group reads on tag-less tables
                            idx = jnp.where(
                                pad_mask, jnp.clip(idx, 0, nb - 1), nb
                            )
                        codes.append(idx)
                    ordered_cards.append(cards[i])
                combined, _tot = combine_keys(codes, ordered_cards)
                gid = combined.astype(jnp.int32)
                ng = num_groups
                gmask_init = None
            else:
                # iterative collision-free ranking
                combined = None
                for i, spec in enumerate(key_specs):
                    if spec[0] == "tag":
                        vals = env[spec[1]].astype(jnp.int64)
                    elif spec[0] == "time":
                        step, _start, nb = spec[1]
                        vals = bucket_index(
                            env[ts_name], step, time_starts[time_ordinal[i]]
                        )
                    else:
                        # constant expressions (GROUP BY 1+1 / literal
                        # aliases) compile to scalars — broadcast to rows
                        vals = jnp.broadcast_to(
                            jnp.asarray(spec[1](env)), (n,)
                        ).astype(jnp.int64)
                    if combined is None:
                        combined = vals
                    else:
                        prev_rank, _gk, _gm = compact_groups(
                            combined, mask, num_groups
                        )
                        # prev_rank ≤ n, vals ranked next step; mix safely
                        r2, _gk2, _gm2 = compact_groups(vals, mask, num_groups)
                        combined = prev_rank.astype(jnp.int64) * (num_groups + 1) + r2
                gid_r, _gkeys, gmask_sp = compact_groups(combined, mask, num_groups)
                gid = gid_r.astype(jnp.int32)
                ng = num_groups
                gmask_init = gmask_sp

            count_fn = sorted_segment_reduce if use_sorted else segment_reduce
            cnt_all = count_fn(
                jnp.ones(n, dtype=jnp.int32), gid, ng, "count", mask
            )
            if not key_specs:
                # global aggregate: SQL returns exactly one row even when
                # zero rows matched (count()=0, other aggregates NULL);
                # the matched-row count ships out so the host can NULL
                # int aggregates too (no device NULL repr — they come
                # back as 0/sentinel fills)
                gmask = jnp.ones(1, dtype=bool)
                out_cnt_all = cnt_all
            else:
                gmask = cnt_all > 0
                if gmask_init is not None:
                    gmask = gmask & gmask_init
                out_cnt_all = None

            out = {"__gmask__": gmask}
            if out_cnt_all is not None:
                out["__cnt_all__"] = out_cnt_all
            # key materialization
            if key_specs and dense_ok:
                # dense grid: keys decompose arithmetically from the group
                # index — no gather, no scatter
                from greptimedb_tpu.ops.segment import decompose_keys

                comps = decompose_keys(
                    jnp.arange(ng, dtype=jnp.int64), ordered_cards
                )
                for pos, i in enumerate(order):
                    spec = key_specs[i]
                    if spec[0] == "tag":
                        out[f"__key{i}__"] = comps[pos]
                    else:
                        step, _start, nb = spec[1]
                        out[f"__key{i}__"] = (
                            comps[pos].astype(jnp.int64) * step
                            + time_starts[time_ordinal[i]]
                        )
            elif key_specs:
                # sparse path: representative row per group via segment_min
                ridx = jnp.arange(n, dtype=jnp.int64)
                prep_ids = jnp.where(
                    mask & (gid >= 0) & (gid < ng), gid, ng
                ).astype(jnp.int32)
                rep = jax.ops.segment_min(
                    jnp.where(mask, ridx, _I64_MAX), prep_ids,
                    num_segments=ng + 1,
                )[:ng]
                safe_rep = jnp.where(rep < _I64_MAX, rep, 0)
                for i, spec in enumerate(key_specs):
                    if spec[0] == "tag":
                        kv = env[spec[1]][safe_rep]
                    elif spec[0] == "time":
                        step, _start, nb = spec[1]
                        start = time_starts[time_ordinal[i]]
                        bucket = bucket_index(env[ts_name], step, start)
                        kv = (bucket * step + start)[safe_rep]
                    else:
                        kv = jnp.broadcast_to(
                            jnp.asarray(spec[1](env)), (n,)
                        ).astype(jnp.int64)[safe_rep]
                    out[f"__key{i}__"] = kv
            for name, fn in agg_specs:
                out[name] = fn(env, gid, ng, mask)

            if batched:
                # one wide pass for all plain sum/avg/count aggregates
                bcols = [env[c].astype(jnp.float32) for _n, _o, c in batched]
                V = jnp.stack(bcols, axis=1)  # [N, C]
                M = mask[:, None] & ~jnp.isnan(V)
                Vz = jnp.where(M, V, 0.0)
                Mi = M.astype(jnp.int32)
                if use_sorted:
                    ids_b = jnp.where(
                        (gid < 0) | (gid >= ng), ng, gid
                    ).astype(jnp.int32)
                    grid_ids = jnp.arange(ng, dtype=jnp.int32)
                    b_starts = jnp.searchsorted(ids_b, grid_ids, side="left")
                    b_ends = jnp.searchsorted(ids_b, grid_ids, side="right")

                    def csum2(x):
                        return jnp.concatenate(
                            [jnp.zeros((1, x.shape[1]), x.dtype),
                             jnp.cumsum(x, axis=0)], axis=0)

                    S = segmented_sum_scan(Vz, ids_b, b_starts, b_ends)
                    CNT = (csum2(Mi.astype(jnp.int64))[b_ends]
                           - csum2(Mi.astype(jnp.int64))[b_starts])
                else:
                    ids_b = jnp.where(
                        mask & (gid >= 0) & (gid < ng), gid, ng
                    ).astype(jnp.int32)
                    S = jax.ops.segment_sum(Vz, ids_b, num_segments=ng + 1)[:ng]
                    CNT = jax.ops.segment_sum(
                        Mi, ids_b, num_segments=ng + 1
                    )[:ng].astype(jnp.int64)
                for j, (name, op, _c) in enumerate(batched):
                    if op == "sum":
                        out[name] = jnp.where(
                            CNT[:, j] > 0, S[:, j], jnp.nan)
                    elif op == "count":
                        out[name] = CNT[:, j]
                    else:  # mean
                        out[name] = jnp.where(
                            CNT[:, j] > 0,
                            S[:, j] / jnp.maximum(CNT[:, j], 1).astype(S.dtype),
                            jnp.nan,
                        )
            return out

        return kernel

    # ---- raw (non-aggregate) path -------------------------------------
    @staticmethod
    def _topk_spec(plan: SelectPlan, ctx, table: DeviceTable) -> dict | None:
        """Eligibility for the device top-k raw scan: ORDER BY keys must
        all be numeric device columns whose code order equals value order
        (so NOT tags / string-dict fields), LIMIT must be present and
        small, and the projection must not contain window functions
        (their value depends on the full row set)."""
        from greptimedb_tpu.query.ast import WindowFunc
        from greptimedb_tpu.query.ast import expr_contains

        if plan.limit is None or not plan.order_by or plan.distinct:
            return None
        if plan.having is not None:
            # HAVING filters on the host AFTER the device truncates;
            # top-k would drop rows the filter needs
            return None
        k = plan.limit + (plan.offset or 0)
        if k > (1 << 16) or k >= table.padded_rows:
            return None
        for item in plan.items:
            if not isinstance(item.expr, Star) and expr_contains(
                    item.expr, WindowFunc):
                return None
        keys = []
        for o in plan.order_by:
            e = o.expr
            if not isinstance(e, Column):
                return None
            try:
                name = ctx.resolve(e.name)
            except Exception:  # noqa: BLE001
                return None
            if name not in table.columns or not ctx.schema.has_column(name):
                return None
            c = ctx.schema.column(name)
            if c.is_tag or c.dtype.is_string_like:
                return None
            keys.append((name, o.asc, o.nulls_first))
        return {"k": k, "keys": tuple(keys)}

    def _execute_raw(
        self, plan: SelectPlan, table: DeviceTable
    ) -> tuple[dict, object]:
        ctx = plan.ctx
        ctx.table_dicts = table.dicts  # vector search / string-dict exprs
        ctx.fulltext = self._fulltext_provider(plan, table)
        ts_name = ctx.schema.time_index.name if ctx.schema.time_index else None
        where_fn = compile_device(plan.where, ctx) if plan.where is not None else None
        lo, hi = plan.time_range

        needed: set[str] = set()
        has_star = any(isinstance(i.expr, Star) for i in plan.items)
        if has_star:
            needed = {c.name for c in ctx.schema}
        for item in plan.items:
            if not isinstance(item.expr, Star):
                referenced_columns(item.expr, ctx, needed)
        for o in plan.order_by:
            referenced_columns(o.expr, ctx, needed)
        cols = sorted(needed & set(table.columns.keys()))

        # Device top-k: ORDER BY <numeric device columns> LIMIT k sorts and
        # slices ON DEVICE, so only k rows cross to the host instead of the
        # whole filtered table (reference: part_sort/windowed-sort execs,
        # src/query/src/part_sort.rs).  The host re-sorts the k survivors,
        # so device selection only has to return the right SET.
        topk = self._topk_spec(plan, ctx, table)

        dict_ver = tuple(len(ctx.encoders[c.name]) for c in ctx.schema.tag_columns)
        cache_key = (
            "raw", plan.fingerprint(), table.padded_rows, tuple(cols), dict_ver,
            _vec_fingerprint(plan, table), topk and tuple(topk.items()),
        )
        kernel = self._cache.get(cache_key)
        if kernel is None:
            def filter_mask(env, row_mask, ts_lo, ts_hi):
                """The ONE raw-scan filter (shared by both kernels so the
                top-k path can never diverge from the full scan). Time
                bounds arrive traced — moving windows reuse the kernel."""
                mask = row_mask
                if ts_name is not None:
                    mask = mask & (env[ts_name] >= ts_lo) & (env[ts_name] < ts_hi)
                if where_fn is not None:
                    mask = mask & where_fn(env)
                return mask

            if topk is not None:
                k = topk["k"]
                spec = topk["keys"]  # ((col, asc, nulls_first), ...)

                def kernel_fn(t: DeviceTable, ts_lo, ts_hi):
                    env = dict(t.columns)
                    mask = filter_mask(env, t.row_mask, ts_lo, ts_hi)
                    keys = []  # minor → major for lexsort
                    for col, asc, nulls_first in reversed(spec):
                        v = env[col]
                        if jnp.issubdtype(v.dtype, jnp.floating):
                            isnull = jnp.isnan(v)
                            nf = (not asc) if nulls_first is None else nulls_first
                            rank = jnp.where(isnull, 0 if nf else 2, 1)
                            v = jnp.where(isnull, 0, v)
                        else:
                            if v.dtype == jnp.bool_:
                                v = v.astype(jnp.int32)
                            rank = jnp.ones_like(v, dtype=jnp.int32)
                        keys.append(v if asc else -v)
                        keys.append(rank)
                    keys.append(~mask)  # invalid rows sort last
                    order = jnp.lexsort(tuple(keys))[:k]
                    packed = {c: env[c][order] for c in cols}
                    packed["__n__"] = jnp.minimum(
                        jnp.sum(mask.astype(jnp.int64)), k)
                    return packed
            else:

                def kernel_fn(t: DeviceTable, ts_lo, ts_hi):
                    env = dict(t.columns)
                    mask = filter_mask(env, t.row_mask, ts_lo, ts_hi)
                    sub = {c: env[c] for c in cols}
                    packed, new_mask = compact_rows(sub, mask)
                    packed["__n__"] = jnp.sum(mask.astype(jnp.int64))
                    return packed

            # DeviceTable-pytree kernel: never AOT-persisted (see the
            # agg path) — classified and journaled, served by plain jit
            kernel = self.compiler.get_or_build(
                "sql", cache_key,
                lambda: named_jit(
                    "sql_rows_raw" if topk is None else "sql_rows_topk"
                )(kernel_fn),
                persist=False)
            self._cache[cache_key] = kernel
        out = kernel(
            table,
            np.int64(lo) if lo is not None else _I64_MIN,
            np.int64(hi) if hi is not None else _I64_MAX,
        )

        def finish(out: dict) -> tuple[dict[str, np.ndarray], int]:
            n = int(out.pop("__n__"))
            env: dict[str, np.ndarray] = {}
            for c in cols:
                arr = out[c][:n]
                col = (ctx.schema.column(c) if ctx.schema.has_column(c)
                       else None)
                if col is not None and col.is_tag:
                    vals = ctx.encoders[c].values()
                elif c in table.dicts:  # dictionary-encoded string FIELD
                    vals = table.dicts[c]
                else:
                    env[c] = arr
                    continue
                lookup = np.array(list(vals) + [None], dtype=object)
                codes = arr.astype(np.int64)
                codes = np.where((codes < 0) | (codes >= len(vals)),
                                 len(vals), codes)
                env[c] = lookup[codes]
            return env, n

        return out, finish
