"""QueryEngine: executes Select statements and shapes results.

Counterpart of the reference's DatafusionQueryEngine::execute
(src/query/src/datafusion.rs:507) minus the substrate: planning and result
shaping on host, the scan/filter/aggregate middle on device via
query.physical. Post-aggregation shaping (HAVING → ORDER BY → LIMIT →
projection) mirrors the standard SQL operator order.
"""

from __future__ import annotations

from collections.abc import Sequence

import numpy as np

from greptimedb_tpu.errors import PlanError, TableNotFound, Unsupported
from greptimedb_tpu.query.ast import (
    Exists, Expr, InList, InSubquery, Literal, ScalarSubquery, Select,
    SelectItem, Star,
)
from greptimedb_tpu.query.exprs import TableContext, eval_host
from greptimedb_tpu.query.physical import Executor, fetch_host
from greptimedb_tpu.query.planner import SelectPlan, plan_select
from greptimedb_tpu.query.window import collect_windows, compute_window
from greptimedb_tpu.utils.tracing import TRACER


def _scan_stats_seq() -> int:
    from greptimedb_tpu.storage.scan import scan_stats

    return scan_stats().get("seq", 0)


def _attach_scan_stats(metrics, seq0: int) -> None:
    """Fold the cold-scan pipeline's phase summary (storage/scan.py) into
    the per-query metrics sink when a scan actually ran under this query
    (cache miss/rebuild) — EXPLAIN ANALYZE's cold row and slow_queries
    then show where cold time went (decode vs merge, files, strategy).
    Warm queries (seq unchanged) add nothing.  The summary is THREAD-
    local (scan_stats), so a compaction or another worker's scan landing
    mid-query can no longer masquerade as this query's cold phases."""
    if metrics is None:
        return
    from greptimedb_tpu.storage.scan import scan_stats

    s = scan_stats()
    if s.get("seq", 0) == seq0:
        return
    for key in ("files", "threads", "decode_ms", "path", "merge_ms"):
        if key in s:
            metrics[f"scan_{key}"] = s[key]


def _encode_replay(sel: Select, dbname: str) -> dict | None:
    """Usage-journal replay payload for one Select, or None when the
    plan contains nodes outside the codec registry (decorrelated tuple
    membership etc.) — such classes still count, they just can't warm.
    The plan ships in canonical (sorted-key) form so replay equality is
    byte-stable across processes sharing one journal."""
    from greptimedb_tpu.query.plancodec import plan_canon

    try:
        return {"kind": "sql_plan", "plan": plan_canon(sel),
                "db": dbname}
    except Exception:  # noqa: BLE001 — capture is best-effort
        return None


class ColumnRows(Sequence):
    """A result's rows as whole columns: numpy arrays of one length, in
    the order of the column names.  Reads as the ``list[list]`` that
    ``to_rows`` builds, and writes nothing: the reply's encoder takes
    ``columns`` as they are (servers/http.py ``_json_reply``)."""

    __slots__ = ("columns",)

    def __init__(self, columns: list[np.ndarray]):
        self.columns = columns

    def __len__(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    def __getitem__(self, i):
        if isinstance(i, slice):
            return ColumnRows([c[i] for c in self.columns])
        i = range(len(self))[i]
        return ColumnRows([c[i:i + 1] for c in self.columns]).to_rows()[0]

    def __iter__(self):
        return iter(self.to_rows())

    def to_rows(self) -> list[list]:
        """Column-wise: ndarray.tolist() converts to Python scalars in C
        (no per-cell numpy scalar boxing), then one zip; NaN reads as
        None."""
        cols_py: list[list] = []
        for col in self.columns:
            lst = col.tolist()
            if col.dtype.kind == "f" and bool(np.isnan(col).any()):
                lst = [None if v != v else v for v in lst]
            elif col.dtype.kind == "O":
                lst = [_pyval(v) for v in lst]
            cols_py.append(lst)
        return [list(t) for t in zip(*cols_py)]


class QueryResult:
    """One statement's answer.  ``QueryResult(names, rows)`` holds the
    rows it was given; ``_shape`` gives ``columns`` and no rows, and
    ``rows`` is then built on first read and kept.  From there on the
    list is the result (its holder may change it), so the columns go."""

    def __init__(self, column_names: list[str],
                 rows: list[list] | None = None, affected_rows: int = 0,
                 # greptime type names per column (e.g. "Float64")
                 column_types: list[str] | None = None, *,
                 columns: ColumnRows | None = None):
        self.column_names = column_names
        self.affected_rows = affected_rows
        self.column_types = column_types
        self.columns = columns
        self._rows = [] if rows is None and columns is None else rows

    @property
    def rows(self) -> list[list]:
        if self._rows is None:
            self._rows = self.columns.to_rows()
            self.columns = None
        return self._rows

    @rows.setter
    def rows(self, rows: list[list]) -> None:
        self._rows = rows
        self.columns = None

    @property
    def num_rows(self) -> int:
        return len(self.columns if self._rows is None else self._rows)

    def to_pydict(self) -> dict[str, list]:
        return {
            name: [r[i] for r in self.rows]
            for i, name in enumerate(self.column_names)
        }

    def __eq__(self, other):
        if other.__class__ is not self.__class__:
            return NotImplemented
        return (self.column_names, self.rows, self.affected_rows,
                self.column_types) == (
            other.column_names, other.rows, other.affected_rows,
            other.column_types)

    def __repr__(self) -> str:
        return f"QueryResult[{self.num_rows} rows x {len(self.column_names)} cols]"


class TableProvider:
    """What the engine needs from the storage/catalog layers."""

    def table_context(self, table: str) -> TableContext:
        raise NotImplementedError

    def device_table(self, table: str, plan: SelectPlan):
        """Returns (DeviceTable, ts_bounds)."""
        raise NotImplementedError


def _null_key(v, asc: bool, nulls_first: bool | None):
    # SQL default: NULLS LAST when ASC, NULLS FIRST when DESC
    is_null = v is None or (isinstance(v, float) and np.isnan(v))
    if nulls_first is None:
        nulls_first = not asc
    null_rank = 0 if (is_null and nulls_first) else (2 if is_null else 1)
    return null_rank, v if not is_null else 0


class SingleTableProvider(TableProvider):
    """Provider over one Region (or region-duck view): any table name maps
    to it.  Used for ephemeral staged tables (joins) and scoped execution
    (datanode shipped sub-queries)."""

    def __init__(self, view, timezone: str = "UTC"):
        self.view = view
        self.timezone = timezone
        self._built: tuple | None = None

    def table_context(self, table: str) -> TableContext:
        return TableContext(self.view.schema, self.view.encoders,
                            self.timezone)

    def device_table(self, table: str, plan):
        from greptimedb_tpu.storage.cache import build_device_table

        gen = self.view.generation
        if self._built is None or self._built[0] != gen:
            self._built = (gen, build_device_table(self.view))
        return self._built[1], self.view.ts_bounds() or (0, 0)


class QueryEngine:
    def __init__(self, provider: TableProvider):
        self.provider = provider
        self.executor = Executor()
        # full-statement dispatch for nested queries (set by GreptimeDB to
        # its execute_statement so information_schema subqueries work);
        # defaults to this engine
        self.dispatch = None

    # ---- subquery resolution ------------------------------------------
    def _run_nested(self, sub: Select) -> QueryResult:
        run = self.dispatch if self.dispatch is not None else self.execute_select
        return run(sub)

    def _rewrite_subqueries(self, e, outer: Select | None = None):
        """Subqueries → literals / IN lists, bottom-up via the shared
        map_expr walker (the reference relies on DataFusion's subquery
        support + decorrelation, src/query/src/datafusion.rs:141).
        EXISTS decorrelates: equality correlations against the outer
        table become a membership test over the inner side's DISTINCT
        key values."""
        from greptimedb_tpu.query.ast import map_expr

        def resolve(node):
            if isinstance(node, ScalarSubquery):
                res = self._run_nested(node.select)
                if len(res.column_names) != 1 or len(res.rows) > 1:
                    raise PlanError(
                        "scalar subquery must return one column and ≤1 row"
                    )
                return Literal(res.rows[0][0] if res.rows else None)
            if isinstance(node, InSubquery):
                res = self._run_nested(node.select)
                if len(res.column_names) != 1:
                    raise PlanError(
                        "IN subquery must return exactly one column"
                    )
                if not res.rows:
                    # IN () = FALSE, NOT IN () = TRUE
                    return Literal(bool(node.negated))
                items = tuple(Literal(r[0]) for r in res.rows)
                return InList(node.expr, items, node.negated)
            if isinstance(node, Exists):
                return self._rewrite_exists(node, outer)
            return node

        return map_expr(e, resolve)

    def _rewrite_exists(self, node: Exists, outer: Select | None):
        """EXISTS (SELECT ...): uncorrelated → boolean literal; a single
        equality correlation `inner_col = outer_col` → decorrelated
        membership `outer_col IN (SELECT DISTINCT inner_col FROM ...)`
        (NOT EXISTS arrives as NOT wrapping this node and negates the
        resulting mask vectorized)."""
        import dataclasses

        from greptimedb_tpu.query.ast import BinaryOp, Column, split_conjuncts

        sub: Select = node.select
        corr = []  # (inner Column, outer Column expr)
        rest = []
        # a column is an OUTER correlation ONLY when explicitly qualified
        # with the outer table's name/alias (`hosts.h`): unqualified and
        # inner-qualified names (incl. joined subquery tables) stay inner
        # — misclassifying an inner-to-inner equality would silently bind
        # a stripped name against the outer table
        outer_names = set()
        if outer is not None and outer.table is not None:
            outer_names = {outer.table, outer.table_alias} - {None}
            short = outer.table.rsplit(".", 1)[-1]
            outer_names.add(short)

        def is_outer(c: Column) -> bool:
            return c.table is not None and c.table in outer_names

        for conj in split_conjuncts(sub.where):
            if (isinstance(conj, BinaryOp) and conj.op == "="
                    and isinstance(conj.left, Column)
                    and isinstance(conj.right, Column)):
                lo, ro = is_outer(conj.left), is_outer(conj.right)
                if lo and not ro:
                    corr.append((conj.right, conj.left))
                    continue
                if ro and not lo:
                    corr.append((conj.left, conj.right))
                    continue
            rest.append(conj)

        if not corr:
            res = self._run_nested(sub)
            return Literal(res.num_rows > 0)
        if (sub.limit is not None or sub.offset is not None
                or sub.group_by or sub.having is not None):
            # decorrelation would silently drop these clauses (LIMIT 0
            # means EXISTS is always false!) — refuse instead
            raise Unsupported(
                "correlated EXISTS with LIMIT/OFFSET/GROUP BY/HAVING")
        from greptimedb_tpu.query.exprs import is_aggregate

        if any(is_aggregate(it.expr) for it in sub.items):
            # an aggregate subquery yields exactly one row per outer value
            # (EXISTS is then unconditionally true) — membership over the
            # correlation column would wrongly drop unmatched outer rows
            raise Unsupported("correlated EXISTS over an aggregate")
        # any OTHER outer reference left in the residual WHERE would bind
        # to the inner table by bare name (exprs.py resolution fallback)
        # and silently evaluate wrong — refuse
        from greptimedb_tpu.query.ast import walk_columns

        for conj in rest:
            for c in walk_columns(conj):
                if is_outer(c):
                    raise Unsupported(
                        "correlated EXISTS supports outer references only "
                        "as equality correlations")
        new_where = None
        for c in rest:
            new_where = c if new_where is None else BinaryOp(
                "AND", new_where, c)
        inner_sel = dataclasses.replace(
            sub,
            items=[SelectItem(Column(ic.name)) for ic, _oc in corr],
            where=new_where,
            distinct=True,
            group_by=[], order_by=[], limit=None, offset=None,
        )
        res = self._run_nested(inner_sel)
        if len(corr) == 1:
            vals = [r[0] for r in res.rows if r[0] is not None]
            if not vals:
                return Literal(False)
            # strip the outer qualifier: the outer plan resolves bare names
            return InList(Column(corr[0][1].name),
                          tuple(Literal(v) for v in vals))
        # multi-key correlation: tuple membership over the inner side's
        # DISTINCT key combinations (the reference reaches this via
        # DataFusion's semi-join decorrelation).  NULL-bearing tuples can
        # never equal — drop them.
        from greptimedb_tpu.query.ast import TupleIn

        rows = tuple(
            tuple(r) for r in res.rows if all(v is not None for v in r)
        )
        if not rows:
            return Literal(False)
        return TupleIn(
            tuple(Column(oc.name) for _ic, oc in corr), rows)

    def _resolve_subqueries(self, sel: Select) -> Select:
        import dataclasses

        from greptimedb_tpu.query.ast import expr_contains

        touched = [sel.where, sel.having] + [it.expr for it in sel.items]
        if not any(
            e is not None and expr_contains(
                e, (ScalarSubquery, InSubquery, Exists))
            for e in touched
        ):
            return sel
        return dataclasses.replace(
            sel,
            where=(self._rewrite_subqueries(sel.where, sel)
                   if sel.where is not None else None),
            having=(self._rewrite_subqueries(sel.having, sel)
                    if sel.having is not None else None),
            items=[
                dataclasses.replace(
                    it, expr=self._rewrite_subqueries(it.expr, sel))
                for it in sel.items
            ],
        )

    # ------------------------------------------------------------------
    def execute_select(self, sel: Select, metrics: dict | None = None) -> QueryResult:
        import time as _time

        if metrics is None:
            # slow-query self-reporting: the provider (GreptimeDB) exposes
            # a per-statement stage sink; when one is active this query's
            # stage breakdown lands there at zero extra cost (the mark()
            # calls below run either way)
            metrics = getattr(self.provider, "stage_sink", None)
        sel = self._resolve_subqueries(sel)
        if sel.table is None:
            return self._execute_tableless(sel)
        if sel.joins:
            from greptimedb_tpu.query.join import execute_join

            return execute_join(self, sel)

        # shape-class replay capture (compile/journal.py): lazily encode
        # this statement (plancodec wire form + session db) so a fresh
        # process can replay it to warm any kernel class it builds.
        # Statements executing outside the db provider (staged join
        # scans, shipped sub-plans) clear the context — their ephemeral
        # tables don't resolve in a replay.
        comp = getattr(self.executor, "compiler", None)
        if comp is not None:
            dbname = getattr(self.provider, "current_db", None)
            if dbname is None:
                comp.clear_replay()
            else:
                comp.set_replay(
                    lambda sel=sel, dbname=dbname: _encode_replay(
                        sel, dbname))

        def mark(name, t0):
            if metrics is not None:
                metrics[name] = round((_time.perf_counter() - t0) * 1000, 3)
            return _time.perf_counter()

        t = _time.perf_counter()
        check = getattr(self.provider, "check_cancelled", None)
        if check is not None:  # cooperative KILL (ProcessManager)
            check()
        ctx = self.provider.table_context(sel.table)
        from greptimedb_tpu.query.optimizer import optimize_select

        with TRACER.stage("optimize"):
            sel, opt_rules = optimize_select(sel, ctx)
        with TRACER.stage("plan"):
            plan = plan_select(sel, ctx)
        if metrics is not None and opt_rules:
            metrics["optimizer_rules"] = ",".join(opt_rules)
        t = mark("plan_ms", t)
        if plan.is_agg and any(
                k.kind == "expr" for k in plan.group_keys):
            res = self._execute_expr_key_agg(sel, ctx, plan)
            if res is not None:
                mark("device_exec_ms", t)
                if metrics is not None:
                    metrics["output_rows"] = res.num_rows
                    metrics["expr_key_fold"] = True
                return res
        if check is not None:
            check()
        # dense time-grid fast path: regular-cadence metric tables lower
        # (tags × time bucket) aggregation to reshape+reduce — no scatter
        pending = None  # (device result, finish): see Executor.execute
        scanned = 0
        import os as _os

        grid_fn = getattr(self.provider, "grid_table", None)
        if grid_fn is not None:
            from greptimedb_tpu.query.physical import grid_plan_candidate

            if grid_plan_candidate(plan):
                scan_seq0 = _scan_stats_seq()
                with TRACER.stage("scan_cache"):
                    grid, ts_bounds = grid_fn(sel.table, plan)
                if grid is not None:
                    t = mark("scan_cache_ms", t)
                    _attach_scan_stats(metrics, scan_seq0)
                    with TRACER.stage("execute"):
                        pending = self.executor.execute_grid(
                            plan, grid, ts_bounds, metrics=metrics)
                    if pending is not None:
                        scanned = grid.spad * grid.tpad
                        if metrics is not None:
                            metrics["grid"] = True
        if pending is None and _os.environ.get(
                "GREPTIME_MESH", "auto") != "off":
            # mesh row path: irregular/sparse tables the grid refuses
            # still aggregate across the device mesh when the query
            # decomposes at the commutativity boundary (the provider
            # returns merged-but-unordered rows; ORDER BY/LIMIT — the
            # non-commutative suffix — finish here)
            mesh_fn = getattr(self.provider, "mesh_select", None)
            if mesh_fn is not None and self._mesh_shapeable(sel):
                with TRACER.stage("execute"):
                    mres = mesh_fn(sel)
                if mres is not None:
                    t = mark("device_exec_ms", t)
                    with TRACER.stage("materialize"):
                        result = self._finish_merged(sel, plan, *mres)
                    mark("shape_ms", t)
                    if metrics is not None:
                        metrics["mesh_rows"] = True
                        metrics["output_rows"] = result.num_rows
                    return result
        if pending is None:
            scan_seq0 = _scan_stats_seq()
            with TRACER.stage("scan_cache"):
                table, ts_bounds = self.provider.device_table(sel.table, plan)
            t = mark("scan_cache_ms", t)
            _attach_scan_stats(metrics, scan_seq0)
            with TRACER.stage("execute"):
                pending = self.executor.execute(plan, table, ts_bounds,
                                                metrics=metrics)
            scanned = table.padded_rows
        out, finish = pending
        host = fetch_host(out)
        t = mark("device_exec_ms", t)
        with TRACER.stage("materialize"):
            env, n = finish(host)
            if plan.sliding is not None:
                env, n = _apply_sliding(plan, env, n)
            result = self._shape(plan, env, n)
        mark("shape_ms", t)
        if metrics is not None:
            metrics["output_rows"] = result.num_rows
            metrics["scanned_rows_padded"] = scanned
        return result

    # ---- cross-query stacked execution --------------------------------
    def execute_select_batch(
        self, sels: list[Select], metrics: dict | None = None,
    ) -> list[QueryResult] | None:
        """Execute N concurrent Selects over the same (table, shape
        class) through ONE stacked device dispatch
        (Executor.execute_grid_batch), shaping each member's result with
        the normal per-query host tail (_shape) so batched output is
        bit-exact vs solo execution.  Returns None whenever ANY member
        falls outside the tight warm-grid eligibility — the scheduler
        then executes the group solo, so this path can only ever be a
        fast path, never a semantic fork."""
        if len(sels) < 2:
            return None
        # the worker thread may still carry the replay context of its
        # LAST solo statement — batch-built kernel classes (the vmapped
        # stack) must journal replay-less, not attach an unrelated
        # statement a warmup boot would then replay for nothing
        comp = getattr(self.executor, "compiler", None)
        if comp is not None:
            comp.clear_replay()
        grid_fn = getattr(self.provider, "grid_table", None)
        if grid_fn is None:
            return None
        table = sels[0].table
        if table is None or any(
            s.table != table or s.joins or s.from_subquery is not None
            for s in sels
        ):
            return None
        from greptimedb_tpu.query.ast import expr_contains

        for s in sels:
            touched = [s.where, s.having] + [it.expr for it in s.items]
            if any(
                e is not None and expr_contains(
                    e, (ScalarSubquery, InSubquery, Exists))
                for e in touched
            ):
                return None
        check = getattr(self.provider, "check_cancelled", None)
        if check is not None:
            check()
        from greptimedb_tpu.query.optimizer import optimize_select
        from greptimedb_tpu.query.physical import grid_plan_candidate

        try:
            ctx = self.provider.table_context(table)
            plans = []
            for s in sels:
                s_opt, _rules = optimize_select(s, ctx)
                plan = plan_select(s_opt, ctx)
                if not grid_plan_candidate(plan) or plan.sliding is not None:
                    return None
                plans.append(plan)
        except (PlanError, Unsupported, TableNotFound):
            return None
        with TRACER.stage("scan_cache"):
            grid, ts_bounds = grid_fn(table, plans[0])
        if grid is None:
            return None
        with TRACER.stage("execute", batch=len(plans)):
            pending = self.executor.execute_grid_batch(
                plans, grid, ts_bounds, metrics=metrics)
        if pending is None:
            return None
        out, finish = pending
        host = fetch_host(out)
        results = []
        with TRACER.stage("materialize", batch=len(plans)):
            for plan, (env, n) in zip(plans, finish(host)):
                results.append(self._shape(plan, env, n))
        return results

    def _execute_expr_key_agg(self, sel: Select, ctx,
                              plan: SelectPlan) -> QueryResult | None:
        """GROUP BY over computed tag expressions (upper(h), length(h),
        concat(h, dc), …): aggregate at raw-tag granularity on device,
        then fold combos sharing one computed key host-side through the
        SHARED merge (rpc/partial.py) — the single-device twin of the
        mesh path's host fold (parallel/dist.py execute_select_on_mesh;
        the reference evaluates expr keys row-wise via DataFusion, here
        rows never leave the device — only (combo × agg) partials do).

        Returns None when not applicable (non-tag references, refused
        split, un-shapeable ORDER BY) — caller falls through to the
        normal path and its error reporting."""
        import dataclasses

        from greptimedb_tpu.query.ast import Column
        from greptimedb_tpu.query.planner import referenced_columns
        from greptimedb_tpu.rpc.partial import merge_partials, split_partial

        if not self._mesh_shapeable(sel):
            return None
        ts_name = (ctx.schema.time_index.name
                   if ctx.schema.time_index is not None else None)
        # HAVING applies AFTER the host fold (its aggregates must be
        # projected outputs so the merged columns carry them)
        having = sel.having
        split_sel = (dataclasses.replace(sel, having=None)
                     if having is not None else sel)
        pplan = split_partial(split_sel, ts_column=ts_name)
        if pplan is None:
            return None
        tag_names = {c.name for c in ctx.schema.tag_columns}
        expr_of_key = {str(k.expr): k for k in plan.group_keys}
        base_tags: list[str] = []
        for k in plan.group_keys:
            if k.kind != "expr":
                continue
            refs: set = set()
            referenced_columns(k.expr, ctx, refs)
            if not refs or not refs <= tag_names:
                return None  # field/ts-dependent keys: no tag fold
            for c in sorted(refs):
                if c not in base_tags:
                    base_tags.append(c)

        # inner query: expr-key items become their base tag columns; the
        # other key items and all partial agg items pass through
        psel = pplan.partial_select
        inner_items = []
        inner_group = [Column(t) for t in base_tags]
        kept_keys: dict[str, str] = {}  # partial key alias -> "expr"|"col"
        for it in psel.items:
            if it.alias in pplan.key_cols:
                gk = expr_of_key.get(str(it.expr))
                if gk is not None and gk.kind == "expr":
                    kept_keys[it.alias] = "expr"
                    continue  # replaced by base tags
                kept_keys[it.alias] = "col"
                inner_items.append(it)
                inner_group.append(Column(it.alias))
            else:
                inner_items.append(it)
        inner_items = [
            SelectItem(Column(t), alias=t) for t in base_tags
        ] + inner_items
        inner_sel = dataclasses.replace(
            psel, items=inner_items, group_by=inner_group)
        res = self.execute_select(inner_sel)

        idx = {n: i for i, n in enumerate(res.column_names)}
        m = len(res.rows)
        env_host = {
            t: np.array([row[idx[t]] for row in res.rows], dtype=object)
            for t in base_tags
        }
        part: dict[str, list] = {}
        for it in psel.items:
            alias = it.alias
            if alias in pplan.key_cols and kept_keys.get(alias) == "expr":
                v = eval_host(it.expr, dict(env_host), m)
                arr = np.asarray(v, dtype=object)
                if arr.ndim == 0:
                    arr = np.full(m, arr.item(), dtype=object)
                part[alias] = arr.tolist()
            else:
                part[alias] = [row[idx[alias]] for row in res.rows]
        names, rows = merge_partials(pplan, [part])
        if having is not None and rows:
            envh = {
                nme: np.array([r[i] for r in rows], dtype=object)
                for i, nme in enumerate(names)
            }
            try:
                keep = np.broadcast_to(np.asarray(
                    eval_host(having, envh, len(rows)), dtype=bool),
                    (len(rows),))
            except Exception:  # noqa: BLE001 — non-projected agg: refuse
                return None
            rows = [r for r, k in zip(rows, keep) if k]
        return self._finish_merged(sel, plan, names, rows)

    @staticmethod
    def _mesh_shapeable(sel: Select) -> bool:
        """The mesh path returns merged rows keyed by OUTPUT names; every
        ORDER BY key must be one (by alias or expression text) or the
        suffix can't be applied here — fall back to single-device."""
        names = {it.output_name for it in sel.items
                 if not isinstance(it.expr, Star)}
        return all(str(o.expr) in names for o in sel.order_by)

    def _finish_merged(self, sel: Select, plan: SelectPlan,
                       names: list[str], rows: list[list]) -> QueryResult:
        """ORDER BY / LIMIT over merged mesh partials (the frontend side
        of MergeScan, same shaping as rpc/frontend.py _shape)."""
        if sel.order_by:
            idx = {n: i for i, n in enumerate(names)}

            def sort_key(row):
                return [SortVal(row[idx[str(ob.expr)]], ob.asc)
                        for ob in sel.order_by]

            rows = sorted(rows, key=sort_key)
        # no OFFSET handling: split_partial refuses OFFSET queries, so
        # none reaches the mesh path
        if sel.limit is not None:
            rows = rows[: sel.limit]
        return QueryResult(names, rows, column_types=[
            _infer_type(it.expr, plan) for it in plan.items
        ])

    def explain(self, sel: Select) -> str:
        if sel.table is None:
            return "Projection (const)"
        ctx = self.provider.table_context(sel.table)
        from greptimedb_tpu.query.optimizer import optimize_select

        sel, opt_rules = optimize_select(sel, ctx)
        plan = plan_select(sel, ctx)
        if plan.time_range != (None, None):
            opt_rules = opt_rules + ["time_range_pushdown"]
        lines = []
        if opt_rules:
            lines.append(f"Optimizer: [{', '.join(opt_rules)}]")
        if plan.limit is not None:
            lines.append(f"Limit: {plan.limit} offset {plan.offset or 0}")
        if plan.order_by:
            keys = ", ".join(
                f"{o.expr} {'ASC' if o.asc else 'DESC'}" for o in plan.order_by
            )
            lines.append(f"Sort: {keys}")
        if plan.having is not None:
            lines.append(f"Having: {plan.having}")
        if plan.is_agg:
            gk = ", ".join(str(k.expr) for k in plan.group_keys)
            strategy = "dense-grid" if all(
                k.kind in ("tag", "time") for k in plan.group_keys
            ) else "sort-ranked"
            lines.append(
                f"TpuAggregate[{strategy}]: groupBy=[{gk}] "
                f"aggr=[{', '.join(map(str, plan.aggs))}]"
            )
        proj = ", ".join(i.output_name for i in plan.items)
        lines.append(f"Projection: {proj}")
        filt = []
        lo, hi = plan.time_range
        if lo is not None or hi is not None:
            filt.append(f"time in [{lo}, {hi})")
        if plan.where is not None:
            filt.append(str(plan.where))
        if filt:
            lines.append(f"Filter: {' AND '.join(filt)}")
        mesh = getattr(self.provider, "mesh", None)
        if mesh is not None:
            lines.append(
                f"TpuScan: table={plan.table} (HBM-resident, series axis "
                f"sharded over {mesh.devices.size}-device mesh, GSPMD "
                "collectives)")
        else:
            lines.append(f"TpuScan: table={plan.table} (HBM-resident, masked)")
        return "\n".join(f"{'  ' * i}{l}" for i, l in enumerate(lines))

    # ------------------------------------------------------------------
    def execute_union(self, union, run_select) -> QueryResult:
        """Set operations: run each member via ``run_select`` (the
        caller's full dispatch, so information_schema members and nested
        set operations work), combine per ``union.op`` —
        UNION concatenates (dedup unless ALL); INTERSECT keeps left rows
        present on the right (ALL: min multiplicity); EXCEPT keeps left
        rows absent from the right (ALL: left-minus-right multiplicity,
        left order preserved) — then apply the statement-level ORDER
        BY/LIMIT."""
        results = [run_select(s) for s in union.selects]
        ncols = len(results[0].column_names)
        for r in results[1:]:
            if len(r.column_names) != ncols:
                raise PlanError(
                    f"{union.op.upper()} members have {ncols} vs "
                    f"{len(r.column_names)} columns"
                )
        op = getattr(union, "op", "union")
        if op == "union":
            rows = [row for r in results for row in r.rows]
            if not union.all:
                seen: set = set()
                deduped = []
                for row in rows:
                    key = tuple(row)
                    if key not in seen:
                        seen.add(key)
                        deduped.append(row)
                rows = deduped
        else:
            rows = self._set_op_rows(op, union.all, results)
        res = QueryResult(results[0].column_names, rows,
                          column_types=results[0].column_types)
        if union.order_by:
            idx = {n: i for i, n in enumerate(res.column_names)}

            def sort_key(row):
                key = []
                for ob in union.order_by:
                    name = str(ob.expr)
                    if name not in idx:
                        raise PlanError(
                            f"ORDER BY {name}: not a UNION output column"
                        )
                    key.append(SortVal(row[idx[name]], ob.asc))
                return key

            res.rows.sort(key=sort_key)
        if union.offset:
            res.rows[:] = res.rows[union.offset:]
        if union.limit is not None:
            res.rows[:] = res.rows[: union.limit]
        return res

    @staticmethod
    def _set_op_rows(op: str, all_: bool, results: list) -> list[list]:
        """INTERSECT/EXCEPT over exactly two member results (the parser
        nests longer chains left-associatively).  DISTINCT semantics
        dedup the output; ALL keeps multiplicities (min for INTERSECT,
        left-minus-right for EXCEPT).  Left member order is preserved."""
        import collections

        left, right = results[0].rows, results[1].rows
        rcount = collections.Counter(tuple(r) for r in right)
        out: list[list] = []
        if op == "intersect":
            if all_:
                budget = dict(rcount)
                for row in left:
                    k = tuple(row)
                    if budget.get(k, 0) > 0:
                        budget[k] -= 1
                        out.append(row)
            else:
                seen: set = set()
                for row in left:
                    k = tuple(row)
                    if k in rcount and k not in seen:
                        seen.add(k)
                        out.append(row)
        else:  # except
            if all_:
                budget = dict(rcount)
                for row in left:
                    k = tuple(row)
                    if budget.get(k, 0) > 0:
                        budget[k] -= 1
                    else:
                        out.append(row)
            else:
                seen = set()
                for row in left:
                    k = tuple(row)
                    if k not in rcount and k not in seen:
                        seen.add(k)
                        out.append(row)
        return out

    def _execute_tableless(self, sel: Select) -> QueryResult:
        env: dict[str, np.ndarray] = {}
        names: list[str] = []
        row: list[object] = []
        for item in sel.items:
            if isinstance(item.expr, Star):
                raise PlanError("SELECT * without FROM")
            from greptimedb_tpu.query.ast import FuncCall, Literal

            e = item.expr
            if isinstance(e, FuncCall) and e.name == "version":
                v = "greptimedb-tpu-0.1.0"
            elif isinstance(e, FuncCall) and e.name in ("now", "current_timestamp"):
                import time as _time

                v = int(_time.time() * 1000)
            elif isinstance(e, FuncCall) and e.name in ("database", "current_schema"):
                v = "public"
            else:
                v = eval_host(e, env, 1)
                if isinstance(v, np.ndarray):
                    v = v.item() if v.size == 1 else v.tolist()
            names.append(item.output_name)
            row.append(v)
        return QueryResult(names, [row])

    def _shape(self, plan: SelectPlan, env: dict[str, np.ndarray], n: int) -> QueryResult:
        ctx = plan.ctx
        # host date functions (date_trunc/date_part/…) need the table's
        # timestamp unit; stash the native→ms factor in the eval env
        try:
            env.setdefault("__ts_factor__", ctx.ts_unit_ms_factor())
        except Exception:  # noqa: BLE001 — no time index
            pass
        # expand stars
        items: list[SelectItem] = []
        for item in plan.items:
            if isinstance(item.expr, Star):
                if plan.is_agg:
                    raise PlanError("SELECT * with GROUP BY")
                from greptimedb_tpu.query.ast import Column

                for c in ctx.schema:
                    if c.name.startswith("__") and c.name.endswith("__"):
                        continue  # internal (join row ids, engine columns)
                    items.append(SelectItem(Column(c.name)))
            else:
                items.append(item)

        # window functions: compute each once into env (eval_host then
        # resolves WindowFunc nodes by name)
        wfs: list = []
        for item in items:
            if not isinstance(item.expr, Star):
                collect_windows(item.expr, wfs)
        for o in plan.order_by:
            collect_windows(o.expr, wfs)
        if wfs:
            if plan.is_agg:
                raise PlanError(
                    "window functions over GROUP BY results are not"
                    " supported; wrap the aggregate in a subquery")
            for wf in wfs:
                env[str(wf)] = compute_window(wf, env, n, eval_host)

        out_cols: dict[str, np.ndarray] = {}
        for item in items:
            key = item.output_name
            v = eval_host(item.expr, env, n)
            arr = np.asarray(v, dtype=object if isinstance(v, str) else None)
            if arr.ndim == 0:
                arr = np.full(n, arr.item() if arr.dtype != object else v)
            out_cols[key] = arr
            env.setdefault(key, arr)
            env.setdefault(str(item.expr), arr)

        keep = np.ones(n, dtype=bool)
        if plan.having is not None:
            keep &= np.asarray(eval_host(plan.having, env, n), dtype=bool)
        idx = np.nonzero(keep)[0]

        names = [i.output_name for i in items]
        if plan.distinct:
            seen: set = set()
            uniq = []
            for i in idx.tolist():
                k = tuple(_pyval(out_cols[name][i]) for name in names)
                if k not in seen:
                    seen.add(k)
                    uniq.append(i)
            idx = np.array(uniq, dtype=np.int64)

        if plan.order_by:
            sort_cols = []
            for o in plan.order_by:
                v = np.asarray(eval_host(o.expr, env, n), dtype=object)
                if v.ndim == 0:
                    v = np.full(n, v.item(), dtype=object)
                sort_cols.append((v, o.asc, o.nulls_first))

            def key_fn(i: int):
                parts = []
                for v, asc, nf in sort_cols:
                    nr, val = _null_key(v[i], asc, nf)
                    parts.append((nr, _Reversed(val) if not asc else val))
                return tuple(parts)

            idx = np.array(sorted(idx.tolist(), key=key_fn), dtype=np.int64)

        if plan.offset:
            idx = idx[plan.offset:]
        if plan.limit is not None:
            idx = idx[: plan.limit]

        # whole columns, no Python object a cell: the rows are built when
        # somebody reads them (QueryResult.rows)
        return QueryResult(
            names, column_types=[_infer_type(item.expr, plan)
                                 for item in items],
            columns=ColumnRows([out_cols[name][idx] for name in names]))


def _apply_sliding(plan: SelectPlan, env: dict, n: int) -> tuple[dict, int]:
    """Combine s-wide tumbling partials into sliding [t, t+w) windows
    (reference range_select semantics: RANGE w evaluated at each ALIGN step).
    Partial volumes are small (groups x buckets), so this runs on host."""
    import collections

    w, s = plan.sliding
    k = w // s
    time_key = next(g for g in plan.group_keys if g.kind == "time")
    tag_keys = [g for g in plan.group_keys if g is not time_key]
    partial_names = sorted({p for parts in plan.sliding_rewrites.values()
                            for p in parts})

    groups: dict = collections.defaultdict(dict)  # tag values -> {bucket: i}
    for i in range(n):
        tags = tuple(env[str(g.expr)][i] for g in tag_keys)
        groups[tags][int(env[str(time_key.expr)][i])] = i

    out_rows: list[tuple] = []  # (tags, t, {partial: combined})
    for tags, buckets in groups.items():
        window_starts = sorted({
            b - j * s for b in buckets for j in range(k)
        })
        for t0 in window_starts:
            window = [buckets[t0 + j * s] for j in range(k)
                      if (t0 + j * s) in buckets]
            combined = {}
            for p in partial_names:
                vals = [env[p][i] for i in window]
                vals = [v for v in vals if not (
                    isinstance(v, float) and np.isnan(v))]
                if not vals:
                    combined[p] = np.nan
                elif p.startswith(("sum(", "count(")):
                    combined[p] = sum(vals)
                elif p.startswith("min("):
                    combined[p] = min(vals)
                elif p.startswith("max("):
                    combined[p] = max(vals)
            out_rows.append((tags, t0, combined))

    m = len(out_rows)
    new_env: dict[str, np.ndarray] = {}
    for gi, g in enumerate(tag_keys):
        col = np.array([r[0][gi] for r in out_rows], dtype=object)
        new_env[g.name] = col
        new_env[str(g.expr)] = col
    tcol = np.array([r[1] for r in out_rows], dtype=np.int64)
    new_env[time_key.name] = tcol
    new_env[str(time_key.expr)] = tcol
    for p in partial_names:
        new_env[p] = np.array([r[2].get(p, np.nan) for r in out_rows])
    # reconstruct the original aggregates (avg = sum/count)
    for orig, parts in plan.sliding_rewrites.items():
        if orig in new_env:
            continue
        if orig.startswith(("avg(", "mean(")):
            s_arr = new_env[parts[0]].astype(float)
            c_arr = new_env[parts[1]].astype(float)
            new_env[orig] = np.where(c_arr > 0, s_arr / np.maximum(c_arr, 1),
                                     np.nan)
        else:
            new_env[orig] = new_env[parts[0]]
    return new_env, m


def _infer_type(expr, plan: SelectPlan) -> str:
    """Greptime type name for an output expression (best effort)."""
    from greptimedb_tpu.query.ast import (
        BinaryOp, Case, Cast, Column, FuncCall, Literal,
    )

    ctx = plan.ctx
    for k in plan.group_keys:
        if str(k.expr) == str(expr):
            if k.kind == "tag":
                return "String"
            if k.kind == "time":
                return ctx.schema.time_index.dtype.value if ctx.schema.time_index else "Int64"
    if isinstance(expr, Column):
        try:
            return ctx.schema.column(ctx.resolve(expr.name)).dtype.value
        except Exception:  # noqa: BLE001
            return "String"
    if isinstance(expr, FuncCall):
        if expr.name == "count":
            return "Int64"
        if expr.name in ("sum", "min", "max", "first_value", "last_value"):
            if expr.args and isinstance(expr.args[0], Column):
                return _infer_type(expr.args[0], plan)
            return "Float64"
        if expr.name in ("date_bin", "date_trunc"):
            return ctx.schema.time_index.dtype.value if ctx.schema.time_index else "Int64"
        return "Float64"
    from greptimedb_tpu.query.ast import WindowFunc as _WF
    if isinstance(expr, _WF):
        if expr.name in ("row_number", "rank", "dense_rank", "ntile",
                         "count"):
            return "Int64"
        if expr.name in ("lag", "lead", "first_value", "last_value", "sum",
                         "min", "max") and expr.args and isinstance(
                             expr.args[0], Column):
            return _infer_type(expr.args[0], plan)
        return "Float64"
    if isinstance(expr, Literal):
        v = expr.value
        if isinstance(v, bool):
            return "Boolean"
        if isinstance(v, int):
            return "Int64"
        if isinstance(v, float):
            return "Float64"
        return "String"
    if isinstance(expr, Cast):
        from greptimedb_tpu.datatypes.types import ConcreteDataType

        try:
            return ConcreteDataType.parse(expr.type_name).value
        except ValueError:
            return "String"
    if isinstance(expr, Case):
        return "String"
    if isinstance(expr, BinaryOp):
        if expr.op.upper() in ("AND", "OR", "=", "!=", "<", "<=", ">", ">=",
                               "LIKE", "ILIKE"):
            return "Boolean"
        return "Float64"
    return "Float64"


class SortVal:
    """Total-orderable sort-key wrapper for host-side row ordering:
    None/NaN sort last, per-key direction."""

    __slots__ = ("v", "asc")

    def __init__(self, v, asc: bool):
        self.v = v
        self.asc = asc

    def _rank(self):
        missing = self.v is None or (
            isinstance(self.v, float) and self.v != self.v
        )
        return (1 if missing else 0, 0 if missing else self.v)

    def __lt__(self, other):
        a, b = self._rank(), other._rank()
        if a[0] != b[0]:
            return a[0] < b[0]
        if a[1] == b[1]:
            return False
        return (a[1] < b[1]) if self.asc else (a[1] > b[1])

    def __eq__(self, other):
        return self._rank() == other._rank()


class _Reversed:
    """Inverts comparison for DESC sort keys."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v


def _pyval(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        f = float(v)
        return None if np.isnan(f) else f
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.str_):
        return str(v)
    return v
