"""Whole-plan fusion of the PromQL selection→window→group chain.

The unfused evaluator runs `sum by (pod) (rate(m[5m]))` as one jitted
window kernel plus a tail of EAGER device ops with host glue: the
extrapolation epilogue (`_extrapolated`) and the cross-series segment
reduction each dispatch separately.  This module lowers the whole chain
— window stats over the matched series' slab of the presorted resident
layout, the function epilogue, and the group reduction — into ONE
jitted XLA program per shape class, so a warm aggregation is a single
device dispatch (Data Path Fusion, arXiv 2605.10511).

Bit-exactness contract: the fused program COMPOSES the evaluator's own
building blocks — ``_window_body`` (the exact function ``_window_kernel``
jits), ``_extrapolated`` / ``_instant_pair``, and the same segment
arithmetic ``eval_aggregation`` runs eagerly — inside one jit.  Padding
rows (series slots beyond the matched set) carry NaN/absent stats, so
they contribute +0 to every segment sum and ±inf fills to min/max, and
their group ids route to a dead overflow segment; per-group floats are
therefore identical to the unfused path (pinned by the fusion parity
fuzz in tests/test_compile_cache.py).  Anything outside the fused
surface — pinned ``@`` selectors, subqueries, quantile/topk, label-
transformed inputs — returns None and the evaluator falls back to the
multi-kernel path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from greptimedb_tpu.compile import named_jit
from greptimedb_tpu.errors import TableNotFound
from greptimedb_tpu.utils.tracing import TRACER, count_histogram_dispatch

# diagnostics: fused dispatches this process (tests/bench read it)
FUSED_DISPATCHES = {"count": 0}

# function → window-kernel kind, mirroring eval_function's routing.
# None = a bare instant selector under the aggregation.
_FUNC_KIND = {
    None: "instant",
    "rate": "counter", "increase": "counter", "delta": "counter",
    "irate": "irate", "idelta": "irate",
    "resets": "counter_rc", "changes": "counter_rc",
    "avg_over_time": "gauge_window", "sum_over_time": "gauge_window",
    "count_over_time": "gauge_window", "last_over_time": "gauge_window",
    "first_over_time": "gauge_window", "stddev_over_time": "gauge_window",
    "stdvar_over_time": "gauge_window", "present_over_time": "gauge_window",
    "min_over_time": "minmax", "max_over_time": "minmax",
    "deriv": "regression",
}
# functions whose selector must carry an explicit [range]
_NEEDS_RANGE = {
    "rate", "increase", "delta", "irate", "idelta", "resets", "changes",
    "avg_over_time", "sum_over_time", "count_over_time", "last_over_time",
    "first_over_time", "stddev_over_time", "stdvar_over_time",
    "present_over_time", "min_over_time", "max_over_time", "deriv",
}
# stddev/stdvar are deliberately NOT fused: their v²−mean² form
# catastrophically cancels, so XLA's FMA contraction inside a fused
# program produces visibly different floats than the eager op sequence —
# cancellation-sensitive ops stay on the multi-kernel path
_FUSED_AGGS = {"sum", "avg", "count", "group", "min", "max"}


def _apply_func(func, p, out, start_ms, range_s):
    """The function epilogue over raw window stats — each branch is the
    evaluator's own eager code, here traced into the fused program."""
    from greptimedb_tpu.promql import engine as pe

    if func is None:  # instant selector: staleness-windowed last sample
        return out["last"]
    if func in ("rate", "increase", "delta"):
        # non-pinned grid: range_end = start + step * t, exactly the
        # np.float64 vector the unfused path builds (i64→f64 is exact
        # for epoch-ms, so the traced form is bit-identical)
        range_end = start_ms + p.step_ms * jnp.arange(
            p.num_steps, dtype=jnp.int64)
        return pe._extrapolated(out, range_s, range_end,
                                counter=func != "delta",
                                is_rate=func == "rate")
    if func in ("irate", "idelta"):
        return pe._instant_pair(func, out["last_ts"], out["prev_ts"],
                                out["last_val"], out["prev_val"])
    if func in ("resets", "changes"):
        return out[func]
    if func in ("min_over_time", "max_over_time"):
        return out["min" if func == "min_over_time" else "max"]
    if func == "deriv":
        return out["slope"]
    # gauge_window family — the exact table eval_function builds
    present = ~jnp.isnan(out["last"])
    table = {
        "avg_over_time": lambda: out["avg"],
        "sum_over_time": lambda: out["sum"],
        "count_over_time": lambda: jnp.where(present, out["count"],
                                             jnp.nan),
        "last_over_time": lambda: out["last"],
        "first_over_time": lambda: out["first"],
        "stddev_over_time": lambda: jnp.sqrt(out["var"]),
        "stdvar_over_time": lambda: out["var"],
        "present_over_time": lambda: jnp.where(present, 1.0, jnp.nan),
    }
    return table[func]()


def _build_fused(p, func, op, ng, n_sel, range_s, hist=None):  # gl: warm-path
    """One program: window stats → epilogue → group reduce.  Returned
    unjitted; the caller jits (and AOT-persists) it.  With ``hist`` =
    (q, K) the ``ng`` = G·K sums are the buckets of G histograms, folded
    to their q-quantile (``histogram_fold``) in the same program, which
    then takes the K bounds as a fifth argument."""
    from greptimedb_tpu.promql import engine as pe

    body = pe._window_body(p)
    S = p.num_sel

    def fused(layout, sel_tsids, start_ms, gid):
        # gid: [n_sel] i32 group ids (dense, first-appearance)
        out = body(layout, sel_tsids, start_ms)
        v = _apply_func(func, p, out, start_ms, range_s)  # [S, T]
        pad = S - n_sel
        gid_full = (
            jnp.concatenate([gid, jnp.full((pad,), ng, gid.dtype)])
            if pad else gid
        )

        def gseg(x, segf=jax.ops.segment_sum):
            # padding rows route to the dead overflow segment ng
            return segf(x, gid_full, num_segments=ng + 1)[:ng]

        # below mirrors eval_aggregation's eager math verbatim
        present = ~jnp.isnan(v)
        cnt = gseg(present.astype(jnp.int32))
        fcnt = cnt.astype(jnp.float32)
        has = cnt > 0
        if op in ("sum", "avg", "count", "group"):
            s = gseg(jnp.where(present, v, 0))
            if op == "sum":
                return jnp.where(has, s, jnp.nan)
            if op == "avg":
                return jnp.where(has, s / jnp.maximum(fcnt, 1), jnp.nan)
            if op == "count":
                return jnp.where(has, fcnt, jnp.nan)
            return jnp.where(has, 1.0, jnp.nan)  # group
        fill = jnp.inf if op == "min" else -jnp.inf
        segf = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        red = gseg(jnp.where(present, v, fill), segf)
        return jnp.where(has, red, jnp.nan)

    if hist is None:
        return fused
    q, k = hist

    def fused_histogram(layout, sel_tsids, start_ms, gid, bounds):
        sums = fused(layout, sel_tsids, start_ms, gid)  # [G·K, T]
        return pe.histogram_fold(sums.reshape(ng // k, k, -1), bounds, q)

    return fused_histogram


def _chain(e):
    """(function, selector) of an aggregation over one window function
    of one selector — the chain the fused program runs — or None."""
    from greptimedb_tpu.promql.parser import FunctionCall, VectorSelector

    inner = e.expr
    func = None
    if type(inner) is VectorSelector:
        if inner.range_s is not None:
            return None  # bare range vector: unfused raises the error
        sel = inner
    elif isinstance(inner, FunctionCall):
        func = inner.func
        if func not in _FUNC_KIND or len(inner.args) != 1:
            return None
        sel = inner.args[0]
        if type(sel) is not VectorSelector:
            return None  # subqueries and nested exprs: multi-kernel path
        if func in _NEEDS_RANGE and sel.range_s is None:
            return None  # unfused raises the canonical PlanError
    else:
        return None
    if sel.at_ts is not None:
        return None  # pinned @: broadcast semantics stay unfused
    return func, sel


def _prepare(ev, func, sel):
    """``_prep_window``'s (args, p, tsids, labels), or None where the
    chain stays unfused (unknown metric, pinned, nothing selected)."""
    try:
        prep = ev._prep_window(sel, _FUNC_KIND[func])
    except TableNotFound:
        return None  # unknown metric: unfused produces the empty vector
    args, p, tsids, labels, pinned, _start, _rng = prep
    if pinned or len(tsids) == 0:
        return None
    return args, p, tsids, labels


def _dispatch(ev, key, build, args, tsids, gid_dev, p, extra=(), **attrs):
    """Run the fused program of ``key`` (built by ``build`` on a miss)
    over the window arguments and the group ids (+ ``extra``)."""
    from greptimedb_tpu.promql import engine as pe

    kern = pe._KERNEL_CACHE.get(key)
    jit_miss = kern is None
    if kern is None:
        from greptimedb_tpu.compile.service import default_compiler

        compiler = getattr(ev.db, "plan_compiler", None) or \
            default_compiler()
        kern = compiler.get_or_build("promql", key, build, persist=True)
        pe._KERNEL_CACHE[key] = kern
    fused_args = args + (gid_dev,)
    mesh = getattr(ev.db, "mesh", None)
    if mesh is not None and mesh.devices.size > 1:
        # canonical placement: the resident sort layout is row-sharded
        # (parallel/dist.py promql_row_shardings) while a transient
        # (cache-off / quota-rejected) build sits on one device — two
        # placements would compile two DIFFERENT fused programs whose
        # cross-device reduce order differs in the last ulp.  Re-place
        # every row-axis array by the cache's own rule so cached and
        # uncached evaluations run the IDENTICAL program (device_put on
        # an already-correctly-placed array is a no-op).
        from greptimedb_tpu.parallel.dist import promql_row_shardings

        def place(a):
            if getattr(a, "ndim", 0) >= 1:
                sh = promql_row_shardings(mesh, int(a.shape[0]))
                if sh is not None:
                    return jax.device_put(a, sh["rows"])
            return a

        fused_args = jax.tree.map(place, fused_args)
    # AOT-store hits deserialize — first call is NOT an XLA compile
    compiling = jit_miss and not getattr(kern, "aot", False)
    pe.count_dispatch(p, args, len(tsids))
    vals = ev._timed_kernel(
        "fused_kernel", lambda: kern(*fused_args, *extra), jit_miss,
        compiling, **attrs)
    from greptimedb_tpu.compile.service import M_FUSED_DISPATCH

    M_FUSED_DISPATCH.labels("promql").inc()
    FUSED_DISPATCHES["count"] += 1
    return vals


def try_fused_aggregation(ev, e):
    """Fused evaluation of one Aggregation node, or None (evaluator
    falls back to the multi-kernel path).  ``ev`` is the PromEvaluator."""
    from greptimedb_tpu.promql import engine as pe

    chain = _chain(e)
    if chain is None or e.op not in _FUSED_AGGS or e.param is not None:
        return None
    func, sel = chain
    prep = _prepare(ev, func, sel)
    if prep is None:
        return None
    args, p, tsids, labels = prep
    with TRACER.stage("group_agg", op=e.op) as st:
        gid_dev, ng, out_labels, _ro, _ss = ev._group_series_of(
            e, labels, len(tsids))
    ev._stage_mark("group_agg", st)
    range_s = sel.range_s if func in _NEEDS_RANGE else None
    key = ("promql_fused", p, func, e.op, ng, len(tsids), range_s)
    vals = _dispatch(
        ev, key,
        lambda: named_jit(f"promql_fused_{e.op}")(_build_fused(
            p, func, e.op, ng, len(tsids), range_s)),
        args, tsids, gid_dev, p, op=e.op, func=func or "instant")
    return pe.EvalResult(vals, out_labels)


def try_fused_histogram(ev, e, q: float):
    """``histogram_quantile(q, sum by (le, …)(<window fn>(sel[r])))`` as
    ONE program, or None (the evaluator folds the evaluated argument).
    The series are numbered o·K + k (outer group, bucket slot; promql/
    engine.py ``_series_bucket_slots``, cached with the group ids), so the
    program's own ``sum`` segment reduce yields the [G·K, T] buckets, and
    the fold follows in the same program.  Its key is the ``sum``
    program's with the quantile's (q, K, G) appended; every other key,
    program and output is the one ``try_fused_aggregation`` builds."""
    from greptimedb_tpu.promql import engine as pe
    from greptimedb_tpu.promql.parser import Aggregation

    agg = e.args[1]
    if (type(agg) is not Aggregation or agg.op != "sum" or agg.without
            or agg.param is not None or "le" not in (agg.grouping or ())):
        return None
    chain = _chain(agg)
    if chain is None:
        return None
    func, sel = chain
    prep = _prepare(ev, func, sel)
    if prep is None:
        return None
    args, p, tsids, labels = prep
    with TRACER.stage("group_agg", op="histogram_quantile") as st:
        slots = ev._bucket_slots_of(agg, labels, len(tsids))
    ev._stage_mark("group_agg", st)
    if slots is None:
        return None
    gid_dev, g, k, bounds_dev, out_labels, filled = slots
    ng = g * k
    range_s = sel.range_s if func in _NEEDS_RANGE else None
    spec = ("histogram_quantile", q if q == q else "NaN", k, g)
    key = ("promql_fused", p, func, "sum", ng, len(tsids), range_s, spec)
    count_histogram_dispatch("fused", g, k, filled)
    vals = _dispatch(
        ev, key,
        lambda: named_jit("promql_fused_histogram_quantile")(_build_fused(
            p, func, "sum", ng, len(tsids), range_s, hist=(q, k))),
        args, tsids, gid_dev, p, extra=(bounds_dev,), op="sum",
        func="histogram_quantile", groups=g, buckets=k)
    return pe.EvalResult(vals, out_labels)
