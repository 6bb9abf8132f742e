"""The PromQL window program on the matched series' slab.

Every window kind is served through ``PromEvaluator`` and compared with
a plain numpy reference that loops over series and windows (Prometheus
semantics, written here from the definitions and sharing nothing with
the engine), over data that stresses the slab's geometry: a regular
scrape (a narrow slab inside long series), irregular timestamps (the
slab falls to its cap, the whole series row), series shorter than the
slab and empty ones, counters that reset on both sides of the slab's
first column, windows wholly before the first and after the last
sample, ``offset`` and ``@``, and series laid so that each one's first
readable row falls at a chosen place of its 128-row chunk (the fold's
seam, ``Slab.fold``).  Stored DOUBLEs compute in f32 on the
device, so the reference rounds its inputs to f32 and the comparison is
by a tolerance suited to f32.
"""

import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.promql import engine as pe
from greptimedb_tpu.promql.parser import parse_promql
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import REGISTRY

RANGE_S = 300
LOOKBACK_MS = 300_000
T0_S = 10_000  # first scrape of every data set, seconds


@pytest.fixture
def db(monkeypatch):
    """One device: this file tests the slab's geometry, not its placement.
    On the harness's eight virtual CPU devices the layout is row-sharded
    (parallel/dist.py ``promql_row_shardings``), so every window program
    opens with an in-process all-gather that needs all eight device
    threads at once; under six xdist workers two of them can fail to
    arrive, and XLA's ``rendezvous.cc`` aborts the worker after 60 s."""
    monkeypatch.setenv("GREPTIME_MESH", "off")
    d = GreptimeDB()
    assert d.mesh is None
    yield d
    d.close()


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def _counter(rng, n, reset_every):
    """A counter in f32-exact steps that resets every ``reset_every``
    samples, so resets land on both sides of any slab's first column."""
    inc = rng.integers(1, 40, n).astype(np.float64)
    out = np.empty(n)
    acc = 0.0
    for i in range(n):
        acc = inc[i] if reset_every and i and i % reset_every == 0 \
            else acc + inc[i]
        out[i] = acc
    return out


def _series(kind: str):
    """{name: (ts_ms int64, values float64)} of one data set."""
    rng = np.random.default_rng(31)
    t0 = T0_S * 1000
    if kind == "regular":
        # 15 s scrape, phases staggered, 240 samples = 1 h
        return {
            f"s{i}": (t0 + 1000 * i + 15_000 * np.arange(240),
                      _counter(rng, 240, (0, 7, 11, 0, 5, 13)[i]))
            for i in range(6)}
    if kind == "irregular":
        # gaps from 1 ms to 40 s: the densest spacing is 1 ms
        out = {}
        for i in range(5):
            gaps = rng.integers(2_000, 40_000, 150)
            gaps[17 * (i + 1)] = 1
            out[f"s{i}"] = (t0 + np.cumsum(gaps),
                            _counter(rng, 150, (0, 9, 4, 0, 6)[i]))
        return out
    if kind == "lengths":
        # longer than the slab, much shorter than it, and empty
        out = {}
        for i, n in enumerate((240, 3, 50, 1, 200)):
            out[f"s{i}"] = (t0 + 15_000 * np.arange(n),
                            _counter(rng, n, (8, 0, 5, 0, 0)[i]))
        out["empty"] = (t0 + 15_000 * np.arange(4), np.full(4, np.nan))
        return out
    if kind in ("run127", "run128"):
        # the longest run one under and exactly a power of two: the
        # first-sample search has as many rounds as that length has bits
        # (``search_bits``), and the windows lie at the run's far end
        longest = int(kind[3:])
        return {
            f"s{i}": (t0 + 15_000 * np.arange(n),
                      _counter(rng, n, (0, 9, 0, 6)[i]))
            for i, n in enumerate((longest, longest - 1, 64, 2))}
    if kind == "aligned":
        # one 15 s scrape in one phase; the runs' lengths put each series'
        # row ``FOLD_CUT`` (its first readable one on the fold grids) at
        # column 0, 1, 64 and 127 of a 128-row chunk of the layout: the
        # fold's seam falls at the slab's head, its middle and its end
        lengths = dict(zip(("p", "s0", "s1", "s2", "s3"),
                           (88, 641, 575, 575, 600)))
        return {
            name: (t0 + 15_000 * np.arange(n),
                   _counter(rng, n, (0, 7, 0, 11, 5)[i]))
            for i, (name, n) in enumerate(lengths.items())}
    raise AssertionError(kind)


# samples of an "aligned" series at or before ``start − range`` of the fold
# grids, and the chunk columns their first readable rows are laid at
FOLD_CUT = 40
FOLD_START_S = T0_S + RANGE_S + 15 * (FOLD_CUT - 1)
FOLD_COLUMNS = {"s0": 0, "s1": 1, "s2": 64, "s3": 127}


def load(db, data, table="m", lift=0.0):
    """``lift`` is added to every value: past 2^24 the resident column
    keeps its low word and the sort layout is the wide one."""
    db.sql(f"CREATE TABLE {table} (name STRING, ts TIMESTAMP(3) TIME INDEX, "
           f"val DOUBLE, PRIMARY KEY (name))")
    r = db._region_of(table)
    for name, (ts, vals) in data.items():
        r.write({"name": [name] * len(ts), "ts": np.asarray(ts, np.int64),
                 "val": np.asarray(vals, np.float64) + lift})


# scenario -> (data set, grid (start_s, end_s, step_s), selector suffix,
#              offset_ms, pinned @ seconds or None)
SCENARIOS = {
    "regular": ("regular", (T0_S + 900, T0_S + 1500, 60), "", 0, None),
    "irregular": ("irregular", (T0_S + 600, T0_S + 1500, 75), "", 0, None),
    "lengths": ("lengths", (T0_S + 30, T0_S + 930, 60), "", 0, None),
    # the grid starts two ranges before the first sample and ends two
    # ranges after the last: windows wholly outside the data on both sides
    "outside": ("regular", (T0_S - 700, T0_S + 3600 + 700, 100), "", 0,
                None),
    "run127": ("run127", (T0_S + 1500, T0_S + 1980, 60), "", 0, None),
    "run128": ("run128", (T0_S + 1500, T0_S + 1980, 60), "", 0, None),
    "offset": ("regular", (T0_S + 900, T0_S + 1500, 60), " offset 90s",
               90_000, None),
    "at": ("regular", (T0_S + 900, T0_S + 1140, 60), f" @ {T0_S + 2000}",
           0, T0_S + 2000),
    # W under one chunk (64), one chunk (128) and four (512); windows of
    # 20 samples straddle the fold's seam wherever it falls inside W
    "fold64": ("aligned", (FOLD_START_S, FOLD_START_S + 600, 60), "", 0,
               None),
    "fold128": ("aligned", (FOLD_START_S, FOLD_START_S + 1500, 60), "", 0,
                None),
    "fold512": ("aligned", (FOLD_START_S, FOLD_START_S + 6600, 120), "", 0,
                None),
}

# kind -> [(function, takes a [range])]
KIND_FUNCS = {
    "instant": [("", False)],
    "counter": [("rate", True), ("increase", True), ("delta", True)],
    "counter_rc": [("resets", True), ("changes", True)],
    "gauge_window": [("sum_over_time", True), ("avg_over_time", True),
                     ("count_over_time", True), ("stddev_over_time", True),
                     ("last_over_time", True), ("first_over_time", True)],
    "regression": [("deriv", True)],
    "irate": [("irate", True), ("idelta", True)],
    "minmax": [("min_over_time", True), ("max_over_time", True)],
}


# ---------------------------------------------------------------------------
# the plain reference: one series, one window at a time
# ---------------------------------------------------------------------------

def _window(ts, vals, t_ms, range_ms):
    """Samples in (t − range, t], NULLs (NaN) left out."""
    keep = (ts > t_ms - range_ms) & (ts <= t_ms) & ~np.isnan(vals)
    return ts[keep], vals[keep].astype(np.float32).astype(np.float64)


def _extrapolated(wt, wv, t_ms, range_ms, counter, is_rate):
    if len(wt) < 2:
        return math.nan
    delta = wv[-1] - wv[0]
    if counter:
        delta += sum(wv[i - 1] for i in range(1, len(wv))
                     if wv[i] < wv[i - 1])
    sampled = (wt[-1] - wt[0]) / 1000.0
    avg = sampled / (len(wt) - 1)
    to_start = (wt[0] - (t_ms - range_ms)) / 1000.0
    to_end = (t_ms - wt[-1]) / 1000.0
    if to_start >= avg * 1.1:
        to_start = avg / 2
    if to_end >= avg * 1.1:
        to_end = avg / 2
    if counter and delta > 0:
        to_start = min(to_start, sampled * (wv[0] / delta))
    out = delta * (sampled + to_start + to_end) / sampled
    return out / (range_ms / 1000.0) if is_rate else out


def ref_point(func, ts, vals, t_ms, range_ms, origin_ms):
    """One output point of ``func`` for one series at evaluation time
    ``t_ms``; ``origin_ms`` is the grid's start (deriv's time origin)."""
    if func == "":
        wt, wv = _window(ts, vals, t_ms, LOOKBACK_MS)
        return wv[-1] if len(wv) else math.nan
    wt, wv = _window(ts, vals, t_ms, range_ms)
    n = len(wv)
    if func in ("rate", "increase", "delta"):
        return _extrapolated(wt, wv, t_ms, range_ms, func != "delta",
                             func == "rate")
    if func in ("resets", "changes"):
        if n == 0:
            return math.nan
        pairs = zip(wv[:-1], wv[1:])
        return float(sum((a > b) if func == "resets" else (a != b)
                         for a, b in pairs))
    if func in ("irate", "idelta"):
        if n < 2 or wt[-1] == wt[-2]:
            return math.nan
        dv = wv[-1] - wv[-2]
        if func == "idelta":
            return dv
        if dv < 0:
            dv = wv[-1]
        return dv / ((wt[-1] - wt[-2]) / 1000.0)
    if func == "deriv":
        if n < 2:
            return math.nan
        x = (wt - origin_ms) / 1000.0
        den = n * (x * x).sum() - x.sum() ** 2
        if den == 0:
            return math.nan
        return (n * (x * wv).sum() - x.sum() * wv.sum()) / den
    if n == 0:
        return math.nan
    return {
        "sum_over_time": lambda: wv.sum(),
        "avg_over_time": lambda: wv.mean(),
        "count_over_time": lambda: float(n),
        "stddev_over_time": lambda: math.sqrt(
            max((wv * wv).mean() - wv.mean() ** 2, 0.0)),
        "last_over_time": lambda: wv[-1],
        "first_over_time": lambda: wv[0],
        "min_over_time": lambda: wv.min(),
        "max_over_time": lambda: wv.max(),
    }[func]()


def reference(func, data, grid, offset_ms, at_s):
    start_s, end_s, step_s = grid
    steps = np.arange(start_s * 1000, end_s * 1000 + 1, step_s * 1000)
    out = {}
    for name, (ts, vals) in data.items():
        ts = np.asarray(ts, np.int64)
        vals = np.asarray(vals, np.float64)
        if at_s is not None:  # one evaluation, shown at every step
            t_eval = at_s * 1000 - offset_ms
            v = ref_point(func, ts, vals, t_eval, RANGE_S * 1000, t_eval)
            out[name] = np.full(len(steps), v)
        else:
            out[name] = np.array([
                ref_point(func, ts, vals, int(t) - offset_ms,
                          RANGE_S * 1000, int(steps[0]) - offset_ms)
                for t in steps])
    return out


def served(db, query, grid):
    ev = pe.PromEvaluator(db, *grid)
    res = ev.eval(parse_promql(query))
    vals = np.asarray(res.values, np.float64)
    return {lab["name"]: vals[i] for i, lab in enumerate(res.labels)}, ev


def _query(func, ranged, suffix):
    sel = f"m[{RANGE_S}s]{suffix}" if ranged else f"m{suffix}"
    return f"{func}({sel})" if func else sel


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(pe.PromEvaluator._KIND_KEYS))
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_kind_against_plain_reference(db, scenario, kind):
    dataset, grid, suffix, offset_ms, at_s = SCENARIOS[scenario]
    data = _series(dataset)
    load(db, data)
    for func, ranged in KIND_FUNCS[kind]:
        got, ev = served(db, _query(func, ranged, suffix), grid)
        want = reference(func, data, grid, offset_ms, at_s)
        assert sorted(got) == sorted(want)
        for name in want:
            g, w = got[name], want[name]
            assert np.array_equal(np.isnan(g), np.isnan(w)), (
                func, name, g, w)
            # f32 values, f64 prefixes: stddev and deriv difference sums
            # of squares, so they get the scale of the values squared
            scale = np.nanmax(np.abs(w), initial=1.0)
            tol = 2e-5 * max(scale, 1.0)
            if func == "stddev_over_time":
                vmax = np.nanmax(np.abs(data[name][1]), initial=1.0)
                tol = 1e-3 * max(vmax, 1.0) ** 0.5 + 2e-5 * scale
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=tol,
                                       err_msg=f"{func} {name}")
    # the slab's width follows the data: narrow on a regular scrape,
    # the whole (padded) series row where the spacing says nothing
    sel = parse_promql(_query("rate", True, suffix)).args[0]
    _args, p, *_rest = ev._prep_window(sel, "counter")
    longest = max(int((~np.isnan(v)).sum()) for _t, v in data.values())
    cap = pe._pow2(longest)
    assert p.run_bits == longest.bit_length()   # 127 -> 7, 128 -> 8
    if dataset == "irregular":
        assert p.slab_w == cap
    else:
        assert p.slab_w == pe.slab_width(
            ev.step_ms, p.num_steps, RANGE_S * 1000, 15_000, longest)
        # only a grid longer than the data itself reaches the cap
        assert (p.slab_w < cap) == (scenario != "outside")
    if dataset == "aligned":
        assert p.slab_w == int(scenario[4:])
        row_ptr = _args[0].row_ptr
        assert _args[0].val_s.shape[0] % 128 == 0
        r0 = dict(zip(sorted(data), np.asarray(row_ptr)))
        assert {name: (int(r0[name]) + FOLD_CUT) % 128
                for name in FOLD_COLUMNS} == FOLD_COLUMNS


# layout -> (data set, grid)
EDGE_LAYOUTS = {
    "lengths": ("lengths", (T0_S - 400, T0_S + 1100, 50)),
    **{name: SCENARIOS[name][:2]
       for name in ("fold64", "fold128", "fold512")},
}


@pytest.mark.parametrize("kind", sorted(pe.PromEvaluator._KIND_KEYS))
@pytest.mark.parametrize("layout", sorted(EDGE_LAYOUTS))
def test_edge_forms_agree_bit_for_bit(db, monkeypatch, layout, kind):
    """Window edges by the compare sweep over the FOLDED slab and by the
    log-W search along the gathered one place the same samples in every
    window, and picks by a compare-select pass and by a gather (min/max:
    the masked sweep and the sparse table) read the same values, so every
    output is the same bits: the fold moved the layout and nothing else."""
    dataset, grid = EDGE_LAYOUTS[layout]
    data = _series(dataset)
    load(db, data)
    func, ranged = KIND_FUNCS[kind][0]

    def run():
        pe._KERNEL_CACHE.clear()
        ev = pe.PromEvaluator(db, *grid)
        return np.asarray(ev.eval(parse_promql(_query(func, ranged, "")))
                          .values)

    swept = run()
    monkeypatch.setattr(pe, "_SWEEP_WIDTH", 0)
    searched = run()
    pe._KERNEL_CACHE.clear()
    assert np.array_equal(swept, searched, equal_nan=True)
    assert not np.isnan(swept).all()


# every counter sits at 2^40 + a few hundred: one f32 for all of them, the
# whole value in the low word, and a reset that only both words show
WIDE_LIFT = float(1 << 40)


@pytest.mark.parametrize("kind", sorted(pe.PromEvaluator._KIND_KEYS))
@pytest.mark.parametrize("layout", ["lengths", "fold128"])
def test_edge_forms_agree_bit_for_bit_on_a_wide_layout(db, monkeypatch,
                                                       layout, kind):
    """The same two forms over a WIDE layout (values as two f32 words):
    the low word is gathered, folded and picked beside the high one in
    both, so every output is the same bits again — and the counter kinds
    read what f32 alone cannot hold."""
    dataset, grid = EDGE_LAYOUTS[layout]
    data = _series(dataset)
    load(db, data, lift=WIDE_LIFT)
    func, ranged = KIND_FUNCS[kind][0]
    expr = parse_promql(_query(func, ranged, ""))

    def run():
        pe._KERNEL_CACHE.clear()
        ev = pe.PromEvaluator(db, *grid)
        out = np.asarray(ev.eval(expr).values)
        assert all(k.wide for k in pe._KERNEL_CACHE
                   if isinstance(k, pe.WindowParams))
        return out

    swept = run()
    monkeypatch.setattr(pe, "_SWEEP_WIDTH", 0)
    searched = run()
    pe._KERNEL_CACHE.clear()
    assert np.array_equal(swept, searched, equal_nan=True)
    assert not np.isnan(swept).all()
    if kind in ("counter", "counter_rc"):
        # the lift cancels in every difference: the answers are those of
        # the unlifted counters, which f32 holds exactly (but for the
        # extrapolation's cut at the counter's zero, which sees the lift)
        db.sql("DROP TABLE m")
        load(db, data)
        pe._KERNEL_CACHE.clear()
        plain = np.asarray(pe.PromEvaluator(db, *grid).eval(expr).values)
        pe._KERNEL_CACHE.clear()
        assert np.array_equal(np.isnan(swept), np.isnan(plain))
        if kind != "counter":
            assert np.array_equal(swept, plain, equal_nan=True)
        else:
            # (a lifted counter is never cut short there: at least as much)
            assert (np.nan_to_num(plain) <= np.nan_to_num(swept) * (1 + 1e-6)
                    ).all() and (swept > 0).any()
            assert (swept == plain).any()


def _node_fleet(db, table="cpu", lift=0.0):
    """Two modes x four CPUs at a 15 s scrape, 2 h: the cell's shape in
    small (benchmark/configs/prom-node-64.json).  ``lift`` is added to
    every value (past 2^24: a wide layout)."""
    db.sql(f"CREATE TABLE {table} (mode STRING, cpu STRING, "
           "ts TIMESTAMP(3) TIME INDEX, val DOUBLE, PRIMARY KEY (mode, cpu))")
    r = db._region_of(table)
    rng = np.random.default_rng(5)
    n = 600
    ts = T0_S * 1000 + 15_000 * np.arange(n)
    for mode in ("user", "idle"):
        for c in range(4):
            r.write({"mode": [mode] * n, "cpu": [str(c)] * n, "ts": ts,
                     "val": lift + np.cumsum(rng.uniform(0, 15, n))})


def test_one_program_for_every_hour_and_mode(db):
    """start_ms and the matched series are data: two evaluations at
    different times over different matchers of equal padded size run one
    compiled program, and W depends on neither."""
    _node_fleet(db)
    q = 'sum by (cpu)(rate(cpu{mode="%s"}[5m]))'
    builds = "greptime_compile_xla_builds_total"
    widths = set()

    def run(mode, start_s):
        ev = pe.PromEvaluator(db, start_s, start_s + 3600, 60)
        expr = parse_promql(q % mode)
        out = np.asarray(ev.eval(expr).values)
        sel = expr.expr.args[0]
        widths.add(ev._prep_window(sel, "counter")[1].slab_w)
        return out

    first = run("user", T0_S + 4000)
    n_kernels = len(pe._KERNEL_CACHE)
    n_builds = REGISTRY.value(builds, ("promql",))
    second = run("idle", T0_S + 4000 + 977)
    run("user", T0_S + 5111)
    assert len(pe._KERNEL_CACHE) == n_kernels
    assert REGISTRY.value(builds, ("promql",)) == n_builds
    # 1 h at 60 s over [5m] on a 15 s scrape: 3,900 s / 15 s + 2 -> 512
    assert widths == {512}
    assert first.shape == second.shape == (4, 61)
    assert np.isfinite(first).all() and np.isfinite(second).all()


ROWS = "greptime_promql_window_rows_total"
SWEPT = "greptime_promql_swept_columns_total"
WIDE = "greptime_promql_wide_rows_total"


@pytest.mark.parametrize("slab_w, rows, swept", [
    (64, 2048, 128),       # W under one 128-row chunk: half is sentinels
    (128, 2048, 128),      # k8s100k.namespace_cpu's: one chunk, folded
    (512, 2048, 512),      # node64.cpu_rate's: four chunks, folded
    (8192, 1 << 20, 8192),  # the widest slab that is swept
    (16384, 1 << 20, 16384 + 128),  # searched: the gathered chunks stay
    (8, 8 * 1250, 16),     # a layout that tiles by 16 rows only
])
def test_swept_columns_of_a_shape_class(slab_w, rows, swept):
    assert pe.swept_columns(slab_w, rows) == swept


def _counted(db, name, query, start_s=T0_S + 4000, span_s=3600):
    """How far counter ``name`` moves over one evaluation of ``query``."""
    before = REGISTRY.value(name, ())
    ev = pe.PromEvaluator(db, start_s, start_s + span_s, 60)
    ev.eval(parse_promql(query))
    return REGISTRY.value(name, ()) - before


def _reader(name):
    """benchmark/layer_metrics/<name>.py, loaded by its path."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def test_swept_columns_counter_advances_by_folded_cells(db):
    """Padded series x max(W, 128) a dispatch: what every [S, T, .] pass
    of the program runs over, from the helper the geometry sizes with."""
    _node_fleet(db)
    # W = 512 is four whole chunks: the fold leaves no sentinel column
    assert _counted(db, SWEPT, 'sum by (cpu)(rate(cpu{mode="user"}[5m]))') \
        == 4 * 512
    assert _counted(db, SWEPT, 'rate(cpu[5m])') == 8 * 512
    assert _counted(db, SWEPT,
                    'quantile_over_time(0.5, cpu{mode="idle"}[5m])') \
        == 2 * 4 * 512
    # the lookback alone is W = 32: the sweeps still run over one chunk
    assert _counted(db, SWEPT, "cpu", span_s=0) == 8 * 128

    # the benchmark's reader divides the two counters
    def snap():
        return {name: REGISTRY.value(name, ()) for name in (ROWS, SWEPT)}

    read = _reader("slab_fill_pct").read
    before = snap()
    _counted(db, SWEPT, 'rate(cpu[5m])')
    after = snap()
    assert read({"metrics_before": before, "metrics_after": after}) == 100.0
    _counted(db, SWEPT, "cpu", span_s=0)
    assert read({"metrics_before": after, "metrics_after": snap()}) == 25.0
    # the parent's program has no such counter; an idle window no dispatch
    assert read({"metrics_before": {}, "metrics_after": {ROWS: 9.0}}) is None
    assert read({"metrics_before": after, "metrics_after": after}) is None


def test_window_rows_counter_advances_by_slab_cells(db):
    _node_fleet(db)
    # four matched series (padded to 4) x W = 512, a dispatch
    assert _counted(db, ROWS, 'sum by (cpu)(rate(cpu{mode="user"}[5m]))') \
        == 4 * 512
    # every series, unfused
    assert _counted(db, ROWS, 'rate(cpu[5m])') == 8 * 512
    # a matrix kernel dispatches its sizing pass and itself
    assert _counted(db, ROWS,
                    'quantile_over_time(0.5, cpu{mode="idle"}[5m])') \
        == 2 * 4 * 512
    # an instant vector at one step gathers the lookback only: 300 s / 15 s
    assert _counted(db, ROWS, "cpu", span_s=0) == 8 * 32
    # a narrow layout (CPU seconds under 2^24) counts no wide cell; the
    # same fleet lifted to 1e10 has a wide one and counts every cell twice:
    # padded series x W under both names
    for query in ('sum by (cpu)(rate(cpu{mode="user"}[5m]))', "rate(cpu[5m])",
                  'quantile_over_time(0.5, cpu{mode="idle"}[5m])'):
        assert _counted(db, WIDE, query) == 0
    _node_fleet(db, "net", lift=1e10)
    for name in (WIDE, ROWS):
        assert _counted(db, name,
                        'sum by (cpu)(rate(net{mode="user"}[5m]))') == 4 * 512
        assert _counted(db, name, "rate(net[5m])") == 8 * 512
        assert _counted(db, name,
                        'quantile_over_time(0.5, net{mode="idle"}[5m])') \
            == 2 * 4 * 512
        assert _counted(db, name, "net", span_s=0) == 8 * 32


# ---------------------------------------------------------------------------
# one traversal a window edge
# ---------------------------------------------------------------------------

PASSES = "greptime_promql_sweep_passes_total"
_ROWS, _SERIES, _SEL, _STEPS = 4096, 12, 8, 7


def _class_args(p, wide):
    sd = jax.ShapeDtypeStruct
    f32 = sd((_ROWS,), jnp.float32)
    return (pe.SortLayout(sd((_ROWS,), jnp.int32), sd((_ROWS,), jnp.uint32),
                          f32, sd((_SERIES + 1,), jnp.int32),
                          f32 if wide else None),
            sd((p.num_sel,), jnp.int32), sd((), jnp.int64))


def _cube_reduces(text, p):
    """Reduces of the lowered program whose operands are the swept cube:
    padded series x steps x swept columns, in whichever order."""
    cube = sorted((p.num_sel, p.num_steps, pe.swept_columns(p.slab_w, _ROWS)))
    n = 0
    for m in re.finditer(r"stablehlo\.reduce\(.*? : \(tensor<([0-9x]+)x\w+>",
                         text):
        dims = sorted(int(d) for d in m.group(1).split("x"))
        n += dims == cube
    return n


@pytest.mark.parametrize("kind", ["counter", "instant", "gauge_window",
                                  "irate", "regression", "counter_rc",
                                  "minmax"])
@pytest.mark.parametrize("slab_w", [64, 128, 512])
@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
def test_sweep_passes_counted_are_the_traversals_lowered(wide, slab_w, kind):
    """The counter a dispatch advances by is the number of reduces over
    [S, T, F] in the program's own lowered text: one a window edge, the
    count and every word read there under one mask; an f64 crosses as two
    f32 words, so no operand of such a reduce is 64 bits wide."""
    p = pe.WindowParams(
        step_ms=30_000, num_steps=_STEPS, range_ms=300_000, num_sel=_SEL,
        total_series=_SERIES, kind=kind, slab_w=slab_w, run_bits=10,
        wide=wide)
    args = _class_args(p, wide)
    text = jax.jit(pe._window_body(p)).lower(*args).as_text()
    before = REGISTRY.value(PASSES, ())
    pe.count_dispatch(p, args, selected=5)
    counted = REGISTRY.value(PASSES, ()) - before
    assert counted == pe.sweep_passes(kind, slab_w) == _cube_reduces(text, p)
    assert counted == (4 if kind == "minmax" else 2)
    if kind == "counter":
        assert counted <= 3
    cube = "x".join(str(d) for d in (pe.swept_columns(slab_w, _ROWS), _STEPS,
                                     _SEL))
    assert f"tensor<{cube}xf32>" in text or kind == "minmax"
    assert f"tensor<{cube}xf64>" not in text
    assert f"tensor<{cube}xi64>" not in text
    # the searched form sweeps nothing, and counts nothing
    wide_slab = pe.WindowParams(**{**p.__dict__, "slab_w": 16384})
    before = REGISTRY.value(PASSES, ())
    pe.count_dispatch(wide_slab, args, selected=5)
    assert REGISTRY.value(PASSES, ()) == before


def _word_slab(sweep: bool):
    """A gathered slab by hand, 128 + 128 columns: five series whose first
    readable sample sits at column 0, 1, 64, 127 and 5 of the first chunk,
    40, 128, 3, 90 and 0 samples long, a scrape every 15 s."""
    S, F, c, T = 5, 128, 128, 9
    off = np.array([0, 1, 64, 127, 5], np.int32)
    run = np.array([40, 128, 3, 90, 0])
    g = np.arange(F + c)[None, :]
    ok = (g >= off[:, None]) & (g < (off + run)[:, None])
    big = (1 << 31) - 1
    rel = np.where(ok, -280_000 + 15_000 * (g - off[:, None]) + 7 * off[:, None],
                   np.where(g < off[:, None], -big - 1, big)).astype(np.int32)
    steps = 60_000 * np.arange(T, dtype=np.int64)
    val = np.where(ok, 1.0, 0.0).astype(np.float32)
    return pe.Slab(
        jnp.asarray(rel), jnp.asarray(val), (jnp.asarray(val),),
        jnp.asarray(ok), jnp.asarray(off), jnp.asarray(run > 0),
        ((steps - 300_000).astype(np.int32), steps.astype(np.int32)),
        sweep, F if sweep else F + c), ok


@pytest.mark.parametrize("form", ["swept", "searched"])
def test_grouped_pick_returns_both_words_of_an_f64(form):
    """f64 arrays with both f32 words in use (a counter at 2^40 + a
    fraction, a sum of drops beside it, an int32 beside both) read at a
    window's two edges in one group come back as ``take_along_axis``
    returns them, bit for bit, and the edges as the counting pass places
    them."""
    slab, ok = _word_slab(form == "swept")
    rng = np.random.default_rng(3)
    frac = rng.integers(0, 128, ok.shape) / 128.0
    x = np.where(ok, float(1 << 40) + np.cumsum(
        rng.integers(1, 9000, ok.shape), axis=1) + frac, 0.0)
    drops = np.where(ok, 3.0 * (1 << 33) + np.cumsum(frac, axis=1), 0.0)
    hi = x.astype(np.float32).astype(np.float64)
    assert (np.abs(x - hi)[ok] > 0).any() and (x != hi)[ok].mean() > 0.9
    arrays = {"x": jnp.asarray(x), "drops": jnp.asarray(drops),
              "rel": slab.rel,
              "words": tuple(jnp.asarray(a.astype(np.float32))
                             for a in (hi, x - hi))}
    lo, hi_, first, last = jax.jit(
        lambda a: pe._read_edges(slab, a, dict(a)))(arrays)
    want_lo, want_hi = pe._read_edges(slab)[:2]
    assert np.array_equal(lo, want_lo) and np.array_equal(hi_, want_hi)
    has = np.asarray(hi_ > lo)
    assert has.any() and not has.all()
    w = slab.width
    for got, i in ((first, lo), (last, hi_ - 1)):
        col = slab.col(jnp.clip(i, 0, w - 1))
        for name, a in arrays.items():
            if name == "words":
                a = arrays["x"]
            want = np.asarray(jnp.take_along_axis(slab.fold(a), col, axis=1))
            assert got[name].dtype == want.dtype
            assert np.array_equal(np.asarray(got[name])[has], want[has]), name


def test_sweep_passes_counter_and_its_reader(db):
    """Two traversals a dispatch of the counter kind, fused or not; a
    matrix kind's program and its sizing pass place the edges, two each;
    the benchmark's reader divides by the window's requests."""
    _node_fleet(db)
    assert _counted(db, PASSES,
                    'sum by (cpu)(rate(cpu{mode="user"}[5m]))') == 2
    assert _counted(db, PASSES, "rate(cpu[5m])") == 2
    assert _counted(db, PASSES, "max_over_time(cpu[5m])") == 4
    assert _counted(db, PASSES,
                    'quantile_over_time(0.5, cpu{mode="idle"}[5m])') == 4
    read = _reader("sweep_passes_per_query").read

    def snap():
        return {name: REGISTRY.value(name, ()) for name in (ROWS, PASSES)}

    before = snap()
    for mode in ("user", "idle", "user"):
        _counted(db, PASSES, 'sum by (cpu)(rate(cpu{mode="%s"}[5m]))' % mode)
    after = snap()
    log = [{}] * 3
    assert read({"metrics_before": before, "metrics_after": after,
                 "log": log}) == 2.0
    # the parent's program has no such counter; an idle window no dispatch
    assert read({"metrics_before": {}, "metrics_after": {ROWS: 9.0},
                 "log": log}) is None
    assert read({"metrics_before": after, "metrics_after": after,
                 "log": log}) is None


# ---------------------------------------------------------------------------
# the first-sample search
# ---------------------------------------------------------------------------

ROUNDS = "greptime_promql_search_rounds_total"
# the timestamps' high word steps from 2 to 3 here (and the low one wraps)
T_WRAP = 3 << 32


def _runs(n, c, bits, pad):
    """Run lengths of a layout of ``n`` rows, ``pad`` of them invalid at
    its end, read in chunks of ``c``: c − 1 (so the next run, c + 1 long,
    starts at a chunk's last row), 0, 1, c − 1, c, c + 1 and 2**bits − 1,
    each kept where it is under 2**bits; fillers; and a run that ends at
    the last valid row, inside the last chunk where nothing is padded."""
    longest = (1 << bits) - 1
    lengths = [c - 1] + [m for m in (c + 1, 0, 1, c - 1, c, c + 1, longest, 2)
                         if m <= longest]
    tail = max(c // 2, 1)
    rest = n - pad - sum(lengths) - tail
    assert rest >= 0
    while rest:
        lengths.append(min(rest, longest))
        rest -= lengths[-1]
    return lengths + [tail]


def _search_layout(n, c, bits, pad, wide):
    """A (tsid, ts)-sorted layout of ``_runs``' series, every run 30 s
    apart and laid so that it straddles ``T_WRAP`` at its own phase; the
    invalid rows hold timestamps below every threshold."""
    lengths = _runs(n, c, bits, pad)
    ts = np.full(n, -(1 << 40), np.int64)
    ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
    for s, m in enumerate(lengths):
        phase = (37 * s) % max(m, 1)
        ts[ptr[s]:ptr[s + 1]] = T_WRAP + 30_000 * (np.arange(m) - phase) + s % 7
    hi, lo = (np.asarray(a) for a in pe._split_i64(jnp.asarray(ts)))
    val = jnp.zeros(n, jnp.float32)
    layout = pe.SortLayout(jnp.asarray(hi), jnp.asarray(lo), val,
                           jnp.asarray(ptr), val if wide else None)
    return layout, ts, ptr, lengths


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("bits", [7, 9])
@pytest.mark.parametrize("n, c, pad", [
    (2048, 128, 0), (2048, 128, 300), (64 * 21, 64, 0), (8 * 125, 8, 0),
    (999, 1, 0)])
def test_first_rows_are_the_whole_search(n, c, pad, bits, wide):
    """``base`` found by the search's top rounds and one count over two
    gathered chunks is, slot for slot, the integer the whole search and
    numpy's ``searchsorted`` give: runs of 0, 1, c − 1, c, c + 1 and
    2**bits − 1 samples, a run from a chunk's last row, one in the last
    chunk (its second chunk clipped), padding slots and series newer than
    the layout, thresholds before and after every run and across the high
    word's step; and the slab gathered from it starts there."""
    assert math.gcd(n, 128) == c
    layout, ts, ptr, lengths = _search_layout(n, c, bits, pad, wide)
    assert max(lengths) == (1 << bits) - 1
    assert pe.search_bits(max(lengths)) == bits
    total = len(lengths)
    starts = ptr[:-1][np.asarray(lengths) > 0]
    assert ((starts % c) == c - 1).any()
    if not pad:
        assert ptr[-2] >= n - c     # the last run lies in the last chunk
    sel = np.concatenate([np.arange(total), [-1, total, total + 5, -1]])
    sel = jnp.asarray(sel, jnp.int32)
    first_rows = jax.jit(pe._first_rows, static_argnums=3)

    def whole(thr):
        sel_ok, r0, run, _base = first_rows(layout, sel, thr, bits)

        def ts_at(i):
            at = jnp.clip(r0 + i, 0, n - 1)
            return pe._join_i64(layout.ts_hi[at], layout.ts_lo[at])
        return r0 + pe._count_le(ts_at, run, thr, bits)

    thresholds = [T_WRAP - (1 << 40), T_WRAP + (1 << 40), T_WRAP - 1, T_WRAP,
                  T_WRAP + 1, T_WRAP + 3] + [
        T_WRAP + 30_000 * m + d for m in (-500, -130, -64, -1, 2, 63, 127, 400)
        for d in (0, 5, 29_999)]
    for thr in thresholds:
        thr = jnp.int64(thr)
        sel_ok, r0, run, base = (np.asarray(a) for a in first_rows(
            layout, sel, thr, bits))
        want = np.array([
            ptr[s] + np.searchsorted(ts[ptr[s]:ptr[s + 1]], int(thr), "right")
            if 0 <= s < total else ptr[min(max(s, 0), total - 1)]
            for s in np.asarray(sel)], np.int32)
        assert np.array_equal(base, want), int(thr)
        assert np.array_equal(base, np.asarray(whole(thr))), int(thr)
        assert np.array_equal(sel_ok, (np.asarray(sel) >= 0)
                              & (np.asarray(sel) < total))
    # the slab gathered from ``base``: its first readable column, and as
    # many readable columns as the run has rows from ``base`` on
    p = pe.WindowParams(step_ms=30_000, num_steps=3, range_ms=300_000,
                        num_sel=sel.shape[0], total_series=total,
                        kind="counter", slab_w=128, run_bits=bits, wide=wide)
    thr = T_WRAP + 30_000 * 2 + 5
    slab = jax.jit(lambda lay, s, t: pe._slab_gather(p, lay, s, t))(
        layout, sel, jnp.int64(thr + p.range_ms))
    _ok, r0, run, base = (np.asarray(a) for a in first_rows(
        layout, sel, jnp.int64(thr), bits))
    assert np.array_equal(np.asarray(slab.off), base % c)
    gathered = slab.ok.shape[1] - base % c
    assert np.array_equal(np.asarray(slab.ok).sum(axis=1),
                          np.minimum(r0 + run - base, gathered))


@pytest.mark.parametrize("run_bits, rounds", [(7, 0), (12, 5)])
@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
def test_search_rounds_counted_are_the_scalar_gathers_lowered(wide, run_bits,
                                                              rounds):
    """On a table of whole 128-row chunks the search gathers a scalar a
    series from each timestamp word in ``search_rounds`` rounds (the
    run's bits less 7), the program's lowered text says so, and the
    dispatch counter advances by it a program; the rounds it leaves out
    are one gather of two chunk rows a word."""
    p = pe.WindowParams(
        step_ms=30_000, num_steps=_STEPS, range_ms=300_000, num_sel=_SEL,
        total_series=_SERIES, kind="counter", slab_w=128, run_bits=run_bits,
        wide=wide)
    args = _class_args(p, wide)
    text = jax.jit(pe._window_body(p)).lower(*args).as_text()
    scalar = re.findall(rf": \(tensor<{_ROWS}xu?i32>, tensor<{_SEL}x1xi32>\)"
                        rf" -> tensor<{_SEL}xu?i32>", text)
    # the count's two chunk rows and the slab's (W = 128: two), a word
    chunk_rows = re.findall(
        rf": \(tensor<{_ROWS // 128}x128xu?i32>, tensor<{_SEL}x2x1xi32>\)",
        text)
    assert pe.search_rounds(run_bits, _ROWS) == rounds
    assert len(scalar) == 2 * rounds
    assert len(chunk_rows) == 4
    for programs in (1, 2):
        before = REGISTRY.value(ROUNDS, ())
        pe.count_dispatch(p, args, selected=5, programs=programs)
        assert REGISTRY.value(ROUNDS, ()) - before == programs * rounds


@pytest.mark.parametrize("run_bits, n, rounds", [
    (7, 4096, 0), (12, 4096, 5), (3, 4096, 0), (16, 2048 * 125, 9),
    (9, 64 * 21, 3), (9, 999, 9)])
def test_search_rounds_of_a_shape_class(run_bits, n, rounds):
    assert pe.search_rounds(run_bits, n) == rounds


def test_search_rounds_counter_and_its_reader(db):
    """A dispatch advances the counter by the rounds of its layout's class,
    fused or not, twice for a matrix kind (its sizing pass searches too);
    the benchmark's reader divides by the window's requests."""
    _node_fleet(db)
    q = 'sum by (cpu)(rate(cpu{mode="user"}[5m]))'
    ev = pe.PromEvaluator(db, T0_S + 4000, T0_S + 7600, 60)
    args, p, *_rest = ev._prep_window(
        parse_promql(q).expr.args[0], "counter")
    # 600 samples a series (10 bits) in a table of whole 128-row chunks
    assert (p.run_bits, args[0].val_s.shape[0] % 128) == (10, 0)
    rounds = pe.search_rounds(p.run_bits, args[0].val_s.shape[0])
    assert rounds == 3
    assert _counted(db, ROUNDS, q) == rounds
    assert _counted(db, ROUNDS, "rate(cpu[5m])") == rounds
    assert _counted(db, ROUNDS,
                    'quantile_over_time(0.5, cpu{mode="idle"}[5m])') \
        == 2 * rounds
    read = _reader("search_rounds_per_query").read

    def snap():
        return {name: REGISTRY.value(name, ()) for name in (ROWS, ROUNDS)}

    before = snap()
    for mode in ("user", "idle", "user"):
        _counted(db, ROUNDS, 'sum by (cpu)(rate(cpu{mode="%s"}[5m]))' % mode)
    after = snap()
    log = [{}] * 3
    assert read({"metrics_before": before, "metrics_after": after,
                 "log": log}) == rounds
    # the parent's program has no such counter; an idle window no dispatch
    assert read({"metrics_before": {}, "metrics_after": {ROWS: 9.0},
                 "log": log}) is None
    assert read({"metrics_before": after, "metrics_after": after,
                 "log": log}) is None
