"""A DOUBLE column that float32 cannot hold, through the PromQL window
program: counters near 1e7, 1e10 and 3e14 (a pod that has moved 300 TB)
keep their float64 samples on the device as two f32 words
(storage/cache.py ``low_word_col``, promql/engine.py ``WindowParams.wide``)
and every value the program reads is both words joined.

The reference is Prometheus's own definitions in plain numpy float64,
looped over series and windows, sharing nothing with the engine: the
extrapolated ``rate`` / ``increase`` / ``delta``, ``irate``, ``resets``,
``last_over_time`` and ``sum_over_time``, over seeded tables with resets,
a replaced series and runs of one to three samples.  The same samples
rounded to float32 first (what the parent's value column held) miss the
tolerance by 10x and more, so the tolerance tells the widths apart; and a
column of small magnitudes keeps the narrow layout, the class key and the
programs it had before, text for text.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.compile.fused import _build_fused
from greptimedb_tpu.compile.shape import canon_key
from greptimedb_tpu.promql import engine as pe
from greptimedb_tpu.promql.parser import parse_promql
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.storage.cache import low_word_col
from greptimedb_tpu.utils.telemetry import REGISTRY

T0_S = 20_000
SCRAPE_S = 30
RANGE_S = 300
GRID = (T0_S + 200, T0_S + 2900, 60)     # start, end, step (seconds)
# |got - ref| <= TOL x max(|ref|, a thousandth of the largest |ref|): an
# f32 result carries 6e-8, the extrapolation's f64 arithmetic nothing
TOL = 2e-6
WIDE = "greptime_promql_wide_rows_total"
ROWS = "greptime_promql_window_rows_total"
FUNCS = ("rate", "increase", "irate", "delta", "resets", "last_over_time",
         "sum_over_time")
# the ones that difference two samples: what an f32 column gets wrong
DIFFERENCING = ("rate", "increase", "irate", "delta")


@pytest.fixture
def db(monkeypatch):
    """One device, as tests/test_promql_slab.py has it and why."""
    monkeypatch.setenv("GREPTIME_MESH", "off")
    d = GreptimeDB()
    assert d.mesh is None
    pe._KERNEL_CACHE.clear()
    yield d
    pe._KERNEL_CACHE.clear()
    d.close()


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def _counter(rng, n, start, step, resets=(), whole=True):
    inc = rng.uniform(0.1 * step, 1.9 * step, n)
    if whole:
        inc = np.floor(inc) + 1
    v = start + np.cumsum(inc)
    for at in resets:        # falls to under one scrape's increment
        v[at:] = v[at:] - v[at] + (np.floor(inc[at] / 3) if whole
                                   else inc[at] / 3)
    return v


def series(seed: int = 11) -> dict:
    """{name: (ts_ms int64, values float64)}: 100 scrapes at 30 s."""
    rng = np.random.default_rng(seed)
    n, t0 = 100, T0_S * 1000
    ts = t0 + 1000 * SCRAPE_S * np.arange(n)
    out = {
        # whole bytes at 3e14 (exact in two words), a reset mid-way
        "huge": (ts, _counter(rng, n, 3e14, 6e5, resets=(47,))),
        "huge-slow": (ts + 7000, _counter(rng, n, 2.9e14, 40)),
        # 1e10: float32 steps of 1,024
        "big": (ts + 1000, _counter(rng, n, 1e10, 5e3, resets=(20, 71))),
        "big-frac": (ts + 2000, _counter(rng, n, 1.3e10, 900.0, whole=False)),
        # just under and over 2^24, fractional steps
        "edge": (ts + 3000, _counter(rng, n, 1e7, 1.5e3, whole=False)),
        # a pod replaced: its series ends, another begins near 0
        "old": (ts[:38], _counter(rng, 38, 8e10, 2e4)),
        "new": (ts[39:], _counter(rng, n - 39, 0.0, 2e4)),
    }
    for k in (1, 2, 3):      # short runs inside the grid
        at = 30 + 11 * k
        out[f"short{k}"] = (ts[at:at + k], _counter(rng, k, 5e12, 7e4))
    return out


def load(db, data, table="m", cast=None):
    db.sql(f"CREATE TABLE {table} (name STRING, ts TIMESTAMP(3) TIME INDEX, "
           f"val DOUBLE, PRIMARY KEY (name))")
    r = db._region_of(table)
    for name, (ts, vals) in data.items():
        vals = np.asarray(vals, np.float64)
        if cast is not None:
            vals = vals.astype(cast).astype(np.float64)
        r.write({"name": [name] * len(ts), "ts": np.asarray(ts, np.int64),
                 "val": vals})


# ---------------------------------------------------------------------------
# Prometheus, plainly, in float64
# ---------------------------------------------------------------------------

def _extrapolated(ts, v, t, counter, is_rate):
    if len(v) < 2:
        return np.nan
    delta = v[-1] - v[0]
    if counter:
        delta += sum(a for a, b in zip(v[:-1], v[1:]) if b < a)
    sampled = (ts[-1] - ts[0]) / 1000.0
    avg = sampled / (len(v) - 1)
    to_start = (ts[0] - (t - RANGE_S * 1000)) / 1000.0
    to_end = (t - ts[-1]) / 1000.0
    if to_start >= avg * 1.1:
        to_start = avg / 2
    if to_end >= avg * 1.1:
        to_end = avg / 2
    if counter and delta > 0:
        to_start = min(to_start, sampled * (v[0] / delta))
    res = delta * (sampled + to_start + to_end) / sampled
    return res / RANGE_S if is_rate else res


def _one(func, ts, v, t):
    """``func`` over the samples of one series inside (t - range, t]."""
    if len(v) == 0:
        return np.nan
    if func in ("rate", "increase", "delta"):
        return _extrapolated(ts, v, t, func != "delta", func == "rate")
    if func == "irate":
        if len(v) < 2:
            return np.nan
        dv = v[-1] - v[-2]
        return (v[-1] if dv < 0 else dv) / ((ts[-1] - ts[-2]) / 1000.0)
    if func == "resets":
        return float(sum(b < a for a, b in zip(v[:-1], v[1:])))
    if func == "last_over_time":
        return v[-1]
    if func == "sum_over_time":
        return float(np.sum(v))
    raise AssertionError(func)


def reference(func, data, cast=None) -> dict:
    """{name: float64 [steps]}; ``cast`` rounds the samples first."""
    start, end, step = GRID
    steps = 1000 * np.arange(start, end + 1, step)
    out = {}
    for name, (ts, vals) in data.items():
        vals = np.asarray(vals, np.float64)
        if cast is not None:
            vals = vals.astype(cast).astype(np.float64)
        row = np.full(len(steps), np.nan)
        for j, t in enumerate(steps):
            m = (ts > t - RANGE_S * 1000) & (ts <= t)
            row[j] = _one(func, ts[m], vals[m], t)
        if not np.isnan(row).all():
            out[name] = row
    return out


def served(db, func, table="m") -> dict:
    """``sum by (name)``: a group a series, so the fused road answers the
    same question as the unfused one."""
    ev = pe.PromEvaluator(db, *GRID)
    res = ev.eval(parse_promql(
        f"sum by (name)({func}({table}[{RANGE_S}s]))"))
    vals = np.asarray(res.values, np.float64)
    # a series without a point is left out of a reply, and of the reference
    return {lab["name"]: vals[i] for i, lab in enumerate(res.labels)
            if not np.isnan(vals[i]).all()}


def worst_error(got: dict, want: dict) -> float:
    assert sorted(got) == sorted(want)
    worst = 0.0
    for name, w in want.items():
        g = got[name]
        assert np.array_equal(np.isnan(g), np.isnan(w)), (name, g, w)
        if np.isnan(w).all():
            continue
        floor = 1e-3 * np.nanmax(np.abs(w))
        err = np.abs(g - w) / np.maximum(np.abs(w), max(floor, 1e-30))
        worst = max(worst, float(np.nanmax(err)))
    return worst


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("road", ["fused", "unfused"])
@pytest.mark.parametrize("form", ["swept", "searched"])
@pytest.mark.parametrize("func", FUNCS)
def test_wide_column_against_float64_reference(db, monkeypatch, ineligible,
                                               func, form, road):
    data = series()
    load(db, data)
    cols = db.cache.get(db._region_of("m")).columns
    assert cols["val"].dtype == jnp.float32        # what SQL reads
    assert cols[low_word_col("val")].dtype == jnp.float32
    if form == "searched":
        monkeypatch.setattr(pe, "_SWEEP_WIDTH", 0)
    wide = REGISTRY.value(WIDE, ())
    rows = REGISTRY.value(ROWS, ())
    if road == "unfused":
        with ineligible("fusion"):
            got = served(db, func)
    else:
        got = served(db, func)
    fused = [k for k in pe._KERNEL_CACHE if isinstance(k, tuple)
             and k[0] == "promql_fused"]
    assert bool(fused) == (road == "fused")
    params = [k for k in pe._KERNEL_CACHE if isinstance(k, pe.WindowParams)]
    assert all(p.wide for p in params + [k[1] for k in fused])
    # every cell the program gathered came off the wide layout
    assert REGISTRY.value(WIDE, ()) - wide == REGISTRY.value(ROWS, ()) - rows
    assert REGISTRY.value(ROWS, ()) > rows
    want = reference(func, data)
    if func in DIFFERENCING:
        assert {"huge", "big", "edge", "old", "new", "short2",
                "short3"} <= set(want)
    assert worst_error(got, want) <= TOL, func


@pytest.mark.parametrize("func", DIFFERENCING)
def test_samples_rounded_to_float32_miss_the_tolerance(db, func):
    """The control: the same program over the same samples as a float32
    column held them lies 10x and more past the tolerance, and so does
    the plain reference over them."""
    data = series()
    load(db, data, cast=np.float32)
    want = reference(func, data)
    assert worst_error(served(db, func), want) >= 10 * TOL
    assert worst_error(reference(func, data, cast=np.float32),
                       want) >= 10 * TOL


# lowered text (sha256, 16 hex) of the narrow programs, padded series 8 of
# 12, 7 steps of 30 s over [5m], a layout of 4,096 rows: one traversal a
# window edge, and a first-sample search that finishes on a gathered chunk.
# `python tests/test_promql_wide.py` prints this table for the tree it is
# run in.  A PR that changes the f32 program on purpose replaces them.
NARROW_PROGRAMS = {
    "counter-128": "0bfb1735ef9541d9",
    "counter-512": "f485705fa959afa0",
    "counter-64": "0bfb1735ef9541d9",
    "counter_rc-128": "bface2dca77351a4",
    "counter_rc-512": "6531cce050ca8f78",
    "counter_rc-64": "bface2dca77351a4",
    "fused-rate-sum-128": "0acd63ef76a8616e",
    "fused-rate-sum-512": "fdd1f23ff2a7af6f",
    "fused-rate-sum-64": "0acd63ef76a8616e",
    "gauge_window-128": "91d0b66a2fa3c3db",
    "gauge_window-512": "f45af23366d7808d",
    "gauge_window-64": "91d0b66a2fa3c3db",
    "instant-128": "00121bfeeefcef17",
    "instant-512": "ff757543591d85df",
    "instant-64": "00121bfeeefcef17",
    "irate-128": "bee19e58920b4c28",
    "irate-512": "68a4d4c940a91629",
    "irate-64": "bee19e58920b4c28",
    "minmax-128": "48df3d7ef8ed437e",
    "minmax-512": "750694d1b412a2c6",
    "minmax-64": "48df3d7ef8ed437e",
    "regression-128": "c318a813300cad9e",
    "regression-512": "5405542656c91d77",
    "regression-64": "c318a813300cad9e",
}
# the same of ``_build_sort_layout`` over a table of 4,096 rows: a compile
# cache that holds the narrow layout's program keeps serving it
NARROW_SORT_LAYOUT = "cd6c9f7e3d53c2b4"
NARROW_KEY = (
    "promql|(s'promql_fused',dc:WindowParams(step_ms=i30000,num_steps=i7,"
    "range_ms=i300000,num_sel=i8,total_series=i12,kind=s'counter',"
    "slab_w=i%d,run_bits=i10),s'rate',s'sum',i3,i6,i300)")
_ROWS, _SERIES, _SEL = 4096, 12, 8


def _narrow_params(kind: str, w: int) -> pe.WindowParams:
    return pe.WindowParams(
        step_ms=30_000, num_steps=7, range_ms=300_000, num_sel=_SEL,
        total_series=_SERIES, kind=kind, slab_w=w, run_bits=10)


def _lowered(fn, *extra) -> str:
    sd = jax.ShapeDtypeStruct
    args = (pe.SortLayout(sd((_ROWS,), jnp.int32), sd((_ROWS,), jnp.uint32),
                          sd((_ROWS,), jnp.float32),
                          sd((_SERIES + 1,), jnp.int32)),
            sd((_SEL,), jnp.int32), sd((), jnp.int64)) + extra
    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def narrow_sort_layout() -> str:
    sd = jax.ShapeDtypeStruct
    text = pe._build_sort_layout.lower(
        sd((_ROWS,), jnp.int64), sd((_ROWS,), jnp.float32),
        sd((_ROWS,), jnp.int32), sd((_ROWS,), jnp.bool_), _SERIES).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def narrow_programs(widths=(64, 128, 512)) -> dict:
    out = {}
    for w in widths:
        for kind in sorted(pe.PromEvaluator._KIND_KEYS):
            out[f"{kind}-{w}"] = _lowered(
                pe._window_body(_narrow_params(kind, w)))
        out[f"fused-rate-sum-{w}"] = _lowered(
            _build_fused(_narrow_params("counter", w), "rate", "sum", 3, 6,
                         300), jax.ShapeDtypeStruct((6,), jnp.int32))
    return out


@pytest.mark.parametrize("w", [64, 128, 512])
def test_narrow_column_keeps_layout_key_and_program(db, w):
    """Magnitudes under 2^24: no low word in the resident table, the
    four-array layout with f32 values, the class key without the new
    field, and the programs of ``NARROW_PROGRAMS``, text for text — so
    the outputs are theirs too."""
    data = {name: (ts, vals % 1e6) for name, (ts, vals) in series().items()}
    load(db, data)
    cols = db.cache.get(db._region_of("m")).columns
    assert low_word_col("val") not in cols
    ev = pe.PromEvaluator(db, *GRID)
    sel = parse_promql(f"rate(m[{RANGE_S}s])").args[0]
    args, p, *_rest = ev._prep_window(sel, "counter")
    assert not p.wide and not args[0].wide
    # the four arrays a narrow program always took, and no fifth
    assert [a.dtype for a in jax.tree.leaves(args[0])] == [
        jnp.int32, jnp.uint32, jnp.float32, jnp.int32]
    wide = REGISTRY.value(WIDE, ())
    got = served(db, "rate")
    assert REGISTRY.value(WIDE, ()) == wide
    # f32 samples, as before: the float64 reference at f32's tolerance
    assert worst_error(got, reference("rate", data)) <= 2e-4
    narrow = _narrow_params("counter", w)
    assert canon_key("promql", ("promql_fused", narrow, "rate", "sum", 3, 6,
                                300)) == NARROW_KEY % w
    assert "wide" in canon_key("promql", pe.WindowParams(
        **{**narrow.__dict__, "wide": True}))
    assert narrow_sort_layout() == NARROW_SORT_LAYOUT
    programs = narrow_programs((w,))
    assert programs == {name: digest
                        for name, digest in NARROW_PROGRAMS.items()
                        if name.endswith(f"-{w}")}


def test_histogram_program_is_a_sibling():
    """The fused histogram_quantile program (the ``sum`` program's body,
    its G·K sums folded) leaves the ``sum`` program's text and key as
    ``NARROW_PROGRAMS`` and ``NARROW_KEY`` have them, so no cell's
    compile cache is re-keyed; its own text and key differ."""
    sd = jax.ShapeDtypeStruct
    narrow = _narrow_params("counter", 128)
    hist = _lowered(
        _build_fused(narrow, "rate", "sum", 3 * 2, 6, 300, hist=(0.99, 2)),
        sd((6,), jnp.int32), sd((2,), jnp.float32))
    summed = _lowered(_build_fused(narrow, "rate", "sum", 3, 6, 300),
                      sd((6,), jnp.int32))
    assert summed == NARROW_PROGRAMS["fused-rate-sum-128"] != hist
    key = canon_key("promql", ("promql_fused", narrow, "rate", "sum", 6, 6,
                               300, ("histogram_quantile", 0.99, 2, 3)))
    assert key.startswith((NARROW_KEY % 128)[:-len(",i3,i6,i300)")])
    assert key.endswith(",(s'histogram_quantile',f0.99,i2,i3))")


if __name__ == "__main__":
    import json

    print(json.dumps({**narrow_programs(),
                      "sort-layout": narrow_sort_layout()},
                     indent=1, sort_keys=True))
