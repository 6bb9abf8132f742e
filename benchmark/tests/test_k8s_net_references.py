"""``k8s_net_rate``'s reference against a loop over samples at rehearsal
size (the generated data holds restarts and replaced pods, past 1e11),
its pieces on hand-worked series at 1e12, what ``lower`` rounds and what
it leaves, the data set's own promises, ``needed_bytes`` by hand, and a
whole run of the cell with the timed path broken underneath: an altered
value, and samples held as one float32 word, both read ``correct:
false``."""

import io

import numpy as np
import pytest
import run

CONFIG = "prom-k8s-net-120k"
WORKLOAD = "k8snet120k.namespace_bandwidth"
LARGEST = 20_382     # pods of the largest of 200 namespaces, 1/rank
MIX = "k8s_cluster_bandwidth"


def _cell(seed: int = 11):
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    return run.new_cell(config, rehearse=True, seed=seed)


def _family_and_requests(cell, n: int):
    mix = run.load_json(run.HERE, "traffic", f"{MIX}.json")
    family = run.load_module("queries", mix["family"])
    traffic = run.Traffic(family, cell, mix, seed=11, stream=1)
    return family, [traffic.next() for _ in range(n)]


def _rate_of_points(pts, t, range_s):
    """Prometheus's extrapolatedRate of one series' (time, value) points
    inside (t - range, t], one by one; None under two points."""
    if len(pts) < 2:
        return None
    delta = pts[-1][1] - pts[0][1]
    for (_t0, a), (_t1, b) in zip(pts, pts[1:]):
        if b < a:
            delta += a
    sampled = pts[-1][0] - pts[0][0]
    avg = sampled / (len(pts) - 1)
    to_start = pts[0][0] - (t - range_s)
    to_end = t - pts[-1][0]
    if to_start >= 1.1 * avg:
        to_start = avg / 2
    if to_end >= 1.1 * avg:
        to_end = avg / 2
    if delta > 0:
        to_start = min(to_start, sampled * pts[0][1] / delta)
    return delta * (sampled + to_start + to_end) / sampled / range_s


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_is_the_brute_force_loop(seed):
    cell = _cell(seed)
    family, reqs = _family_and_requests(cell, 4)
    p, ds, data = cell.params, cell.ds, cell.data
    t0 = ds.T0 // 1000
    seen = {"reset": 0, "late": 0, "early": 0, "one_sample": 0, "big": 0}
    assert {r["class"] for r in reqs} == set(family.CLASSES)
    for req in reqs:
        v = data["values"][family.table_of(cell, req["class"])]
        keys, vals = family.reference(cell, req)
        got = {tuple(k): x for k, x in zip(keys.tolist(), vals[:, 0])}
        want = {}
        for t in range(req["start_s"], req["end_s"] + 1, p["interval_s"]):
            for s in range(v.shape[1]):
                pts = [(t0 + k * p["interval_s"], v[k, s])
                       for k in range(ds.steps(p))
                       if t - family.RANGE_S < t0 + k * p["interval_s"] <= t
                       and not np.isnan(v[k, s])]
                seen["one_sample"] += len(pts) == 1
                seen["reset"] += any(b < a for (_x, a), (_y, b)
                                     in zip(pts, pts[1:]))
                seen["big"] += any(x > 1e11 for _t, x in pts)
                if len(pts) >= 2:
                    seen["late"] += (pts[0][0] - p["interval_s"]
                                     > t - family.RANGE_S)
                    seen["early"] += pts[-1][0] < t
                r = _rate_of_points(pts, t, family.RANGE_S)
                if r is not None:
                    key = (int(data["namespace"][s]), t * 1000)
                    want[key] = want.get(key, 0.0) + r
        assert sorted(got) == sorted(want)
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-12)
    # the rehearsal data exercises every case the issue names
    assert all(seen.values()), seen


def test_rate_at_scrapes_hand_worked_at_1e12():
    family = run.load_module("queries", "k8s_net_rate")
    nan, big = np.nan, 1e12
    vals = np.array([
        # a reset after big + 20; starts late; ends early; one sample; empty
        [big + 10.0, nan, big + 3.0, nan, nan],
        [big + 20.0, nan, big + 6.0, nan, nan],
        [5.0, big + 4.0, nan, nan, nan],
        [15.0, big + 9.0, nan, big + 7.0, nan],
    ])
    out = family.rate_at_scrapes(vals, 15_000, 60_000)
    assert out.shape == (5, 4)
    # scrape 3 (t = 45 s), window (-15 s, 45 s]: all four scrapes;
    # increase 10 + 5 + 10 = 25 over 45 s sampled (what fell, big + 20, is
    # added back); 15 s to the window's start is under 1.1 x 15 s and the
    # time to zero is far away
    assert out[0, 3] == pytest.approx(25 * (45 + 15) / 45 / 60, rel=1e-12)
    # two samples, at 30 s and 45 s: 5 over 15 s; 45 s to the window's
    # start is extrapolated by half an interval
    assert out[1, 3] == pytest.approx(5 * (15 + 7.5) / 15 / 60, rel=1e-12)
    # ended at 15 s: 3 over 15 s, 30 s to the window's end -> half an
    # interval, 15 s to its start
    assert out[2, 3] == pytest.approx(3 * (15 + 15 + 7.5) / 15 / 60,
                                      rel=1e-12)
    assert np.isnan(out[3, 3]) and np.isnan(out[4]).all()
    assert np.isnan(out[:, 0]).all()   # one sample of every series
    total = family.group_sum(out, np.array([0, 0, 1, 1, 2]), 4)
    assert total[0, 3] == pytest.approx(out[0, 3] + out[1, 3])
    assert total[1, 3] == pytest.approx(out[2, 3])
    assert np.isnan(total[2]).all() and np.isnan(total[3]).all()


def test_window_is_left_open():
    """A sample exactly ``range`` before the evaluation time is outside."""
    family = run.load_module("queries", "k8s_net_rate")
    vals = 1e12 + np.arange(1.0, 13.0)[:, None] * 30.0   # 1 B/s, every 30 s
    out = family.rate_at_scrapes(vals, 30_000, 300_000)
    # scrape 11: samples 2..11 (ten of them, 270 s sampled), not sample 1
    assert out[0, 11] == pytest.approx(270 * (270 + 30 + 0) / 270 / 300,
                                       rel=1e-12)


def test_lower_rounds_the_samples_and_nothing_else():
    """The control of this deployment is a program that reads its samples
    at float32: at 1e12 a float32 has steps of 65,536, so a counter that
    rises by 30 a scrape reads flat, and one that rises by 65,536 reads
    right."""
    family = run.load_module("queries", "k8s_net_rate")
    cell = _cell()
    cls = "receive_bandwidth"
    n, s = cell.data["values"][cell.ds.RECEIVE].shape
    slow = 1e12 + 30.0 * np.arange(n)
    fast = float(1 << 39) + 65536.0 * np.arange(n)
    cell.data["values"] = {cell.ds.RECEIVE: np.stack(
        [slow, fast] + [np.full(n, np.nan)] * (s - 2), axis=1)}
    cell.data["namespace"] = np.array([0, 1] + [2] * (s - 2))
    t0 = cell.ds.T0 // 1000
    req = {"class": cls, "start_s": t0 + 40 * 30, "end_s": t0 + 60 * 30}
    keys, exact = family.reference(cell, req)
    _k, rounded = family.reference(cell, req, family.F32)
    by_ns = {ns: (exact[keys[:, 0] == ns, 0], rounded[keys[:, 0] == ns, 0])
             for ns in (0, 1)}
    assert by_ns[0][0] == pytest.approx(1.0, rel=1e-12)       # 30 B / 30 s
    assert (np.abs(by_ns[0][1] - 1.0) > 0.5).all()            # flat or a step
    assert by_ns[1][0] == pytest.approx(65536 / 30, rel=1e-12)
    assert np.array_equal(by_ns[1][0], by_ns[1][1])           # nothing else


def test_data_set_keeps_its_promises():
    import pyarrow as pa

    cell = _cell()
    ds, p, data = cell.ds, cell.params, cell.data
    assert list(data["values"]) == list(ds.TABLES) and len(ds.TABLES) == 2
    for table in ds.TABLES:
        v = data["values"][table]
        assert v.shape == (ds.steps(p), ds.n_series(p))
        has = ~np.isnan(v)
        assert len(ds.TABLES) * int(has.sum()) == ds.rows(p)
        assert (has.sum(axis=1) == p["pods"]).all()
        # whole bytes under 2^49: exact in float64 and in two float32s
        assert (v[has] % 1 == 0).all() and (v[has] >= 0).all()
        assert v[has].max() < ds.LIMIT == 2.0 ** 49 < 2.0 ** 53
        hi = v[has].astype(np.float32)
        lo = (v[has] - hi.astype(np.float64)).astype(np.float32)
        assert (hi.astype(np.float64) + lo.astype(np.float64) == v[has]).all()
        # a life is one stretch of scrapes; replaced pods' series end early
        # and their successors start late
        lives = np.diff(has.astype(np.int8), axis=0)
        assert (np.abs(lives).sum(axis=0) <= 1).all()
        late = np.flatnonzero(~has[0])
        early = np.flatnonzero(~has[-1])
        assert len(late) == len(early) == ds.replaced_pods(p)
        # restarts: the only falls, at most one a series, to under one
        # interval's bytes; the same series in both tables
        falls = np.diff(v, axis=0) < 0   # NaN compares false
        assert 1 <= falls.sum() <= ds.restarted_series(p)
        assert (falls.sum(axis=0) <= 1).all()
        assert set(np.flatnonzero(falls.any(axis=0))) <= set(data["restarts"])
    assert not np.array_equal(*data["values"].values(), equal_nan=True)
    assert ds.namespace_pods(p).sum() == p["pods"]
    assert (np.diff(ds.namespace_pods(p)) <= 0).all()
    # nine tags, each a dictionary column; absent samples are not sent
    assert list(data["tags"]) == ds.TAGS and len(ds.TAGS) == 9
    assert data["tags"]["interface"][1] == ["eth0"]
    sent = dict.fromkeys(ds.TABLES, 0)
    for table, body, n in ds.arrow_bodies(data, p):
        got = pa.ipc.open_stream(io.BytesIO(body)).read_all()
        assert got.num_rows == n and not np.isnan(
            got.column("greptime_value").to_numpy()).any()
        assert got.column_names == ds.TAGS + ["ts", "greptime_value"]
        sent[table] += n
    assert sum(sent.values()) == ds.rows(p)
    assert len(set(sent.values())) == 1
    assert len(ds.ddl(p)) == 2 and all(t in ds.count_sql(p) for t in ds.TABLES)
    # the same seed, the same data
    again = ds.generate(11, p)
    for table in ds.TABLES:
        assert np.array_equal(again["values"][table], data["values"][table],
                              equal_nan=True)
    assert again["tags"]["pod"][1] == data["tags"]["pod"][1]


def test_full_size_counts():
    """What the configuration's file says of the series, from the data
    set's own arithmetic (no data made)."""
    config = run.load_json(run.HERE, "configs", f"{CONFIG}.json")
    ds = run.load_module("datasets", config["dataset"])
    p = config["params"]
    assert p == {"nodes": 1500, "pods": 120_000, "namespaces": 200,
                 "interval_s": 30, "hours": 1}
    assert p["pods"] == config["series"]["live_a_table"]
    assert ds.n_series(p) == config["series"]["in_a_table"] == 126_000
    assert config["series"]["matched"] == 126_000
    assert config["series"]["padded"] == 131_072 == 1 << 17
    assert ds.replaced_pods(p) == 6_000
    assert ds.restarted_series(p) == 1_260
    assert ds.rows(p) == 28_800_000 and ds.steps(p) == 120
    assert ds.steps({**p, "hours": 0.75}) == 90      # the fallback
    assert p["namespaces"] == config["series"]["groups"]
    assert int(ds.namespace_pods(p)[0]) == LARGEST
    # the oldest, fastest counter stays under 2^49 through the hour, and
    # under the largest whole number two float32 words hold for certain
    top = ds.START_CAP + ds.RATE_CLIP[1] * ds.BURST[1] * 3600
    assert top < 3.1e14 < ds.LIMIT
    assert ds.START_CAP < ds.RATE_CLIP[1] * ds.AGE_DAYS * 86400
    assert config["sample_precision"] == "float64"
    assert config["compute_precision"] == "float32"


def test_needed_bytes_hand_worked():
    cell = _cell()
    family = run.load_module("queries", "k8s_net_rate")
    ds, data = cell.ds, cell.data
    t0 = ds.T0 // 1000
    # 30 min ending at scrape 100: evaluation scrapes 40..100, the first
    # window reaches back to scrape 31: scrapes 31..100 of every series of
    # the class's table that has them, 16 B each; 5 namespaces x 61 points
    # x 16 B
    for cls in family.CLASSES:
        req = {"class": cls, "start_s": t0 + 40 * 30, "end_s": t0 + 100 * 30}
        has = ~np.isnan(data["values"][family.table_of(cell, cls)][31:101])
        assert family.needed_bytes(cell, req) == (
            16 * int(has.sum()) + 16 * 5 * 61)
        # with every series alive throughout it would be 70 samples a series
        assert int(has.sum()) < 70 * has.shape[1]


# ---------------------------------------------------------------------------
# a whole run of the cell, sound and broken (test_faults.py's pattern)
# ---------------------------------------------------------------------------

def _run():
    return run.run_cell(WORKLOAD, seed=7, seconds=1.0, trace=False,
                        rehearse=True, need_tpu=False)


def _over(res) -> list[str]:
    return [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"max_err.receive_bandwidth",
            "max_err.transmit_bandwidth"} <= set(res["checks"])


def test_altered_value_is_not_correct(monkeypatch):
    """Every reply after the warm-up carries one point moved by 0.1 %."""
    import copy

    from greptimedb_tpu.promql import format as prom_format

    real, calls = prom_format.range_payload, {"n": 0}

    def altered(*a, **kw):
        out = copy.deepcopy(real(*a, **kw))
        calls["n"] += 1
        result = out.get("data", {}).get("result")
        if calls["n"] > 2 and result:
            t, v = result[0]["values"][0]
            result[0]["values"][0] = [t, repr(float(v) * 1.001 + 1e-3)]
        return out

    monkeypatch.setattr(prom_format, "range_payload", altered)
    res = _run()
    assert not res["correct"] and res["failed"] > 0, res["checks"]
    assert any(k.startswith("max_err.") for k in _over(res)), res["checks"]


def test_samples_held_at_float32_are_not_correct(monkeypatch):
    """The resident table keeps no low word, as the parent's did: every
    reply is the float32 column's answer, well formed and wrong."""
    from greptimedb_tpu.storage import cache

    monkeypatch.setattr(cache, "_low_word", lambda *a, **kw: None)
    res = _run()
    assert not res["correct"] and res["failed"] > 0, res["checks"]
    assert set(_over(res)) == {"max_err.receive_bandwidth",
                               "max_err.transmit_bandwidth"}, res["checks"]
    for name in _over(res):
        c = res["checks"][name]
        assert c["value"] > 2 * c["limit"], res["checks"]
