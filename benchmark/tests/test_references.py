"""Each class's numpy reference against a brute-force loop at rehearsal
size, and ``needed_bytes`` on hand-worked requests."""

import numpy as np
import pytest
import run


def _cell(config_name: str, seed: int = 11):
    config = run.load_json(run.HERE, "configs", f"{config_name}.json")
    return run.new_cell(config, rehearse=True, seed=seed)


def _requests(cell, mix_name: str, n: int):
    mix = run.load_json(run.HERE, "traffic", f"{mix_name}.json")
    family = run.load_module("queries", mix["family"])
    traffic = run.Traffic(family, cell, mix, seed=11, stream=1)
    return family, [traffic.next() for _ in range(n)]


@pytest.mark.parametrize("mix", ["tsbs_dashboards", "tsbs_double_groupby"])
def test_tsbs_reference_is_the_brute_force_loop(mix):
    cell = _cell("tsbs-cpu-4000")
    family, reqs = _requests(cell, mix, 16)
    v = cell.data["values"]
    step_ms = cell.params["interval_s"] * 1000
    for req in reqs:
        keys, vals = family.reference(cell, req)
        agg = np.mean if req["class"] == "double_groupby" else np.max
        b = 360 if req["class"] != "single_groupby" else 6
        want = {}
        if req["class"] == "double_groupby":
            for h in range(v.shape[1]):
                for k in range(req["k0"], req["k1"], b):
                    want[(h, cell.ds.T0 + k * step_ms)] = [
                        agg(v[k:k + b, h, f]) for f in range(req["metrics"])]
        else:
            for k in range(req["k0"], req["k1"], b):
                want[(cell.ds.T0 + k * step_ms,)] = [
                    agg([v[s, h, f] for s in range(k, k + b)
                         for h in req["hosts"]])
                    for f in range(req["metrics"])]
        assert [tuple(k) for k in keys.tolist()] == sorted(want)
        np.testing.assert_allclose(
            vals, [want[tuple(k)] for k in keys.tolist()], rtol=1e-12)


def test_tsbs_rows_carry_the_ten_tags():
    import io

    import pyarrow as pa

    cell = _cell("tsbs-cpu-4000")
    ds, tags = cell.ds, cell.data["tags"]
    assert list(tags) == ds.TAGS and len(ds.TAGS) == 10
    for t in ds.TAGS:
        assert f"{t} STRING" in ds.ddl(cell.params)[0]
    assert f"PRIMARY KEY ({', '.join(ds.TAGS)})" in ds.ddl(cell.params)[0]
    # a datacenter lies in its host's region; the same seed, the same tags
    for r, d in zip(tags["region"][0], tags["datacenter"][0]):
        assert tags["datacenter"][1][d].startswith(tags["region"][1][r])
    again = ds.host_tags(11, cell.params["hosts"])
    assert all((again[t][0] == tags[t][0]).all() for t in ds.TAGS)
    _table, body, n = next(ds.arrow_bodies(cell.data, cell.params))
    got = pa.ipc.open_stream(io.BytesIO(body)).read_all()
    assert got.num_rows == n
    hosts = cell.params["hosts"]
    for t in ds.TAGS:
        want = [tags[t][1][c] for c in tags[t][0]]
        assert got.column(t).to_pylist()[hosts:2 * hosts] == want


def test_rate_reference_is_the_brute_force_loop():
    cell = _cell("prom-node-64")
    family, reqs = _requests(cell, "prom_mode_rate", 8)
    p, ds = cell.params, cell.ds
    v32 = cell.data["values"].astype(np.float32).astype(np.float64)
    for req in reqs:
        keys, vals = family.reference(cell, req)
        got = {tuple(k): x for k, x in zip(keys.tolist(), vals[:, 0])}
        mi = ds.MODES.index(req["mode"])
        n = 0
        for inst in range(p["instances"]):
            for t in range(req["start_s"], req["end_s"] + 1, family.STEP_S):
                total = 0.0
                for cpu in range(p["cpus"]):
                    s = (inst * p["cpus"] + cpu) * len(ds.MODES) + mi
                    # samples in (t - range, t], one by one
                    pts = [(ds.T0 // 1000 + k * p["interval_s"], v32[k, s])
                           for k in range(ds.steps(p))
                           if t - family.RANGE_S
                           < ds.T0 // 1000 + k * p["interval_s"] <= t]
                    assert len(pts) >= 2
                    delta = pts[-1][1] - pts[0][1]  # no resets in this data
                    sampled = pts[-1][0] - pts[0][0]
                    avg = sampled / (len(pts) - 1)
                    to_start = pts[0][0] - (t - family.RANGE_S)
                    to_end = t - pts[-1][0]
                    if to_start >= 1.1 * avg:
                        to_start = avg / 2
                    if to_end >= 1.1 * avg:
                        to_end = avg / 2
                    if delta > 0:
                        to_start = min(to_start,
                                       sampled * pts[0][1] / delta)
                    total += (delta * (sampled + to_start + to_end)
                              / sampled / family.RANGE_S)
                assert got[(inst, t * 1000)] == pytest.approx(total, rel=1e-9)
                n += 1
        assert n == len(got)


def test_rate_counts_a_counter_reset():
    family = run.load_module("queries", "prom_rate")
    vals = np.array([[10.0], [20.0], [5.0], [15.0]])  # reset after 20
    out = family.rate(vals, 0, 15_000, np.array([45_000]), 60_000)
    # increase 10 + 5 (after the reset) + 10 = 25 over 45 s sampled; the
    # window reaches 15 s before the first sample, under 1.1 x the 15 s
    # between samples, so all of it is extrapolated over, and the time to
    # zero, 45 * 10 / 25 = 18 s, does not cap it
    assert out[0, 0] == pytest.approx(25 * (45 + 15) / 45 / 60)
    # a gap of 1.1 intervals or more is extrapolated over by half an interval
    out = family.rate(vals, 0, 15_000, np.array([45_000]), 62_000)
    assert out[0, 0] == pytest.approx(25 * (45 + 7.5) / 45 / 62)


def test_needed_bytes_hand_worked():
    tsbs = _cell("tsbs-cpu-4000")
    tsbs.params = {"hosts": 4000, "hours": 12, "interval_s": 10}
    sql = run.load_module("queries", "tsbs_sql")
    # single-groupby-5-8-1: 8 hosts x 60 minutes x 5 fields x 4 B read,
    # 60 rows x (8 B minute + 5 x 4 B) written
    req = {"class": "single_groupby", "k0": 0, "k1": 360,
           "hosts": list(range(8)), "metrics": 5}
    assert sql.needed_bytes(tsbs, req) == 8 * 60 * 5 * 4 + 60 * 28
    # cpu-max-all-8: 8 hosts x 8 hours x 10 fields x 4 B, 8 rows x 48 B
    req = {"class": "cpu_max_all", "k0": 0, "k1": 8 * 360,
           "hosts": list(range(8)), "metrics": 10}
    assert sql.needed_bytes(tsbs, req) == 8 * 8 * 10 * 4 + 8 * 48
    # double-groupby-all: 4000 x 12 x 10 x 4 B, 48,000 rows x (16 + 40) B
    req = {"class": "double_groupby", "k0": 0, "k1": 12 * 360,
           "hosts": None, "metrics": 10}
    assert sql.needed_bytes(tsbs, req) == 1_920_000 + 48_000 * 56
    node = _cell("prom-node-64")
    node.params = {"instances": 64, "cpus": 8, "hours": 12, "interval_s": 15}
    prom = run.load_module("queries", "prom_rate")
    # 512 matched series x (3600 + 300) / 15 samples x 12 B, 64 x 61 points
    req = {"class": "mode_rate", "start_s": 0, "end_s": 3600}
    assert prom.needed_bytes(node, req) == 512 * 260 * 12 + 64 * 61 * 12
