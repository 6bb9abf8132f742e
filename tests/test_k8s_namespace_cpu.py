"""The cell ``k8s100k.namespace_cpu`` at its rehearsal size on the CPU:
cAdvisor's CPU counters under ten labels (benchmark/datasets/
k8s_cadvisor.py: restarts, replaced pods, pod-level series without an
image) loaded through the HTTP server, the cluster panel's request sent
through ``/v1/prometheus/api/v1/query_range``, keys compared exactly and
values with the family's own reference (benchmark/queries/k8s_rate.py)
under the family's limit.  What the deployment forced of the program is
held here too: the write path's series resolution past 62 bits of tag
codes, ``image!=""`` over empty and NULL tags, and the counters the
cell's per-layer metrics read.
"""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.promql import engine as pe
from greptimedb_tpu.servers import HttpServer
from greptimedb_tpu.standalone import GreptimeDB
from greptimedb_tpu.utils.telemetry import REGISTRY

WORKLOAD = "k8s100k.namespace_cpu"
SELECTED = "greptime_promql_selected_series_total"
PADDED = "greptime_promql_padded_series_total"
BUILDS = "greptime_compile_xla_builds_total"


@pytest.mark.parametrize(
    "served", [(WORKLOAD, seed) for seed in (7, 2100000777, 1900000333)],
    indirect=True)
def test_replies_agree_with_the_reference(served):
    data = served.cell.data
    # the data holds what the issue asks the program to cope with
    assert (np.diff(data["values"], axis=0) < 0).any()      # a restart
    assert np.isnan(data["values"][0]).any()                # a new pod
    assert np.isnan(data["values"][-1]).any()               # a replaced one
    limit = served.family.LIMITS["namespace_cpu"]
    for _ in range(4):
        req = served.traffic.next()
        verdict, err = served.judge(req)
        assert verdict == "ok"
        assert err <= limit
        keys, _vals = served.family.reference(served.cell, req)
        assert len(keys) == served.cell.params["namespaces"] * 61


@pytest.mark.parametrize("served", [(WORKLOAD, 11)], indirect=True)
def test_another_end_builds_no_program_and_counts_its_series(served):
    reqs = [served.traffic.next() for _ in range(8)]
    first = reqs[0]
    other = next(r for r in reqs if r["end_s"] != first["end_s"])
    assert served.judge(first)[0] == "ok"
    kernels = len(pe._KERNEL_CACHE)
    builds = REGISTRY.value(BUILDS, ("promql",))
    selected, padded = REGISTRY.value(SELECTED, ()), REGISTRY.value(PADDED, ())
    assert served.judge(other)[0] == "ok"
    assert len(pe._KERNEL_CACHE) == kernels
    assert REGISTRY.value(BUILDS, ("promql",)) == builds
    # one dispatch: the containers' series, padded to a power of two
    matched = served.cell.ds.matched_series(served.cell.params)
    assert REGISTRY.value(SELECTED, ()) - selected == matched == 126
    assert REGISTRY.value(PADDED, ()) - padded == 128
    # /metrics carries both, and the cell's readers divide them
    after = served.client.metrics()
    assert SELECTED in after and PADDED in after
    ctx = {"metrics_before": {SELECTED: selected, PADDED: padded},
           "metrics_after": after, "log": [other]}
    read = {name: served.run.load_module("layer_metrics", name).read(ctx)
            for name in ("selected_series_per_query", "series_pad_fill_pct")}
    assert read == {"selected_series_per_query": 126.0,
                    "series_pad_fill_pct": 100.0 * 126 / 128}
    # a program without the counters gives nothing and does not raise
    bare = {"metrics_before": {}, "metrics_after": {"x": 1.0}, "log": [other]}
    for name in read:
        assert served.run.load_module("layer_metrics", name).read(bare) is None


def _query_range(port: int, query: str, start: int, end: int, step: int):
    qs = urllib.parse.urlencode({"query": query, "start": start, "end": end,
                                 "step": step})
    url = f"http://127.0.0.1:{port}/v1/prometheus/api/v1/query_range?{qs}"
    with urllib.request.urlopen(url) as r:
        body = json.loads(r.read())
    assert body["status"] == "success"
    return {tuple(sorted(s["metric"].items())): s["values"]
            for s in body["data"]["result"]}


def test_negative_matcher_drops_empty_and_null_tags():
    """``image!=""`` keeps a series only where it has an image, whether
    the table holds '' or NULL for the others."""
    db = GreptimeDB()
    srv = HttpServer(db, port=0)
    srv.start()
    try:
        db.sql("CREATE TABLE c (image STRING, pod STRING, job STRING, "
               "ts TIMESTAMP(3) TIME INDEX, greptime_value DOUBLE, "
               "PRIMARY KEY (image, pod, job))")
        rows = []
        for i in range(12):
            t = 1000 * 30 * i
            rows += [f"('img', 'a', 'cadvisor', {t}, {2.0 * i})",
                     f"('', 'b', 'cadvisor', {t}, {3.0 * i})",
                     f"(NULL, 'c', 'cadvisor', {t}, {5.0 * i})",
                     f"('img', 'd', 'other', {t}, {7.0 * i})"]
        db.sql("INSERT INTO c VALUES " + ",".join(rows))
        q = 'sum by (pod)(rate(c{job="cadvisor",image%s""}[2m]))'
        kept = _query_range(srv.port, q % "!=", 300, 330, 30)
        assert set(kept) == {(("pod", "a"),)}
        dropped = _query_range(srv.port, q % "=", 300, 330, 30)
        assert set(dropped) == {(("pod", "b"),), (("pod", "c"),)}
        assert float(kept[(("pod", "a"),)][0][1]) == pytest.approx(2.0 / 30)
    finally:
        srv.stop()
        db.close()


@pytest.mark.parametrize("wide", [False, True])
def test_series_ids_past_62_bits_of_tag_codes(wide):
    """Four tags of 70,000 values each are 68 bits of codes: the write
    path folds them with a dense re-coding in between.  Every distinct
    tuple gets one id, ids go out in first-occurrence order, and a second
    write of the same tuples (in another order, with new ones among
    them) finds them again."""
    from greptimedb_tpu.datatypes.batch import DictColumn

    n_vals = 70_000 if wide else 300
    db = GreptimeDB()
    try:
        db.sql("CREATE TABLE w (a STRING, b STRING, c STRING, d STRING, "
               "ts TIMESTAMP(3) TIME INDEX, v DOUBLE, "
               "PRIMARY KEY (a, b, c, d))")
        region = db._region_of("w")
        rng = np.random.default_rng(5)
        vocab = np.array([f"v{i:05d}" for i in range(n_vals)], dtype=object)

        def write(codes, t0):
            n = len(codes)
            # every value of every vocabulary is referenced once, so the
            # code widths are the vocabularies'
            full = np.concatenate([codes, np.tile(
                np.arange(n_vals, dtype=np.int64)[:, None], (1, 4))])
            cols = {name: DictColumn(vocab, full[:, j].astype(np.int32))
                    for j, name in enumerate("abcd")}
            cols["ts"] = t0 + np.arange(len(full), dtype=np.int64)
            cols["v"] = np.ones(len(full))
            tags = {name: cols[name] for name in "abcd"}
            tsids = region._encode_tags(tags, len(full))
            return tsids[:n], full

        first = rng.integers(n_vals, size=(4000, 4))
        first[1000:2000] = first[:1000]            # repeats inside a write
        got, full = write(first, 0)
        if wide:
            widths = [int(full[:, j].max()).bit_length() for j in range(4)]
            assert sum(widths) > 62
        seen: dict[tuple, int] = {}
        for row in full.tolist():
            seen.setdefault(tuple(row), len(seen))
        want = np.array([seen[tuple(r)] for r in first.tolist()])
        assert np.array_equal(got, want)
        second = np.concatenate([first[::-1][:1500],
                                 rng.integers(n_vals, size=(500, 4))])
        got2, full2 = write(second, 10_000_000)
        for row in full2.tolist():
            seen.setdefault(tuple(row), len(seen))
        assert np.array_equal(
            got2, np.array([seen[tuple(r)] for r in second.tolist()]))
        assert region.num_series == len(seen)
    finally:
        db.close()
