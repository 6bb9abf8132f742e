"""The cell ``apiserver116k.resource_p99`` at its rehearsal size on the
CPU: kube-apiserver's request-duration histogram (benchmark/datasets/
k8s_apiserver.py: three replicas, 22 buckets a label set, a replica that
restarts, counters past 2^24) loaded through the HTTP server, the read
and write latency SLI rules sent through
``/v1/prometheus/api/v1/query_range``, keys compared exactly and values
with the family's own float64 reference (benchmark/queries/
k8s_apiserver_quantile.py) under the family's limits.  What the
deployment forced of the program is held here too: the rule runs as ONE
fused program a request (bucket fold and quantile included), its
counters say so, and the argument evaluated and folded apart gives the
same answers.
"""

import numpy as np
import pytest

from greptimedb_tpu.compile.fused import FUSED_DISPATCHES
from greptimedb_tpu.utils.telemetry import REGISTRY

WORKLOAD = "apiserver116k.resource_p99"
QUANTILES = "greptime_promql_histogram_quantiles_total"
SLOTS = "greptime_promql_histogram_buckets_total"
FILLED = "greptime_promql_histogram_filled_buckets_total"


@pytest.mark.parametrize(
    "served", [(WORKLOAD, 7), (WORKLOAD, 2100000777, "one-device")],
    indirect=True)
def test_replies_agree_with_the_float64_reference(served):
    ds, family = served.cell.ds, served.family
    vals = served.cell.data["values"][ds.TABLE]
    # the data holds what the deployment asks the program to cope with
    assert (np.diff(vals, axis=0) < 0).any()            # a restart
    assert vals.max() > 2.0 ** 24                       # past f32's 2^24
    assert (vals % 1 == 0).all()                        # whole requests
    seen = set()
    for _ in range(4):
        req = served.traffic.next()
        seen.add(req["class"])
        verdict, err = served.judge(req)
        assert verdict == "ok"
        assert err <= family.LIMITS[req["class"]] / 3
        keys, _vals = family.reference(served.cell, req)
        assert 0 < len(keys) <= served.cell.params["resources"] * 61
    assert seen == set(family.LIMITS)


@pytest.mark.parametrize("served", [(WORKLOAD, 11, "one-device")],
                         indirect=True)
def test_one_fused_program_a_request(served):
    """Each rule is one fused dispatch, counted as a fused quantile a
    resource over 22 filled bucket slots a resource."""
    resources = served.cell.params["resources"]
    reqs = [served.traffic.next() for _ in range(2)]
    for req in reqs:   # warm: compile outside the counted requests
        assert served.judge(req)[0] == "ok"
    fused = REGISTRY.value(QUANTILES, ("fused",))
    slots, filled = REGISTRY.value(SLOTS, ()), REGISTRY.value(FILLED, ())
    before = FUSED_DISPATCHES["count"]
    for req in reqs:
        assert served.judge(req)[0] == "ok"
    assert FUSED_DISPATCHES["count"] == before + 2
    assert REGISTRY.value(QUANTILES, ("fused",)) - fused == 2 * resources
    assert REGISTRY.value(SLOTS, ()) - slots == 2 * resources * 22
    assert REGISTRY.value(FILLED, ()) - filled == 2 * resources * 22
    after = served.client.metrics()
    assert after[f'{QUANTILES}{{path="fused"}}'] >= 2 * resources


@pytest.mark.parametrize("served", [(WORKLOAD, 13, "one-device")],
                         indirect=True)
def test_unfused_fold_gives_the_same_answers(served, ineligible):
    """The rule with the fused road closed: the ``sum`` evaluated, its
    4,400 series numbered on the host and folded by one program — the
    same keys, and values within the limit, as the reference."""
    unfused = REGISTRY.value(QUANTILES, ("unfused",))
    with ineligible("fused_histogram"):
        for _ in range(2):
            req = served.traffic.next()
            verdict, err = served.judge(req)
            assert verdict == "ok"
            assert err <= served.family.LIMITS[req["class"]] / 3
    assert REGISTRY.value(QUANTILES, ("unfused",)) - unfused == (
        2 * served.cell.params["resources"])
