"""kube-apiserver's ``apiserver_request_sli_duration_seconds`` as a
highly available control plane exposes it and kube-prometheus scrapes
it: a histogram of 21 finite bounds (0.05 ... 60 s) plus ``+Inf``, one
``_bucket`` counter a label set and bound, under the metric's labels
(``verb``, ``group``, ``version``, ``resource``, ``subresource``,
``scope``, ``component``), ``le``, and the target's (``instance``,
``job="apiserver"``), every ``interval_s`` seconds.  One table.

numpy and pyarrow only; nothing of a sibling data set.  ``params``:
``replicas``, ``resources``, ``namespaced``, ``requests_per_s`` (a
replica), ``interval_s``, ``hours``.  What is fixed by the parameters
alone, so that ``rows(params)`` needs no seed:

- A namespaced resource has 9 label sets a replica: GET / resource,
  LIST / namespace, LIST / cluster, POST / namespace, PUT, PATCH and
  DELETE / resource, PUT and PATCH / resource on ``status``; a
  cluster-scoped one 8, the same without LIST / namespace and with POST
  / cluster.  Four more a replica on subresources the SLI rules leave
  out: GET ``pods/log``, ``pods/proxy``, ``nodes/proxy``,
  ``services/proxy``.  At 160 namespaced and 40 cluster-scoped
  resources: 1,764 label sets a replica, x 22 buckets x 3 replicas =
  116,424 series, each with a sample at every scrape.
- The read rule (``verb=~"LIST|GET"``) matches 560 label sets a replica
  (36,960 series), the write rule (``verb=~"POST|PUT|PATCH|DELETE"``)
  1,200 (79,200 series); both fold to ``resources`` x 22 buckets.

From the seed: which label set takes which Zipf rank (rate proportional
to 1/rank, ``requests_per_s`` a replica over its label sets), each
replica's share of a label set (0.8 to 1.2 of the mean), each scrape's
requests (Poisson) and their latencies (a multinomial draw over the
buckets from a log-normal a verb class, ``LATENCY``), made cumulative
over ``le``, so that every scrape's buckets are monotonic; each
replica's uptime (``UPTIME_DAYS``) and the counters' start (the same
draw over ``rate x uptime`` requests); the replica that restarts inside
the hour and the scrape at which all its counters read 0.  The busiest
counters pass 2^24 (exact in float64, not in float32) and every value is
a whole number under 2^40.

``generate`` returns ``values`` {table: [steps, series] float64},
``tags`` {name: (codes int32 [series], vocabulary list)}, and for the
reference ``resource``, ``bucket`` (slot in ``BOUNDS``), ``read`` and
``write`` (bool [series]: the rules' matchers), ``replica``,
``restart`` (replica, step).
"""

from __future__ import annotations

import io
import math

import numpy as np

TABLE = "apiserver_request_sli_duration_seconds_bucket"
TABLES = (TABLE,)
TAGS = ["component", "group", "instance", "job", "le", "resource", "scope",
        "subresource", "verb", "version"]
T0 = 1704067200000  # 2024-01-01T00:00:00Z (ms)
# the metric's bounds in k8s.io/apiserver/pkg/endpoints/metrics, s
BOUNDS = (0.05, 0.1, 0.2, 0.4, 0.6, 0.8, 1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 5.0,
          6.0, 8.0, 10.0, 15.0, 20.0, 30.0, 45.0, 60.0, math.inf)
LE = [("+Inf" if math.isinf(b) else f"{b:g}") for b in BOUNDS]
# (median s, sigma of the log) of a request's latency by verb class
LATENCY = {"get": (0.025, 0.9), "list": (0.12, 1.2), "write": (0.045, 0.9)}
UPTIME_DAYS = (3.0, 30.0)    # a replica's uptime, uniform between
REPLICA_SHARE = (0.8, 1.2)   # a replica's share of a label set's mean
# (verb, scope, subresource) a namespaced resource's label sets
NAMESPACED = [("GET", "resource", ""), ("LIST", "namespace", ""),
              ("LIST", "cluster", ""), ("POST", "namespace", ""),
              ("PUT", "resource", ""), ("PATCH", "resource", ""),
              ("DELETE", "resource", ""), ("PUT", "resource", "status"),
              ("PATCH", "resource", "status")]
CLUSTER = [("GET", "resource", ""), ("LIST", "cluster", ""),
           ("POST", "cluster", ""), ("PUT", "resource", ""),
           ("PATCH", "resource", ""), ("DELETE", "resource", ""),
           ("PUT", "resource", "status"), ("PATCH", "resource", "status")]
# GET on subresources the SLI rules leave out: (resource, subresource)
EXCLUDED = [("pods", "log"), ("pods", "proxy"), ("nodes", "proxy"),
            ("services", "proxy")]
READ_VERBS = ("LIST", "GET")
WRITE_VERBS = ("POST", "PUT", "PATCH", "DELETE")
SLI_EXCLUDED_SUB = ("proxy", "attach", "log", "exec", "portforward")
# built-in resources (group, version, name); CRDs fill the rest
BUILTIN_NAMESPACED = [
    ("", "v1", "pods"), ("", "v1", "services"), ("", "v1", "endpoints"),
    ("", "v1", "configmaps"), ("", "v1", "secrets"), ("", "v1", "events"),
    ("", "v1", "persistentvolumeclaims"), ("", "v1", "serviceaccounts"),
    ("", "v1", "limitranges"), ("", "v1", "resourcequotas"),
    ("", "v1", "podtemplates"), ("", "v1", "replicationcontrollers"),
    ("apps", "v1", "deployments"), ("apps", "v1", "replicasets"),
    ("apps", "v1", "statefulsets"), ("apps", "v1", "daemonsets"),
    ("apps", "v1", "controllerrevisions"), ("batch", "v1", "jobs"),
    ("batch", "v1", "cronjobs"), ("networking.k8s.io", "v1", "ingresses"),
    ("networking.k8s.io", "v1", "networkpolicies"),
    ("coordination.k8s.io", "v1", "leases"),
    ("discovery.k8s.io", "v1", "endpointslices"),
    ("autoscaling", "v2", "horizontalpodautoscalers"),
    ("policy", "v1", "poddisruptionbudgets"),
    ("rbac.authorization.k8s.io", "v1", "roles"),
    ("rbac.authorization.k8s.io", "v1", "rolebindings"),
    ("events.k8s.io", "v1", "events"),
    ("storage.k8s.io", "v1", "csistoragecapacities"),
    ("authorization.k8s.io", "v1", "localsubjectaccessreviews"),
]
BUILTIN_CLUSTER = [
    ("", "v1", "nodes"), ("", "v1", "namespaces"),
    ("", "v1", "persistentvolumes"), ("", "v1", "componentstatuses"),
    ("rbac.authorization.k8s.io", "v1", "clusterroles"),
    ("rbac.authorization.k8s.io", "v1", "clusterrolebindings"),
    ("storage.k8s.io", "v1", "storageclasses"),
    ("storage.k8s.io", "v1", "csinodes"), ("storage.k8s.io", "v1", "csidrivers"),
    ("storage.k8s.io", "v1", "volumeattachments"),
    ("scheduling.k8s.io", "v1", "priorityclasses"),
    ("node.k8s.io", "v1", "runtimeclasses"),
    ("apiextensions.k8s.io", "v1", "customresourcedefinitions"),
    ("apiregistration.k8s.io", "v1", "apiservices"),
    ("admissionregistration.k8s.io", "v1", "mutatingwebhookconfigurations"),
    ("admissionregistration.k8s.io", "v1", "validatingwebhookconfigurations"),
    ("certificates.k8s.io", "v1", "certificatesigningrequests"),
    ("flowcontrol.apiserver.k8s.io", "v1", "flowschemas"),
    ("flowcontrol.apiserver.k8s.io", "v1", "prioritylevelconfigurations"),
    ("networking.k8s.io", "v1", "ingressclasses"),
    ("authentication.k8s.io", "v1", "tokenreviews"),
    ("authorization.k8s.io", "v1", "subjectaccessreviews"),
]
_BODY_BYTES = 46 << 20       # the server refuses bodies over 64 MiB
_ROW_BYTES = 16 + 4 * len(TAGS)


def steps(params: dict) -> int:
    return int(round(params["hours"] * 3600)) // params["interval_s"]


def resources(params: dict) -> list[tuple[str, str, str, bool]]:
    """(group, version, name, namespaced) of each resource: the built-in
    ones, then CRDs to ``namespaced`` and ``resources``.  Two built-ins of
    one name in two groups (``events``) are two resources to the API
    server and one ``resource`` label value to a ``by (resource)`` rule:
    the CRDs' names are all distinct, so this list's names are too once
    the second ``events`` is left out."""
    ns_n, n = params["namespaced"], params["resources"]
    seen: set[str] = set()
    out = []
    for (g, v, name), namespaced in (
            [(r, True) for r in BUILTIN_NAMESPACED]
            + [(r, False) for r in BUILTIN_CLUSTER]):
        if name not in seen:
            seen.add(name)
            out.append((g, v, name, namespaced))
    ns = [r for r in out if r[3]][:ns_n]
    cl = [r for r in out if not r[3]][:n - ns_n]
    ns += [(f"crd{i:03d}.example.com", "v1", f"crd{i:03d}s", True)
           for i in range(ns_n - len(ns))]
    cl += [(f"ccrd{i:03d}.example.com", "v1", f"ccrd{i:03d}s", False)
           for i in range(n - ns_n - len(cl))]
    return ns + cl


def label_sets(params: dict) -> list[tuple[int, str, str, str]]:
    """(resource index, verb, scope, subresource) of a replica's label
    sets, in the order the series are laid out."""
    res = resources(params)
    out = []
    for i, (_g, _v, _name, namespaced) in enumerate(res):
        out += [(i, *ls) for ls in (NAMESPACED if namespaced else CLUSTER)]
    index = {r[2]: i for i, r in enumerate(res)}
    out += [(index[name], "GET", "resource", sub) for name, sub in EXCLUDED
            if name in index]
    return out


def n_series(params: dict) -> int:
    return params["replicas"] * len(label_sets(params)) * len(BOUNDS)


def rows(params: dict) -> int:
    return steps(params) * n_series(params)


def verb_class(verb: str) -> str:
    return {"GET": "get", "LIST": "list"}.get(verb, "write")


def bucket_shares(cls: str) -> np.ndarray:
    """[22]: a request's chance of each bucket (not cumulative) under the
    class's log-normal latency."""
    median, sigma = LATENCY[cls]
    edges = np.array([b for b in BOUNDS[:-1]])
    cdf = 0.5 * (1.0 + np.array([math.erf(math.log(e / median)
                                          / (sigma * math.sqrt(2.0)))
                                 for e in edges]))
    p = np.diff(np.concatenate([[0.0], cdf, [1.0]]))
    return p / p.sum()


def _cumulative_draw(rng, n: np.ndarray, cls_of: np.ndarray) -> np.ndarray:
    """[..., L, 22] cumulative bucket counts of ``n`` [..., L] requests
    each drawn over its label set's class's buckets."""
    out = np.zeros(n.shape + (len(BOUNDS),), np.int64)
    for cls in LATENCY:
        on = cls_of == cls
        if on.any():
            out[..., on, :] = rng.multinomial(n[..., on], bucket_shares(cls))
    return np.cumsum(out, axis=-1)


def generate(seed: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    n_steps, interval = steps(params), params["interval_s"]
    sets = label_sets(params)
    n_sets, reps, k = len(sets), params["replicas"], len(BOUNDS)
    cls_of = np.array([verb_class(v) for _r, v, _s, _sub in sets])
    # Zipf over label sets: the rank-r set has a share 1/r of the rate
    ranks = rng.permutation(n_sets) + 1
    share = (1.0 / ranks) / (1.0 / np.arange(1, n_sets + 1)).sum()
    rate = (params["requests_per_s"] * share[None, :]
            * rng.uniform(*REPLICA_SHARE, size=(reps, n_sets)))   # [R, L]
    uptime = rng.uniform(*UPTIME_DAYS, size=reps) * 86400.0
    start = _cumulative_draw(rng, rng.poisson(rate * uptime[:, None]),
                             cls_of)                            # [R, L, K]
    inc = _cumulative_draw(
        rng, rng.poisson(np.broadcast_to(rate * interval,
                                         (n_steps, reps, n_sets))),
        cls_of)                                                 # [T, R, L, K]
    values = start[None] + np.cumsum(inc, axis=0)
    # a rolling upgrade: one replica restarts, its counters read 0 at one
    # scrape and count again from there
    r_rep = int(rng.integers(reps))
    r_step = int(rng.integers(2, n_steps - 1))
    values[r_step:, r_rep] = np.cumsum(inc[r_step:, r_rep], axis=0) \
        - inc[r_step, r_rep][None]
    values = values.reshape(n_steps, -1).astype(np.float64)

    # series order: replica, label set, bucket
    rep_of = np.repeat(np.arange(reps), n_sets * k)
    set_of = np.tile(np.repeat(np.arange(n_sets), k), reps)
    bucket = np.tile(np.arange(k), reps * n_sets)
    res = resources(params)
    res_of = np.array([s[0] for s in sets])[set_of]
    verbs = sorted({s[1] for s in sets})
    scopes = sorted({s[2] for s in sets})
    subs = sorted({s[3] for s in sets})
    groups = sorted({r[0] for r in res})
    versions = sorted({r[1] for r in res})

    def coded(vocab, of):
        return (np.array([vocab.index(x) for x in of], np.int32), vocab)

    verb = [sets[i][1] for i in range(n_sets)]
    scope = [sets[i][2] for i in range(n_sets)]
    sub = [sets[i][3] for i in range(n_sets)]
    res_names = [r[2] for r in res]
    zero = np.zeros(len(set_of), np.int32)
    tags = {
        "component": (zero, ["apiserver"]),
        "group": (coded(groups, [res[i][0] for i in res_of])[0], groups),
        "instance": (rep_of.astype(np.int32),
                     [f"10.0.0.{j + 1}:6443" for j in range(reps)]),
        "job": (zero, ["apiserver"]),
        "le": (bucket.astype(np.int32), list(LE)),
        "resource": (res_of.astype(np.int32), res_names),
        "scope": (coded(scopes, scope)[0][set_of], scopes),
        "subresource": (coded(subs, sub)[0][set_of], subs),
        "verb": (coded(verbs, verb)[0][set_of], verbs),
        "version": (coded(versions, [res[i][1] for i in res_of])[0],
                    versions),
    }
    set_verb = np.array(verb)[set_of]
    kept = ~np.isin(np.array(sub)[set_of], SLI_EXCLUDED_SUB)
    return {"values": {TABLE: values}, "tags": tags,
            "resource": res_of.astype(np.int64), "bucket": bucket,
            "replica": rep_of,
            "read": np.isin(set_verb, READ_VERBS) & kept,
            "write": np.isin(set_verb, WRITE_VERBS) & kept,
            "restart": (r_rep, r_step)}


def ddl(params: dict) -> list[str]:
    cols = ", ".join(f"`{t}` STRING" for t in TAGS)
    keys = ", ".join(f"`{t}`" for t in TAGS)
    return [f"CREATE TABLE {TABLE} ({cols}, ts TIMESTAMP(3) TIME INDEX, "
            f"greptime_value DOUBLE, PRIMARY KEY ({keys}))"]


def count_sql(params: dict) -> str:
    return f"SELECT count(*) FROM {TABLE}"


def arrow_bodies(data: dict, params: dict):
    """Yields (table, Arrow IPC stream bytes, rows): a body holds a block
    of series with every sample each of them has, in time order; every
    tag is a dictionary of a few hundred entries at most, sent whole."""
    import pyarrow as pa

    tags = data["tags"]
    vals = data["values"][TABLE]
    n_steps, n = vals.shape
    step_ms = params["interval_s"] * 1000
    vocab = {name: pa.array(v, type=pa.string())
             for name, (_c, v) in tags.items()}
    shared = sum(v.nbytes for v in vocab.values())
    per = max(1, (_BODY_BYTES - shared) // (n_steps * _ROW_BYTES))
    ts = T0 + np.arange(n_steps, dtype=np.int64) * step_ms
    for s in range(0, n, per):
        block = vals[:, s:s + per]
        m = block.shape[1]
        cols = {name: pa.DictionaryArray.from_arrays(
                    pa.array(np.repeat(codes[s:s + per], n_steps)),
                    vocab[name])
                for name, (codes, _v) in tags.items()}
        cols["ts"] = pa.array(np.tile(ts, m))
        cols["greptime_value"] = pa.array(block.T.reshape(-1))
        batch = pa.table(cols)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, batch.schema) as w:
            w.write_table(batch)
        yield TABLE, sink.getvalue(), n_steps * m
