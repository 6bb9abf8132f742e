"""cAdvisor's ``container_cpu_usage_seconds_total`` as a kubelet exposes
it and kube-prometheus scrapes it: one counter a container and one a
pod-level cgroup, scraped every ``interval_s`` seconds, under ten labels
(``container``, ``cpu``, ``id``, ``image``, ``name``, ``namespace``,
``pod`` from cAdvisor; ``instance``, ``job``, ``node`` from the target).

numpy and pyarrow only.  ``params``: ``nodes``, ``pods``, ``namespaces``,
``interval_s``, ``hours``.  What is fixed by the parameters alone, so
that ``rows(params)`` needs no seed:

- ``pods`` pod slots are alive at every moment; namespace k (by rank)
  holds a share of them proportional to 1/k (``namespace_pods``).
- Half of the slots run two containers and half one (mean 1.5), and
  every pod has its pod-level cgroup series, whose ``image``,
  ``container`` and ``name`` are empty, as Prometheus reads an absent
  label: 2.5 series a slot.
- 5 % of the slots (half of them two-container ones) have their pod
  replaced once inside the data: the old pod's series end, and at the
  next scrape the series of a new pod (new ``pod``, ``id``, ``name``,
  perhaps another node; same namespace, containers and images) begin
  from a counter near 0.  So the table holds 2.5 x 1.05 x ``pods``
  series and every scrape has 2.5 x ``pods`` samples.
- 1 % of the container series restart once inside their life: the
  counter falls to the fraction of an interval and goes on, same labels.
  A pod-level cgroup outlives its containers and does not fall.

From the seed: which slots, nodes, images, rates (log-normal a
container, median 0.03 cores, clipped to [1e-4, 4]), ages, and the
steps of replacements and restarts.  A container's counter starts at
rate x age with age up to three days and the product capped at 36,000 s,
so a container stays under 65,536 s and a pod-level sum under 131,072 s
through the hour (float32 resolves 0.004 s and 0.008 s there).

``generate`` returns ``values`` [steps, series] float64 with NaN where a
series has no sample (never sent), ``tags`` {name: (codes int32 [series],
vocabulary list)}, and for the reference ``namespace`` (= the codes of
that tag) and ``matched`` (the series with a non-empty ``image``).
"""

from __future__ import annotations

import io

import numpy as np

TABLE = "container_cpu_usage_seconds_total"
TAGS = ["container", "cpu", "id", "image", "instance", "job", "name",
        "namespace", "node", "pod"]
T0 = 1704067200000  # 2024-01-01T00:00:00Z (ms)
REPLACED_SHARE = 0.05
RESTART_SHARE = 0.01
_CONTAINERS = 48     # distinct container names
_IMAGES = 320        # distinct images
_BODY_BYTES = 46 << 20   # the server refuses bodies over 64 MiB
_ROW_BYTES = 16 + 4 * len(TAGS)


def steps(params: dict) -> int:
    return params["hours"] * 3600 // params["interval_s"]


def replaced_pods(params: dict) -> int:
    """Even, so that half of them are two-container pods."""
    return 2 * int(round(params["pods"] * REPLACED_SHARE / 2))


def live_series(params: dict) -> int:
    return params["pods"] + params["pods"] // 2 * 3 + (params["pods"] % 2)


def n_series(params: dict) -> int:
    return live_series(params) + replaced_pods(params) // 2 * 5


def matched_series(params: dict) -> int:
    """Series with an image: the containers, the replaced pods' too."""
    return n_series(params) - params["pods"] - replaced_pods(params)


def rows(params: dict) -> int:
    return steps(params) * live_series(params)


def namespace_pods(params: dict) -> np.ndarray:
    """Pod slots of each namespace, by rank: proportional to 1/rank,
    at least one, summing to ``pods``."""
    k = params["namespaces"]
    share = 1.0 / np.arange(1, k + 1)
    want = share / share.sum() * (params["pods"] - k)
    n = 1 + np.floor(want).astype(np.int64)
    short = params["pods"] - int(n.sum())
    n[np.argsort(-(want - np.floor(want)), kind="stable")[:short]] += 1
    return n


def namespace_names(params: dict) -> list[str]:
    return [f"ns-{k:03d}" for k in range(params["namespaces"])]


def _hex(rng, count: int, width: int) -> list[str]:
    text = rng.bytes(count * width // 2).hex()
    return [text[i * width:(i + 1) * width] for i in range(count)]


def generate(seed: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    n_steps, interval = steps(params), params["interval_s"]
    slots, nodes = params["pods"], params["nodes"]
    two = np.zeros(slots, bool)
    two[rng.permutation(slots)[:slots // 2]] = True
    slot_ns = rng.permutation(np.repeat(
        np.arange(params["namespaces"]), namespace_pods(params)))
    n_rep = replaced_pods(params)
    rep_slots = np.concatenate([
        rng.permutation(np.flatnonzero(two))[:n_rep // 2],
        rng.permutation(np.flatnonzero(~two))[:n_rep // 2]])
    rep_step = rng.integers(2, n_steps - 1, size=n_rep)

    # pods: the live slots' first pods, then the replacements
    pod_slot = np.concatenate([np.arange(slots), rep_slots])
    n_pods = len(pod_slot)
    pod_first = np.zeros(n_pods, np.int64)     # first step with a sample
    pod_end = np.full(n_pods, n_steps, np.int64)   # one past the last
    pod_end[rep_slots] = rep_step
    pod_first[slots:] = rep_step
    pod_node = rng.integers(nodes, size=n_pods)
    pod_uid = _hex(rng, n_pods, 32)
    workload = rng.integers(max(slots // 8, 1), size=slots)  # a Deployment
    pod_names = [f"app-{workload[s]:05d}-{u[:9]}-{u[9:14]}"
                 for s, u in zip(pod_slot, pod_uid)]

    # series: of every pod its cgroup, then its one or two containers
    per_pod = 2 + two[pod_slot].astype(np.int64)
    n = int(per_pod.sum())
    series_pod = np.repeat(np.arange(n_pods), per_pod)
    start_of = np.cumsum(per_pod) - per_pod
    which = np.arange(n) - start_of[series_pod]     # 0 = the pod's cgroup
    is_container = which > 0
    # a slot's containers keep their names, images and rates in the new pod
    slot_container = rng.integers(_CONTAINERS, size=(slots, 2))
    slot_image = rng.integers(_IMAGES, size=(slots, 2))
    slot_rate = np.clip(np.exp(rng.normal(np.log(0.03), 1.3,
                                          size=(slots, 2))), 1e-4, 4.0)
    s_slot = pod_slot[series_pod]
    c_idx = np.maximum(which - 1, 0)
    rate = np.where(is_container, slot_rate[s_slot, c_idx], 1e-3)
    first = pod_first[series_pod]
    end = pod_end[series_pod]

    # counters: increments of every scrape, summed; absent outside a life
    noise = np.clip(rng.normal(0.0, 1.0, size=(n_steps, n)), -3.0, 3.0)
    inc = rate[None, :] * interval * (1.0 + 0.3 * noise)
    del noise
    at = np.arange(n_steps)[:, None]
    alive = (at >= first[None, :]) & (at < end[None, :])
    inc *= alive
    age = rng.uniform(0.0, 3 * 86400.0, size=n)
    age[first > 0] = rng.uniform(0.0, interval, size=int((first > 0).sum()))
    start = np.minimum(rate * age, 36000.0)
    containers = np.flatnonzero(is_container)
    restarts = rng.permutation(containers)[
        :max(1, int(round(len(containers) * RESTART_SHARE)))]
    life = end[restarts] - first[restarts]
    restart_step = first[restarts] + 1 + (
        rng.random(len(restarts)) * (life - 1)).astype(np.int64)
    values = np.cumsum(inc, axis=0)
    # a pod's cgroup counts what its containers used, restarts included
    pod_total = np.zeros((n_steps, n_pods))
    np.add.at(pod_total.T, series_pod[containers],
              (values[:, containers] + start[None, containers]).T)
    # a restart: what was counted before it is gone, and so is the start
    before = values[restart_step - 1, restarts] + start[restarts]
    fell = at >= restart_step[None, :]
    values[:, restarts] -= fell * (before + rng.random(len(restarts))
                                   * inc[restart_step, restarts])[None, :]
    values += start[None, :]
    cgroups = np.flatnonzero(~is_container)
    values[:, cgroups] += pod_total[:, series_pod[cgroups]]
    values[~alive] = np.nan

    # labels
    cid = _hex(rng, n, 64)
    uid_of = [f"{u[:8]}-{u[8:12]}-{u[12:16]}-{u[16:20]}-{u[20:]}"
              for u in pod_uid]
    qos = ["burstable", "besteffort", "guaranteed"]
    pod_qos = rng.integers(3, size=n_pods)
    ids = [f"/kubepods/{qos[pod_qos[p]]}/pod{uid_of[p]}"
           + (f"/{cid[i]}" if c else "")
           for i, (p, c) in enumerate(zip(series_pod, is_container))]
    name_code = np.zeros(n, np.int32)
    name_code[containers] = 1 + np.arange(len(containers), dtype=np.int32)

    container_names = [""] + [f"c{j:02d}" for j in range(_CONTAINERS)]
    images = [""] + [f"registry.example/team-{j % 40}/svc-{j}:v1.{j % 17}"
                     for j in range(_IMAGES)]
    node_of = pod_node[series_pod].astype(np.int32)
    tags = {
        "container": (np.where(
            is_container, 1 + slot_container[s_slot, c_idx], 0
        ).astype(np.int32), container_names),
        "cpu": (np.zeros(n, np.int32), ["total"]),
        "id": (np.arange(n, dtype=np.int32), ids),
        "image": (np.where(is_container, 1 + slot_image[s_slot, c_idx], 0
                           ).astype(np.int32), images),
        "instance": (node_of, [f"10.{j // 250}.{j % 250}.10:10250"
                               for j in range(nodes)]),
        "job": (np.zeros(n, np.int32), ["cadvisor"]),
        "name": (name_code, [""] + [cid[i] for i in containers]),
        "namespace": (slot_ns[s_slot].astype(np.int32),
                      namespace_names(params)),
        "node": (node_of, [f"node-{j:04d}" for j in range(nodes)]),
        "pod": (series_pod.astype(np.int32), pod_names),
    }
    return {"values": values, "tags": tags,
            "namespace": slot_ns[s_slot].astype(np.int64),
            "matched": containers}


def ddl(params: dict) -> list[str]:
    cols = ", ".join(f"{t} STRING" for t in TAGS)
    return [f"CREATE TABLE {TABLE} ({cols}, ts TIMESTAMP(3) TIME INDEX, "
            f"greptime_value DOUBLE, PRIMARY KEY ({', '.join(TAGS)}))"]


def count_sql(params: dict) -> str:
    return f"SELECT count(*) FROM {TABLE}"


def arrow_bodies(data: dict, params: dict):
    """Yields (table, Arrow IPC stream bytes, rows) in time order; a
    series' absent samples are left out.  Every body carries the tags'
    dictionaries, so the steps a body holds are what fits beside them."""
    import pyarrow as pa

    values, tags = data["values"], data["tags"]
    n_steps, n = values.shape
    step_ms = params["interval_s"] * 1000
    vocab = {name: pa.array(v, type=pa.string())
             for name, (_c, v) in tags.items()}
    dict_bytes = sum(v.nbytes for v in vocab.values())
    per = max(1, (_BODY_BYTES - dict_bytes) // (_ROW_BYTES * n))
    for s in range(0, n_steps, per):
        block = values[s:s + per]
        at, series = np.nonzero(~np.isnan(block))
        cols = {name: pa.DictionaryArray.from_arrays(
            pa.array(codes[series]), vocab[name])
            for name, (codes, _v) in tags.items()}
        cols["ts"] = pa.array(T0 + (s + at).astype(np.int64) * step_ms)
        cols["greptime_value"] = pa.array(block[at, series])
        table = pa.table(cols)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        yield TABLE, sink.getvalue(), len(at)
