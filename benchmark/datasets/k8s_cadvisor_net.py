"""cAdvisor's ``container_network_receive_bytes_total`` and
``container_network_transmit_bytes_total`` as a kubelet exposes them and
kube-prometheus scrapes them: one counter a pod and metric (the pod's
network namespace, reported on its sandbox, ``interface="eth0"``),
scraped every ``interval_s`` seconds, under nine labels (``id``,
``image``, ``interface``, ``name``, ``namespace``, ``pod`` from cAdvisor;
``instance``, ``job``, ``node`` from the target).  Two tables, a table a
metric, over the same series.

numpy and pyarrow only; nothing of a sibling data set.  ``params``:
``nodes``, ``pods``, ``namespaces``, ``interval_s``, ``hours`` (a whole
number of scrapes; 0.75 is one).  What is fixed by the parameters alone,
so that ``rows(params)`` needs no seed:

- ``pods`` pod slots are alive at every moment; namespace k (by rank)
  holds a share of them proportional to 1/k (``namespace_pods``).
- 5 % of the slots have their pod replaced once inside the data: the old
  pod's series end, and at the next scrape the series of a new pod (new
  ``pod``, ``id``, ``name``, perhaps another node; same namespace) begin
  from a counter near 0.  So a table holds 1.05 x ``pods`` series and
  every scrape has ``pods`` samples a table.
- 1 % of the series restart once inside their life (the sandbox is made
  anew): both counters fall to under one interval's bytes and go on,
  same labels.

From the seed: which slots, nodes, byte rates (log-normal a pod, median
20 kB/s, sigma 2.0, clipped to [10 B/s, 1.25e9 B/s = 10 Gbit/s];
transmit drawn apart from receive), each scrape's increment (its mean
times a factor uniform in [0.1, 1.9], floored to a whole byte), ages,
and the steps of replacements and restarts.  A pod's counter starts at
rate x age with age uniform up to ``AGE_DAYS`` = 3 days, and at
``START_CAP`` = 3e14 where that is more (a pod at the clip's 10 Gbit/s
for nearly all of the 3 days): the fastest, oldest counter ends the hour
under 3.1e14, under 2^49 = 5.6e14.  **Every value is a whole number of
bytes under 2^49**, so every sample and every difference of two samples
is exact in float64, and in two float32 words (a whole number under 2^49
is f32(v) plus a whole number of at most 2^24).  The ages set how far
float32 is off: its step at a counter's value against the increase of a
window is, up to a constant, the pod's age against the range.

``generate`` returns ``values`` {table: [steps, series] float64 with NaN
where a series has no sample (never sent)}, ``tags`` {name: (codes int32
[series], vocabulary list)}, the same for both tables, and for the
reference ``namespace`` (= the codes of that tag).
"""

from __future__ import annotations

import io

import numpy as np

RECEIVE = "container_network_receive_bytes_total"
TRANSMIT = "container_network_transmit_bytes_total"
TABLES = (RECEIVE, TRANSMIT)
TAGS = ["id", "image", "interface", "name", "namespace", "pod", "instance",
        "job", "node"]
T0 = 1704067200000  # 2024-01-01T00:00:00Z (ms)
REPLACED_SHARE = 0.05
RESTART_SHARE = 0.01
RATE_MEDIAN = 20e3           # bytes/s
RATE_SIGMA = 2.0
RATE_CLIP = (10.0, 1.25e9)
BURST = (0.1, 1.9)           # each increment, as a share of its mean
AGE_DAYS = 3                 # a pod's counter starts at rate x age,
START_CAP = 3e14             # or here where that is more
LIMIT = float(1 << 49)       # every value lies under it
PER_SERIES = ("id", "name", "pod")   # tags with an entry a series
_BODY_BYTES = 46 << 20       # the server refuses bodies over 64 MiB
_ROW_BYTES = 16 + 4 * len(TAGS)


def steps(params: dict) -> int:
    return int(round(params["hours"] * 3600)) // params["interval_s"]


def replaced_pods(params: dict) -> int:
    return int(round(params["pods"] * REPLACED_SHARE))


def n_series(params: dict) -> int:
    """Series a table: the live slots' pods and the replaced ones'."""
    return params["pods"] + replaced_pods(params)


def restarted_series(params: dict) -> int:
    return max(1, int(round(n_series(params) * RESTART_SHARE)))


def rows(params: dict) -> int:
    return len(TABLES) * steps(params) * params["pods"]


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


def _counters(rng, params, first, end, restarts, restart_step) -> np.ndarray:
    """One metric's [steps, series] counters: whole bytes, NaN outside a
    series' life."""
    n_steps, interval = steps(params), params["interval_s"]
    n = len(first)
    rate = np.clip(np.exp(rng.normal(np.log(RATE_MEDIAN), RATE_SIGMA, size=n)),
                   *RATE_CLIP)
    inc = rng.uniform(*BURST, size=(n_steps, n))
    inc *= rate[None, :] * interval
    np.floor(inc, out=inc)
    at = np.arange(n_steps)[:, None]
    alive = (at >= first[None, :]) & (at < end[None, :])
    inc *= alive
    age = rng.uniform(0.0, AGE_DAYS * 86400.0, size=n)
    late = first > 0       # a replacement pod begins inside its first interval
    age[late] = rng.uniform(0.0, interval, size=int(late.sum()))
    start = np.minimum(np.floor(rate * age), START_CAP)
    values = np.cumsum(inc, axis=0)     # whole numbers: exact
    # a restart: what was counted before it is gone, and so is the start;
    # the counter is at a part of that scrape's own increment
    before = values[restart_step - 1, restarts] + start[restarts]
    kept = np.floor(rng.random(len(restarts)) * inc[restart_step, restarts])
    fell = at >= restart_step[None, :]
    values[:, restarts] -= fell * (before + inc[restart_step, restarts]
                                   - kept)[None, :]
    values += start[None, :]
    values[~alive] = np.nan
    return values


def generate(seed: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    n_steps = steps(params)
    slots, nodes = params["pods"], params["nodes"]
    slot_ns = rng.permutation(np.repeat(
        np.arange(params["namespaces"]), namespace_pods(params)))
    n_rep = replaced_pods(params)
    rep_slots = rng.permutation(slots)[:n_rep]
    rep_step = rng.integers(2, n_steps - 1, size=n_rep)

    # series: the live slots' first pods, then the replacements
    pod_slot = np.concatenate([np.arange(slots), rep_slots])
    n = len(pod_slot)
    first = np.zeros(n, np.int64)          # first step with a sample
    end = np.full(n, n_steps, np.int64)    # one past the last
    end[rep_slots] = rep_step
    first[slots:] = rep_step
    pod_node = rng.integers(nodes, size=n)
    pod_uid = _hex(rng, n, 32)
    workload = rng.integers(max(slots // 8, 1), size=slots)  # a Deployment
    pod_names = [f"app-{workload[s]:05d}-{u[:9]}-{u[9:14]}"
                 for s, u in zip(pod_slot, pod_uid)]
    restarts = rng.permutation(n)[:restarted_series(params)]
    life = end[restarts] - first[restarts]
    restart_step = first[restarts] + 1 + (
        rng.random(len(restarts)) * (life - 1)).astype(np.int64)

    values = {table: _counters(rng, params, first, end, restarts,
                               restart_step) for table in TABLES}

    # labels: cAdvisor reports a pod's network on its sandbox
    sandbox = _hex(rng, n, 64)
    uid_of = [f"{u[:8]}-{u[8:12]}-{u[12:16]}-{u[16:20]}-{u[20:]}"
              for u in pod_uid]
    qos = ["burstable", "besteffort", "guaranteed"]
    pod_qos = rng.integers(3, size=n)
    ids = [f"/kubepods/{qos[pod_qos[i]]}/pod{uid_of[i]}/{sandbox[i]}"
           for i in range(n)]
    each = np.arange(n, dtype=np.int32)
    one = np.zeros(n, np.int32)
    node_of = pod_node.astype(np.int32)
    namespace = slot_ns[pod_slot]
    tags = {
        "id": (each, ids),
        "image": (one, ["registry.k8s.io/pause:3.9"]),
        "interface": (one, ["eth0"]),
        "name": (each, sandbox),
        "namespace": (namespace.astype(np.int32), namespace_names(params)),
        "pod": (each, pod_names),
        "instance": (node_of, [f"10.{j // 250}.{j % 250}.10:10250"
                               for j in range(nodes)]),
        "job": (one, ["cadvisor"]),
        "node": (node_of, [f"node-{j:04d}" for j in range(nodes)]),
    }
    return {"values": values, "tags": tags,
            "namespace": namespace.astype(np.int64),
            "restarts": restarts, "replaced": rep_slots}


def ddl(params: dict) -> list[str]:
    cols = ", ".join(f"{t} STRING" for t in TAGS)
    return [f"CREATE TABLE {table} ({cols}, ts TIMESTAMP(3) TIME INDEX, "
            f"greptime_value DOUBLE, PRIMARY KEY ({', '.join(TAGS)}))"
            for table in TABLES]


def count_sql(params: dict) -> str:
    """One statement over both tables (a sum of scalar subqueries runs on
    this engine too, but its int64 does not pass the HTTP reply's
    ``json.dumps``)."""
    counts = " UNION ALL ".join(f"SELECT count(*) AS n FROM {table}"
                                for table in TABLES)
    return f"SELECT sum(n) FROM ({counts})"


def arrow_bodies(data: dict, params: dict):
    """Yields (table, Arrow IPC stream bytes, rows): a body holds a block
    of series with every sample each of them has, in time order, and the
    two tables' bodies of one block follow one another; a series' absent
    samples are left out.  ``id``, ``name`` and ``pod`` have an entry a
    series, two thirds of a body's bytes if every body carried them all
    (126,000 series: 27 MB), so a body carries its own series' entries
    of those and the small dictionaries whole."""
    import pyarrow as pa

    tags = data["tags"]
    n_steps, n = data["values"][RECEIVE].shape
    step_ms = params["interval_s"] * 1000
    vocab = {name: pa.array(v, type=pa.string())
             for name, (_c, v) in tags.items()}
    own = PER_SERIES
    shared = sum(v.nbytes for name, v in vocab.items() if name not in own)
    a_series = n_steps * _ROW_BYTES + sum(vocab[name].nbytes
                                          for name in own) // n + 1
    per = max(1, (_BODY_BYTES - shared) // a_series)
    for s in range(0, n, per):
        for table in TABLES:
            block = data["values"][table][:, s:s + per]
            series, at = np.nonzero(~np.isnan(block.T))
            cols = {}
            for name, (codes, _v) in tags.items():
                if name in own:   # codes are the series' own numbers
                    cols[name] = pa.DictionaryArray.from_arrays(
                        pa.array(series.astype(np.int32)),
                        vocab[name].slice(s, per))
                else:
                    cols[name] = pa.DictionaryArray.from_arrays(
                        pa.array(codes[s:s + per][series]), vocab[name])
            cols["ts"] = pa.array(T0 + at.astype(np.int64) * step_ms)
            cols["greptime_value"] = pa.array(block[at, series])
            batch = pa.table(cols)
            sink = io.BytesIO()
            with pa.ipc.new_stream(sink, batch.schema) as w:
                w.write_table(batch)
            yield table, sink.getvalue(), len(at)
