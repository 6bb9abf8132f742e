"""TSBS devops ``cpu-only``: one table ``cpu``, TSBS's ten host tags
(``hostname`` and nine drawn for each host from TSBS's own choices), ten
``usage_*`` DOUBLE fields, one row a host every ``interval_s`` seconds.
The primary key is the ten tags, as upstream's table has it when TSBS's
loader creates it from line protocol.

numpy and pyarrow only: nothing here knows the program under test.
Copied from ``chip_smoke.py`` (PR 22), less its line-protocol tail: all
history goes in by Arrow IPC.

``params``: ``hosts``, ``hours``, ``interval_s``.
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TABLE = "cpu"
FIELDS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
]
# TSBS devops host tags (pkg/data/usecases/common/devops/host.go): a
# region and one of its datacenters, a rack of 100, and six more choices
REGIONS = {
    "us-east-1": ["us-east-1a", "us-east-1b", "us-east-1c", "us-east-1e"],
    "us-west-1": ["us-west-1a", "us-west-1b"],
    "us-west-2": ["us-west-2a", "us-west-2b", "us-west-2c"],
    "eu-west-1": ["eu-west-1a", "eu-west-1b", "eu-west-1c"],
    "eu-central-1": ["eu-central-1a", "eu-central-1b"],
    "ap-southeast-1": ["ap-southeast-1a", "ap-southeast-1b"],
    "ap-southeast-2": ["ap-southeast-2a", "ap-southeast-2b"],
    "ap-northeast-1": ["ap-northeast-1a", "ap-northeast-1c"],
    "sa-east-1": ["sa-east-1a", "sa-east-1b", "sa-east-1c"],
}
CHOICES = {
    "rack": [str(i) for i in range(100)],
    "os": ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"],
    "arch": ["x64", "x86"],
    "team": ["SF", "NYC", "LON", "CHI"],
    "service": [str(i) for i in range(20)],
    "service_version": ["0", "1"],
    "service_environment": ["production", "staging", "test"],
}
TAGS = ["hostname", "region", "datacenter", *CHOICES]
T0 = 1451606400000  # 2016-01-01, the TSBS epoch (ms)
_BODY_BYTES = 32 << 20  # the server refuses request bodies over 64 MiB
_ROW_BYTES = 4 * len(TAGS) + 8 + 8 * len(FIELDS)
_BLOCK = 250   # hosts a generator block
_THREADS = 8


def steps_per_hour(params: dict) -> int:
    return 3600 // params["interval_s"]


def steps(params: dict) -> int:
    return params["hours"] * steps_per_hour(params)


def rows(params: dict) -> int:
    return steps(params) * params["hosts"]


def generate(seed: int, params: dict) -> dict:
    """``values`` [steps, hosts, 10] float64 in [0, 100]: a clipped
    random walk per (host, field), as TSBS.  Hosts are made in blocks of
    ``_BLOCK``, each from its own child of the seed, on a few threads
    (numpy releases the GIL), so the same seed gives the same data
    whatever the thread count."""
    n_steps, hosts = steps(params), params["hosts"]
    out = np.empty((n_steps, hosts, len(FIELDS)))
    starts = list(range(0, hosts, _BLOCK))
    children = np.random.SeedSequence(seed).spawn(len(starts))

    def block(h0: int, child) -> None:
        rng = np.random.default_rng(child)
        n_h = min(_BLOCK, hosts - h0)
        state = rng.uniform(0, 100, size=(n_h, len(FIELDS)))
        chunk = 360
        for s in range(0, n_steps, chunk):
            n = min(chunk, n_steps - s)
            walk = rng.normal(0, 1, size=(n, n_h, len(FIELDS)))
            np.cumsum(walk, axis=0, out=walk)
            walk += state[None]
            np.clip(walk, 0, 100, out=out[s:s + n, h0:h0 + n_h])
            state = out[s + n - 1, h0:h0 + n_h]

    with ThreadPoolExecutor(max_workers=_THREADS) as pool:
        for f in [pool.submit(block, h0, c) for h0, c in zip(starts, children)]:
            f.result()
    return {"values": out, "tags": host_tags(seed, hosts)}


def host_tags(seed: int, hosts: int) -> dict:
    """{tag: (int32 code of each host, vocabulary)}, drawn for each host
    as TSBS's ``NewHost`` does."""
    rng = np.random.default_rng([seed, len(TAGS)])
    regions = list(REGIONS)
    region = rng.integers(len(regions), size=hosts)
    first = np.cumsum([0] + [len(REGIONS[r]) for r in regions])
    sizes = np.diff(first)[region]
    tags = {
        "hostname": (np.arange(hosts), [f"host_{i}" for i in range(hosts)]),
        "region": (region, regions),
        "datacenter": (first[region] + rng.integers(sizes),
                       [d for r in regions for d in REGIONS[r]]),
    }
    for name, vocab in CHOICES.items():
        tags[name] = (rng.integers(len(vocab), size=hosts), vocab)
    return {k: (c.astype(np.int32), v) for k, (c, v) in tags.items()}


def ddl(params: dict) -> list[str]:
    cols = ", ".join(f"{m} DOUBLE" for m in FIELDS)
    tags = ", ".join(f"{t} STRING" for t in TAGS)
    return [f"CREATE TABLE {TABLE} ({tags}, ts TIMESTAMP(3) TIME INDEX, "
            f"{cols}, PRIMARY KEY ({', '.join(TAGS)}))"]


def count_sql(params: dict) -> str:
    return f"SELECT count(*) FROM {TABLE}"


def arrow_bodies(data: dict, params: dict):
    """Yields (table, Arrow IPC stream bytes, rows) in time order."""
    import pyarrow as pa

    values = data["values"]
    n_steps, hosts, _ = values.shape
    step_ms = params["interval_s"] * 1000
    vocab = {t: pa.array(v) for t, (_c, v) in data["tags"].items()}
    per = max(1, _BODY_BYTES // (_ROW_BYTES * hosts))
    for s in range(0, n_steps, per):
        n = min(per, n_steps - s)
        cols = {t: pa.DictionaryArray.from_arrays(
            pa.array(np.tile(data["tags"][t][0], n)), vocab[t])
            for t in TAGS}
        cols["ts"] = pa.array(np.repeat(
            T0 + np.arange(s, s + n, dtype=np.int64) * step_ms, hosts))
        for j, m in enumerate(FIELDS):
            cols[m] = pa.array(values[s:s + n, :, j].reshape(-1))
        table = pa.table(cols)
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        yield TABLE, sink.getvalue(), n * hosts
