"""Test harness: force an 8-device virtual CPU platform before jax imports.

Mirrors the reference's in-process mock-cluster strategy
(tests-integration/src/cluster.rs — N in-process datanodes, no containers):
we fake an 8-chip TPU slice with XLA's host-platform device count so all
mesh/sharding/collective paths run in CI without TPU hardware.
"""

import contextlib
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

# Tests run on the CPU: the env var covers child processes, jax.config
# covers this one even if something imported jax before this file.
import jax  # noqa: E402

if not os.environ.get("GREPTIME_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# The fast roads a parity test compares with the road beside them.  The
# program picks each from one predicate over its input, so a test reaches
# the other road by making every input ineligible there.
_FAST_ROADS = {
    # dense time-grid executor -> row-major DeviceTable path
    "grid": ("greptimedb_tpu.query.physical.grid_plan_candidate",
             lambda plan: False),
    # resident bucket-major layout -> dynamic-slice grid kernel
    "layout": ("greptimedb_tpu.query.physical.aligned_layout_eligible",
               lambda *a: False),
    # whole-plan fused PromQL chain -> multi-kernel path
    "fusion": ("greptimedb_tpu.compile.fused.try_fused_aggregation",
               lambda ev, e: None),
    # fused histogram_quantile -> the evaluated argument folded apart
    "fused_histogram": ("greptimedb_tpu.compile.fused.try_fused_histogram",
                        lambda ev, e, q: None),
    # packed-key radix merge of scan parts -> global lexsort
    "packed_merge": ("greptimedb_tpu.storage.scan._pack_keys",
                     lambda *a: None),
}


@pytest.fixture
def ineligible(monkeypatch):
    """``with ineligible("grid"): ...`` — inside the block no input is
    eligible for the named fast road, so the program takes the road it
    falls back to (the reference of the parity tests)."""

    @contextlib.contextmanager
    def road(name):
        target, never = _FAST_ROADS[name]
        with monkeypatch.context() as mp:
            mp.setattr(target, never)
            yield

    return road


_BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                      "benchmark")


@pytest.fixture(scope="session")
def bench_run():
    """benchmark/run.py, imported the way its own tests import it."""
    import importlib.util
    import sys

    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_tests", os.path.join(_BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


class ServedCell:
    """One HTTP server holding one seed's data of one benchmark cell at
    its rehearsal size, and the cell's traffic."""

    def __init__(self, run, workload: str, seed: int):
        from greptimedb_tpu.servers import HttpServer
        from greptimedb_tpu.standalone import GreptimeDB

        spec = run.load_json(run.ROOT, "BENCHMARK.json")
        _entry, config, self.mix = run.load_cell(spec, workload)
        self.family = run.load_module("queries", self.mix["family"])
        self.cell = run.new_cell(config, rehearse=True, seed=seed)
        self.run = run
        self.db = GreptimeDB()
        self.srv = HttpServer(self.db, port=0)
        self.srv.start()
        self.client = run.Client(self.srv.port)
        ds, p = self.cell.ds, self.cell.params
        for stmt in ds.ddl(p):
            self.client.sql(stmt)
        acked = sum(self.client.arrow_write(table, body)
                    for table, body, _n in ds.arrow_bodies(self.cell.data, p))
        assert acked == ds.rows(p)
        assert self.client.sql(ds.count_sql(p))[0][0] == acked
        self.traffic = run.Traffic(self.family, self.cell, self.mix, seed,
                                   stream=1)

    def judge(self, req, answer=None):
        """The harness's verdict on the served reply to ``req``, or on
        ``answer`` put in its place (the control)."""
        if answer is not None:
            return self.run.judge(self.family, self.cell, req, None, None,
                                  answer=answer)
        rec = self.run.exchange(self.client, req)
        return self.run.judge(self.family, self.cell, req, rec["status"],
                              rec["reply"])

    def close(self):
        self.client.close()
        self.srv.stop()
        self.db.close()


@pytest.fixture
def served(bench_run, request, monkeypatch):
    """``@pytest.mark.parametrize("served", [(workload, seed)],
    indirect=True)``: that cell, loaded and served by the default
    ``GreptimeDB()``, which on the harness's eight virtual devices forms
    the mesh (row-sharded layout, the fused program placed by
    ``promql_row_shardings``).  ``(workload, seed, "one-device")`` serves
    it with ``GREPTIME_MESH=off``: for further seeds of a case the mesh
    already runs, since there a window program opens with an all-gather
    that can starve under xdist (PERF.md 7.7)."""
    workload, seed, *placement = request.param
    assert placement in ([], ["one-device"]), placement
    if placement:
        monkeypatch.setenv("GREPTIME_MESH", "off")
    s = ServedCell(bench_run, workload, seed)
    assert (s.db.mesh is None) == bool(placement)
    yield s
    s.close()


@pytest.fixture
def tmp_data_dir(tmp_path):
    d = tmp_path / "data"
    d.mkdir()
    return str(d)


def pytest_configure(config):
    # GREPTIME_LOCK_WITNESS=on: the concurrency/chaos tiers run with the
    # runtime lock-order witness installed for the whole session — every
    # lock created by a fixture is witnessed and real acquisition chains
    # are checked for ABBA inversions.  Off (default): the module is
    # never imported, threading.Lock stays the stock factory (the
    # zero-overhead pin in tests/test_analysis.py).
    import os as _os

    if _os.environ.get("GREPTIME_LOCK_WITNESS", "").lower() in (
            "on", "1", "true"):
        from greptimedb_tpu.analysis.witness import install_from_env

        install_from_env()
    config.addinivalue_line("markers", "golden: golden-file SQL/TQL corpus")
    config.addinivalue_line(
        "markers", "golden_dist: distributed re-run of the golden corpus")
    config.addinivalue_line("markers", "fuzz: randomized DDL/insert/query fuzzing")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tier — node kills under live load with "
        "recovery invariants (fast deterministic cases run in tier-1)")
    config.addinivalue_line(
        "markers",
        "concurrency: serving-scheduler tier — multi-client admission/"
        "batching/priority invariants (fast deterministic cases run in "
        "tier-1, like the chaos tier)")
    config.addinivalue_line(
        "markers", "slow: long soak cases excluded from tier-1")
