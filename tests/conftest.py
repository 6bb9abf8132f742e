"""Test harness: force an 8-device virtual CPU platform before jax imports.

Mirrors the reference's in-process mock-cluster strategy
(tests-integration/src/cluster.rs — N in-process datanodes, no containers):
we fake an 8-chip TPU slice with XLA's host-platform device count so all
mesh/sharding/collective paths run in CI without TPU hardware.
"""

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
