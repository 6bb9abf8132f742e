"""chip_smoke.py rehearsed on the CPU, and the guards that keep a run
without the chip from passing for one."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, **env):
    # one real CPU device, as the rehearsal sees it: conftest's eight
    # virtual devices would form a mesh
    e = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def rehearsal():
    r = _run([SMOKE, "--rehearse", "--scale", "16", "--hours", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    return [json.loads(line) for line in r.stdout.splitlines()]


def test_rehearsal_is_never_a_result(rehearsal):
    assert rehearsal[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_rehearsal_phases_in_order(rehearsal):
    phases = [d["phase"] for d in rehearsal[:-1]]
    assert phases[:2] == ["device", "load"]
    assert phases[-3:] == ["check", "reopen", "xla_cache"]
    assert set(phases[2:-3]) == {"query"}
    load = rehearsal[1]
    assert load["hosts"] == 16 and load["fields"] == 10
    assert load["rows"] == 16 * 360 == load["arrow_rows"] + load["lp_rows"]
    assert load["lp_rows"] == 16 * 60


@pytest.mark.parametrize("query", [
    "double-groupby-all", "single-groupby-1-1-1", "cpu-max-all-8",
    "stddev-by-host", "read-back", "count-newest", "promql-rate-sum"])
def test_rehearsal_query_matches_numpy(rehearsal, query):
    got = [d for d in rehearsal if d.get("query") == query
           and d.get("phase") == "query"]
    assert len(got) == 1
    assert got[0]["correct"] is True
    assert len(got[0]["warm_s"]) == 2


def test_rehearsal_evidence(rehearsal):
    by = {d["query"]: d for d in rehearsal if d.get("phase") == "query"}
    assert by["double-groupby-all"]["dispatch"]["grid_bm"] == 1
    assert by["cpu-max-all-8"]["dispatch"]["grid"] == 1
    check = next(d for d in rehearsal if d.get("phase") == "check")
    assert check["row_path_segment_form"] in ("sorted", "scatter")
    assert check["compile_cache_events"]["fallback"] == 0
    assert check["compile_cache_events"]["persist_error"] == 0
    reopen = next(d for d in rehearsal if d.get("phase") == "reopen")
    assert reopen["correct"] is True
    assert reopen["aot_hits"] > 0
    assert reopen["compile_cache_events"]["fallback"] == 0


def test_without_rehearse_fails_in_device_phase():
    r = _run([SMOKE, "--scale", "16", "--hours", "1"])
    assert r.returncode != 0
    assert "device phase" in r.stderr
    assert r.stdout == ""


_CONFIGURE = ("import jax; {pre}"
              "from greptimedb_tpu.compile.xla_cache import "
              "configure_xla_cache as c; print(c()); "
              "print(jax.config.jax_compilation_cache_dir)")


def test_xla_cache_goes_where_the_environment_says(tmp_path):
    r = _run(["-c", _CONFIGURE.format(pre="")],
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.stdout.split() == [str(tmp_path)] * 2, r.stderr[-2000:]


def test_xla_cache_defaults_to_the_checkout_off_the_cpu():
    # no accelerator here: stand in for one where the helper asks
    r = _run(["-c", _CONFIGURE.format(
        pre="jax.default_backend = lambda: 'tpu'; ")])
    assert r.stdout.split() == [os.path.join(ROOT, ".jax_cache")] * 2, \
        r.stderr[-2000:]
    r = _run(["-c", _CONFIGURE.format(pre="")])
    assert r.stdout.split() == ["None", "None"], r.stderr[-2000:]


def test_database_raises_when_platform_cannot_initialise():
    code = ("import jax; jax.config.update('jax_platforms', 'no_such_chip');"
            "from greptimedb_tpu.standalone import GreptimeDB; GreptimeDB()")
    e = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=e,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no_such_chip" in r.stderr


def test_no_platform_fallback_left_in_the_package():
    pkg = os.path.join(ROOT, "greptimedb_tpu")
    hits = [os.path.join(d, f) for d, _dirs, files in os.walk(pkg)
            for f in files if f.endswith(".py")
            and 'jax_platforms", "cpu"' in open(os.path.join(d, f)).read()]
    assert hits == []
