"""Main-path programs compiled for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached (``/opt/skills/guides/on-chip-measurement``,
section 2).  These are the jitted programs the served TSBS path runs
(``chip_smoke.py``), at the shapes of that deployment: 4000 hosts
(4096 padded), 12 h at 10 s (a [10, 4096, 6144] resident grid, 18.87M
padded rows on the row path), so a later PR that makes one of them
uncompilable for the chip — or minutes slow to compile — fails here at
no chip time.  A compile that passes is not a chip run.

This is the only file that describes the TPU, and it does so inside a
fixture: the process that loads the TPU's library keeps it, so nothing
here touches the topology at import, ``skipif`` or ``parametrize`` time.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

SPAD, TPAD, FIELDS = 4096, 6144, 10   # resident grid [C, S, T]
NB = 18                               # hour buckets the padded grid spans
ROWS = 18_874_368                     # pad_rows(4000 * 4320)
HOSTS = 4000
FIELD_NAMES = tuple(f"f{i}" for i in range(FIELDS))
# node64.cpu_rate (benchmark/configs/prom-node-64.json): 4,096 counters x
# 12 h at 15 s in a sort layout of 12.58M padded rows; one mode matches
# 512 series of 64 instances, 1 h at 60 s over [5m] gathers 512 samples each
PROM_ROWS, PROM_SERIES, PROM_SEL, PROM_GROUPS, PROM_W = (
    12_582_912, 4096, 512, 64, 512)
# k8s100k.namespace_cpu (benchmark/configs/prom-k8s-100k.json): 105,000
# counters x 1 h at 30 s in a layout of the same 12.58M padded rows; the
# panel's matchers keep 63,000 series (65,536 padded) in 200 namespaces,
# 30 min at 30 s over [5m] gathers 128 samples each
K8S_SERIES, K8S_SEL, K8S_MATCHED, K8S_GROUPS, K8S_W = (
    105_000, 65_536, 63_000, 200, 128)
# k8snet120k.namespace_bandwidth (benchmark/configs/prom-k8s-net-120k.json):
# 126,000 byte counters a table x 1 h at 30 s in a WIDE layout of 14.68M
# padded rows (the values as two f32 words); every series matches (131,072
# padded) into 200 namespaces, the same 128 samples each
NET_ROWS, NET_SERIES, NET_SEL = 14_680_064, 126_000, 131_072


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bm_kernel():
    """double-groupby-all over the resident bucket-major partials."""
    from greptimedb_tpu.query.physical import Executor

    return Executor()._bm_kernel_fn(
        ("hostname",), ["hostname"], [SPAD], 12, 3_600_000, None,
        [(f"avg({f})", "mean", i) for i, f in enumerate(FIELD_NAMES)])


def _bm_args(sh_sums, sh_cnts, sh_tags, sh_scalar):
    return (_shape((FIELDS, SPAD, NB), jnp.float32, sh_sums),
            _shape((SPAD, NB), jnp.float32, sh_cnts),
            (_shape((SPAD,), jnp.int32, sh_tags),),
            _shape((), jnp.int32, sh_scalar),
            _shape((), jnp.int64, sh_scalar))


def _grid_kernel():
    """cpu-max-all-8's shape: max of every field by hour over the grid's
    sliced window, tag-only WHERE as a per-series mask."""
    from greptimedb_tpu.query.physical import Executor

    def arg(i):
        return lambda env: env[FIELD_NAMES[i]]

    specs = [(f"max({f})", "max", arg(i), True, i)
             for i, f in enumerate(FIELD_NAMES)]
    return Executor()._build_grid_kernel(
        FIELD_NAMES, "ts", ("hostname",), [], [], True,
        360, 8, 2880, 0, 0, 3_600_000,
        lambda env: env["hostname"] < 8, True, specs,
        1451606400000, 10_000, True)


def _grid_args(sh):
    return (_shape((FIELDS, SPAD, TPAD), jnp.float32, sh),
            _shape((SPAD, TPAD), jnp.bool_, sh),
            (_shape((SPAD,), jnp.int32, sh),),
            _shape((), jnp.int64, sh), _shape((), jnp.int64, sh),
            _shape((), jnp.int64, sh), _shape((), jnp.int32, sh))


def _window_params(step_ms, scrape_ms, run, sel, series, want_w, wide=False):
    """The counter program's shape class for ``num_steps`` 61 over [5m]
    on a layout scraped every ``scrape_ms`` whose longest run is ``run``."""
    from greptimedb_tpu.promql.engine import (WindowParams, search_bits,
                                              slab_width)

    w = slab_width(step_ms, 61, 300_000, scrape_ms, run)
    assert w == want_w
    return WindowParams(step_ms=step_ms, num_steps=61, range_ms=300_000,
                        num_sel=sel, total_series=series, kind="counter",
                        slab_w=w, run_bits=search_bits(run), wide=wide)


def _promql_params():
    # sum by (instance)(rate(node_cpu_seconds_total{mode=..}[5m])), 1 h at 60 s
    return _window_params(60_000, 15_000, 2880, PROM_SEL, PROM_SERIES, PROM_W)


def _layout_args(sh, series=PROM_SERIES, sel=PROM_SEL, rows=PROM_ROWS,
                 value_words=1):
    from greptimedb_tpu.promql.engine import SortLayout

    f32 = _shape((rows,), jnp.float32, sh)
    return (SortLayout(_shape((rows,), jnp.int32, sh),
                       _shape((rows,), jnp.uint32, sh), f32,
                       _shape((series + 1,), jnp.int32, sh),
                       f32 if value_words == 2 else None),
            _shape((sel,), jnp.int32, sh), _shape((), jnp.int64, sh))


def _promql_window():
    from greptimedb_tpu.promql.engine import _window_body

    return _window_body(_promql_params())


def _promql_fused():
    from greptimedb_tpu.compile.fused import _build_fused

    return _build_fused(_promql_params(), "rate", "sum", PROM_GROUPS,
                        PROM_SEL, 300)


def _k8s_fused():
    """sum by (namespace)(rate(container_cpu_usage_seconds_total{..}[5m])),
    30 min at 30 s: the one program of every request of the cell."""
    from greptimedb_tpu.compile.fused import _build_fused

    p = _window_params(30_000, 30_000, 120, K8S_SEL, K8S_SERIES, K8S_W)
    return _build_fused(p, "rate", "sum", K8S_GROUPS, K8S_MATCHED, 300)


def _k8s_net_fused():
    """sum by (namespace)(rate(container_network_receive_bytes_total{..}[5m]))
    over the wide layout: ``_k8s_fused``'s program but for the value's
    width."""
    from greptimedb_tpu.compile.fused import _build_fused

    p = _window_params(30_000, 30_000, 120, NET_SEL, NET_SERIES, K8S_W,
                       wide=True)
    return _build_fused(p, "rate", "sum", K8S_GROUPS, NET_SERIES, 300)


def _segment(form, op, rows, sh):
    from greptimedb_tpu.ops import segment

    fn = {"scatter": segment.segment_reduce,
          "sorted": segment.sorted_segment_reduce}[form]
    return ((lambda v, i: fn(v, i, 48_000, op)),
            (_shape((rows,), jnp.float32, sh), _shape((rows,), jnp.int32, sh)))


CASES = {
    "grid-bucket-major": lambda sh: (_bm_kernel(), _bm_args(sh, sh, sh, sh)),
    "grid-window-max": lambda sh: (_grid_kernel(), _grid_args(sh)),
    "promql-window": lambda sh: (_promql_window(), _layout_args(sh)),
    "promql-fused": lambda sh: (
        _promql_fused(),
        _layout_args(sh) + (_shape((PROM_SEL,), jnp.int32, sh),)),
    "promql-fused-k8s-65536": lambda sh: (
        _k8s_fused(),
        _layout_args(sh, K8S_SERIES, K8S_SEL)
        + (_shape((K8S_MATCHED,), jnp.int32, sh),)),
    "promql-fused-k8snet-131072": lambda sh: (
        _k8s_net_fused(),
        _layout_args(sh, NET_SERIES, NET_SEL, NET_ROWS, value_words=2)
        + (_shape((NET_SERIES,), jnp.int32, sh),)),
    # the form `auto` takes on every backend, at table size
    "segment-scatter-mean": lambda sh: _segment("scatter", "mean", ROWS, sh),
    # the form only `force` reaches: its scan is minutes slow past this
    "segment-sorted-max-64k": lambda sh: _segment("sorted", "max", 1 << 16,
                                                  sh),
}


# fused PromQL case -> (padded series S, steps T, the folded slab's columns
# max(W, 128)): what every [S, T, .] pass of the program may read
SWEPT = {"promql-fused": (PROM_SEL, 61, PROM_W),
         "promql-fused-k8s-65536": (K8S_SEL, 61, K8S_W),
         "promql-fused-k8snet-131072": (NET_SEL, 61, K8S_W)}


def _operands_of_step_passes(text: str, s: int, t: int) -> dict[str, int]:
    """{instruction: its widest [s, X] or [X, s] operand} over the fusions
    of the compiled program whose result holds an [s, t] or [t, s] array:
    the compare-select-reduce passes over the swept cube (and the epilogue
    over [s, t])."""
    shape_of, passes = {}, {}
    for line in text.splitlines():
        name, eq, rhs = line.strip().removeprefix("ROOT ").partition(" = ")
        op = re.search(r" ([a-z][a-z0-9-]*)\(", rhs)
        if not eq or not name.startswith("%") or op is None:
            continue
        shape_of[name] = rhs[:op.start()]
        if op.group(1) == "fusion" and (f"[{s},{t}]" in shape_of[name]
                                        or f"[{t},{s}]" in shape_of[name]):
            passes[name] = re.findall(
                r"%[\w.-]+", rhs[op.end():].split("), kind=")[0])
    widths = {}
    for name, operands in passes.items():
        cols = [int(x) for o in operands for pair in re.findall(
            rf"\[{s},(\d+)\]|\[(\d+),{s}\]", shape_of.get(o, ""))
            for x in pair if x]
        widths[name] = max(cols, default=0)
    return widths


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_one_v5e(one_chip, case):
    fn, args = CASES[case](one_chip)
    t0 = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    seconds = time.time() - t0
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < 12 << 30, f"{case}: {total} bytes on a 16 GB chip"
    assert seconds < 60, f"{case}: {seconds:.0f} s to compile"
    if case in SWEPT:
        # the sentinel chunk is folded away before the sweeps: no pass
        # over [S, T, .] reads the gathered W + 128 columns, and none is
        # a loop over series
        s, t, width = SWEPT[case]
        text = compiled.as_text()
        assert " while(" not in text
        passes = _operands_of_step_passes(text, s, t)
        assert max(passes.values()) == width, passes
        # one traversal a window edge: two fusions read the slab's columns
        assert list(passes.values()).count(width) == 2, passes


def test_bucket_major_shards_over_the_mesh(topo, one_chip):
    """The four-chip program: partials split on the series axis (the
    placement parallel/dist.py bucket_major_shardings gives them), the
    series→group merge as a collective, a quarter of the bytes each."""
    from greptimedb_tpu.parallel.dist import bucket_major_shardings

    mesh = Mesh(np.array(topo.devices), ("shard",))
    sh = bucket_major_shardings(mesh, SPAD)
    rep = NamedSharding(mesh, P())
    args = _bm_args(sh["sums"], sh["cnts"],
                    NamedSharding(mesh, P("shard")), rep)
    compiled = jax.jit(_bm_kernel()).lower(*args).compile()
    text = compiled.as_text()
    assert any(c in text for c in ("all-reduce", "all-gather",
                                   "reduce-scatter", "collective-permute"))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = jax.jit(_bm_kernel()).lower(*_bm_args(*[one_chip] * 4)) \
        .compile().memory_analysis().argument_size_in_bytes
    assert per_device < 0.3 * whole
