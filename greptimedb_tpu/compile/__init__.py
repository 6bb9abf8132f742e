"""Query-compiler subsystem: whole-plan fused XLA programs, a persistent
compilation cache, and AOT warmup.

The three legs of ROADMAP open item 5 (the compile-latency attack):

- **Whole-plan fusion** (``fused.py``): every physical plan is classified
  into a *shape class* — a canonicalized fingerprint over operator tree,
  window geometry, dtypes and resident-layout kind (``shape.py``) — and
  each class executes as ONE jitted XLA program.  The SQL grid paths
  (bucket-major aligned, dynamic-slice) have been single fused programs
  since PR 1/PR 3; this subsystem takes ownership of their
  classification and adds the missing chain: the PromQL
  selection→window→group pipeline, whose window kernel, rate
  extrapolation and cross-series aggregation previously ran as one jit
  plus a tail of eager dispatches with host glue, now lowers to a single
  program (Data Path Fusion, arXiv 2605.10511: eliminating intermediate
  materialization between query stages is the next multiplier after
  caching).  A chain outside the fused surface takes the multi-kernel
  path (``try_fused_aggregation`` returns None).

- **Persistent compilation cache** (``store.py`` + ``service.py``): AOT
  artifacts — ``jax.jit(...).lower(...).compile()`` executables
  serialized via ``jax.experimental.serialize_executable`` — persist on
  disk in a CRC-enveloped store (the PR-9 GTM1 discipline) keyed by
  (shape-class fingerprint, jaxlib version, backend, device topology,
  machine), so a restarted node recompiles nothing it has seen before.
  ``GREPTIME_COMPILE_CACHE=on`` additionally places jax's own
  compilation cache (``xla_cache.py``) so non-routed jits persist too.

- **AOT warmup** (``warmup.py`` + ``journal.py``): a per-instance usage
  journal records each shape class with enough replay context (the
  plancodec-encoded plan / TQL parameters) to rebuild its kernels in a
  fresh process.  Region-open warmup precompiles the top-K classes, and
  a scheduler-idle hook drains the rest, so a restarted node serves fast
  warm-class queries immediately (TCR, arXiv 2203.01877: plans lower
  cleanly to reusable accelerator programs).
"""

from __future__ import annotations

__all__ = ["named_jit", "PlanCompiler"]


def named_jit(name: str, **jit_kwargs):
    """``jax.jit`` under a stable name: ``@named_jit("sql_grid")``.  The
    name is a program FAMILY, never a shape or a literal; the compiled
    module and the profiler's ``XLA Modules`` line read ``jit_<name>``,
    so per-program device time can be followed across refactors."""
    import jax

    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, **jit_kwargs)

    return wrap


def __getattr__(name):  # lazy: keep `import greptimedb_tpu.compile` light
    if name == "PlanCompiler":
        from greptimedb_tpu.compile.service import PlanCompiler

        return PlanCompiler
    raise AttributeError(name)
