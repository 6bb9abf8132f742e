"""Programs built or loaded inside the window: delta of
greptime_compile_xla_builds_total (all engines) plus JAX's compilation
cache hits and misses (every first jit of a shape is one or the other).
Expected 0: step 6 warms every shape."""

BUILDS = "greptime_compile_xla_builds_total"


def read(ctx):
    def builds(m):
        return sum(v for k, v in m.items() if k.startswith(BUILDS))

    jit = sum(ctx["xla_after"][k] - ctx["xla_before"][k]
              for k in ("hits", "misses"))
    return builds(ctx["metrics_after"]) - builds(ctx["metrics_before"]) + jit
