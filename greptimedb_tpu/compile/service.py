"""PlanCompiler: the one gate every kernel build goes through.

The executor/promql kernel caches stay the in-memory fast path (a dict
hit costs nothing); on a miss they call ``get_or_build`` here instead of
invoking the builder directly.  The compiler then:

1. canonicalizes the runtime cache key into a shape-class fingerprint
   (shape.py) and notes the class in the usage journal (journal.py) with
   lazily-captured replay context,
2. consults the persistent AOT store (store.py): a hit deserializes the
   executable — ZERO XLA compilation — and returns it wrapped with a
   rebuild fallback,
3. otherwise returns a kernel that lowers + compiles on first call and
   persists the executable for every later process.

An unconfigured store, an anonymous class, a serialization failure, or
an artifact that refuses its arguments (signature drift) all degrade to
exactly the pre-existing ``jax.jit`` path; an error from the compiler
or the device does not — it propagates.
"""

from __future__ import annotations

import contextlib
import os
import threading

from greptimedb_tpu.compile.shape import canon_key, class_id
from greptimedb_tpu.utils.telemetry import REGISTRY

M_COMPILE_EVENTS = REGISTRY.counter(
    "greptime_compile_cache_events_total",
    "Persistent compile-cache events (aot_hit/build/persist/"
    "persist_error/corrupt/stale_evict/fallback)",
    labels=("event",),
)
M_XLA_BUILDS = REGISTRY.counter(
    "greptime_compile_xla_builds_total",
    "Kernel classes that required a real XLA compile (not served AOT)",
    labels=("engine",),
)
M_FUSED_DISPATCH = REGISTRY.counter(
    "greptime_compile_fused_dispatch_total",
    "Whole-plan fused program dispatches",
    labels=("engine",),
)
M_WARMUP = REGISTRY.counter(
    "greptime_compile_warmup_total",
    "AOT warmup replays by outcome",
    labels=("outcome",),
)
M_CACHE_DISK = REGISTRY.gauge(
    "greptime_compile_cache_disk_bytes",
    "Bytes of serialized AOT artifacts on disk",
)


# What a ``Compiled`` raises when handed arguments that differ from the
# ones it was lowered for (pytree structure, shape, dtype, sharding) —
# signature drift, the one failure the kernels below answer by going
# back to jit.  Errors from the compiler or the device (XlaRuntimeError
# / JaxRuntimeError) are not drift and propagate.
_DRIFT = (TypeError, ValueError)


class _PersistingKernel:
    """Fresh build: lower+compile on first call (inside the caller's
    timed compile phase, so device-phase attribution stays honest), then
    persist the executable.  Falls back to the plain jitted function when
    the arguments drift from the signature it was compiled for."""

    aot = False

    def __init__(self, jitted, persist_cb):
        self._jitted = jitted
        self._persist_cb = persist_cb
        self._compiled = None

    def __call__(self, *args):
        # deliberately lock-free: call sites are serialized by the db
        # executor lock; a racing duplicate first-call would just compile
        # twice and persist last-writer-wins (atomic file replace)
        if self._compiled is None:
            # a program the compiler or the device refuses raises here,
            # as it would under plain jit
            self._compiled = self._jitted.lower(*args).compile()
            self._persist_cb(self._compiled)
        if self._compiled is self._jitted:
            return self._jitted(*args)
        try:
            return self._compiled(*args)
        except _DRIFT:
            # a Compiled is pytree/shape-STRICT where jit would retrace
            # (signature drift the class key failed to capture): restore
            # jit semantics permanently for this class and re-execute
            M_COMPILE_EVENTS.labels("fallback").inc()
            self._compiled = self._jitted
            return self._jitted(*args)


class _AotKernel:
    """Deserialized executable with a rebuild fallback: if the artifact
    refuses its arguments (signature drift the class key failed to
    capture), rebuild via the original builder once and keep serving."""

    aot = True

    def __init__(self, fn, rebuild, engine: str):
        self._fn = fn
        self._rebuild = rebuild
        self._engine = engine

    def __call__(self, *args):
        try:
            return self._fn(*args)
        except _DRIFT:  # one rebuild, then the real error
            if self._rebuild is None:
                raise
            M_COMPILE_EVENTS.labels("fallback").inc()
            M_XLA_BUILDS.labels(self._engine).inc()
            self._fn, self._rebuild = self._rebuild(), None
            self.aot = False
            return self._fn(*args)


class PlanCompiler:
    """Per-executor compile service (see module docstring).  Created
    unconfigured — memory-only classification, zero disk IO — and armed
    by the server via ``configure`` when a persistent data home exists."""

    def __init__(self):
        self._lock = threading.Lock()
        self.store = None
        self.journal = None
        self._replay = threading.local()
        self._quiet = threading.local()  # warmup replays don't self-count
        # instance mirrors of the registry counters (memory.py
        # discipline: /status and benches read without a scrape)
        self.mem_builds = 0
        self.aot_hits = 0
        self.persists = 0

    # ------------------------------------------------------------------
    def configure(self, root: str, quota_bytes: int | None = None) -> None:
        from greptimedb_tpu.compile.journal import UsageJournal
        from greptimedb_tpu.compile.store import ArtifactStore

        with self._lock:
            self.store = ArtifactStore(root, quota_bytes)
            self.journal = UsageJournal(os.path.join(root, "usage.json"))
        store = self.store
        import weakref

        ref = weakref.ref(store)
        M_CACHE_DISK.set_function(
            lambda: float(s.bytes()) if (s := ref()) is not None else 0.0)

    def close(self) -> None:
        j = self.journal
        if j is not None:
            j.save()

    # ---- replay context ----------------------------------------------
    def set_replay(self, fn) -> None:
        """Arm the calling thread's replay capture: ``fn()`` is invoked
        lazily (at most once, on a journal-new class) to produce the
        replay dict for whatever statement is currently executing."""
        self._replay.fn = fn

    def clear_replay(self) -> None:
        self._replay.fn = None

    def _replay_fn(self):
        return getattr(self._replay, "fn", None)

    @contextlib.contextmanager
    def warming(self):
        """Suppress journal counting on the calling thread: warmup's own
        replays must not re-increment the classes they warm, or top-K
        ranking self-perpetuates regardless of real use."""
        self._quiet.on = True
        try:
            yield
        finally:
            self._quiet.on = False

    # ---- the gate -----------------------------------------------------
    def get_or_build(self, engine: str, key, builder, *,
                     persist: bool = True, metrics: dict | None = None):
        """One kernel for ``key``: AOT-loaded when the persistent store
        has this class for this environment, else freshly built (and
        persisted on first call when eligible).  ``builder`` must return
        the jitted function exactly as the call site used to build it."""
        canon = canon_key(engine, key)
        cid = class_id(canon) if canon is not None else None
        store = self.store
        journal = self.journal
        if (cid is not None and journal is not None
                and not getattr(self._quiet, "on", False)):
            journal.note(cid, engine, canon, self._replay_fn())
        if cid is not None and persist and store is not None:
            fn = store.load(cid, canon)
            if fn is not None:
                with self._lock:
                    self.aot_hits += 1
                M_COMPILE_EVENTS.labels("aot_hit").inc()
                if metrics is not None:
                    metrics["compile_cache"] = "aot"
                return _AotKernel(fn, builder, engine)
        with self._lock:
            self.mem_builds += 1
        M_COMPILE_EVENTS.labels("build").inc()
        M_XLA_BUILDS.labels(engine).inc()
        if metrics is not None:
            metrics["compile_cache"] = "build"
        jitted = builder()
        if cid is None or not persist or store is None:
            return jitted

        def persist_cb(compiled, cid=cid, canon=canon, engine=engine):
            if store.save(cid, canon, engine, compiled):
                with self._lock:
                    self.persists += 1
                M_COMPILE_EVENTS.labels("persist").inc()
            else:
                M_COMPILE_EVENTS.labels("persist_error").inc()

        return _PersistingKernel(jitted, persist_cb)

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        out = {"mem_builds": self.mem_builds, "aot_hits": self.aot_hits,
               "persists": self.persists}
        if self.store is not None:
            out.update({
                "disk_bytes": self.store.bytes(),
                "loads": self.store.loads,
                "saves": self.store.saves,
                "corrupt": self.store.corrupt,
                "stale": self.store.stale,
            })
        if self.journal is not None:
            out["journal_classes"] = len(self.journal)
        return out


_DEFAULT: PlanCompiler | None = None
_DEFAULT_LOCK = threading.Lock()


def default_compiler() -> PlanCompiler:
    """Process-wide unconfigured compiler for callers without a db-owned
    one (embedded evaluators): memory-only classification."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = PlanCompiler()
        return _DEFAULT
