"""Tracing: spans + OTLP/HTTP export.

Reference: common-telemetry's tracing layer exporting OTLP spans to a
collector (src/common/telemetry/src/tracing_*.rs, config
[logging].otlp_endpoint).  Spans record into a bounded in-process
buffer; the exporter encodes ExportTraceServiceRequest protobuf (the
same wire format servers/trace.py parses — a greptimedb-tpu instance
can export its own spans to another instance, or to any OTLP
collector) and POSTs it over HTTP.

``Tracer.stage`` is the ONE stage boundary of the query and ingest
paths.  One pair of ``perf_counter`` reads feeds three sinks: the
always-on histogram ``greptime_query_stage_seconds{stage}``, a
``jax.profiler.TraceAnnotation`` (recorded only while a profiler session
is open, on the device trace's clock) and, with the tracer enabled, the
OTLP span.  A disabled tracer never allocates a span record.  CPU time
is read by thread at scrape time (utils/telemetry.py
``greptime_thread_cpu_seconds``), not by stage: a thread's CPU clock is a system
call, which cost 6 µs and more a read on a TPU v5e host where the wall
clock costs 0.08 µs.
"""

from __future__ import annotations

import contextlib
import gc
import os
import re
import struct
import threading
import time
import urllib.request

from greptimedb_tpu.utils.proto import (  # the ONE wire encoder
    pb_fixed64 as _fixed64_field, pb_len as _field, pb_varint as _varint,
    pb_vint_field as _vint_field,
)
from greptimedb_tpu.utils.telemetry import REGISTRY

# Always on, as upstream keeps greptime_query_stage_elapsed: what a
# request's time went to must be readable from /metrics of a server that
# was started with no tracing option at all.
M_STAGE = REGISTRY.histogram(
    "greptime_query_stage_seconds",
    "Wall time of one stage of the query and ingest paths",
    labels=("stage",),
    buckets=(1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01, 0.025, 0.05,
             0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)
M_GC_PAUSE = REGISTRY.histogram(
    "greptime_gc_pause_seconds",
    "Pause of one full (generation 2) Python garbage collection",
    labels=("generation",),
)
# Says the slab engaged: S_padded x W of every window program dispatched
# (promql/engine.py ``slab_width``), host arithmetic on static shapes.
M_WINDOW_ROWS = REGISTRY.counter(
    "greptime_promql_window_rows_total",
    "Slab cells (padded matched series x slab width) gathered by "
    "dispatched PromQL window programs",
)
# Says the fold engaged: S_padded x the columns every [S, T, .] pass runs
# over (promql/engine.py ``swept_columns``); equal to the counter above
# where no pass sweeps a sentinel column.
M_SWEPT_COLUMNS = REGISTRY.counter(
    "greptime_promql_swept_columns_total",
    "Slab columns (padded matched series x swept width) that each pass of "
    "dispatched PromQL window programs runs over",
)
M_SELECTED_SERIES = REGISTRY.counter(
    "greptime_promql_selected_series_total",
    "Series the label matchers kept, of dispatched PromQL window programs",
)
M_PADDED_SERIES = REGISTRY.counter(
    "greptime_promql_padded_series_total",
    "Series slots (the selection padded to the program's static size) of "
    "dispatched PromQL window programs",
)
# Says the values' low word engaged: the share of the slab cells above
# whose program ran on a WIDE layout (promql/engine.py ``WindowParams.wide``:
# a DOUBLE column past 2^24 read as two f32 words).
M_WIDE_ROWS = REGISTRY.counter(
    "greptime_promql_wide_rows_total",
    "Slab cells (padded matched series x slab width) of dispatched PromQL "
    "window programs that read a two-word value column",
)
# Says the grouped picks engaged: traversals of [series, steps, swept
# width] in the source of the dispatched programs (promql/engine.py
# ``sweep_passes``): one a window edge where the slab is swept, 0 where it
# is searched; a pick of its own for every array read would count 8 for
# ``rate``.
M_SWEEP_PASSES = REGISTRY.counter(
    "greptime_promql_sweep_passes_total",
    "Compare-select-reduce traversals of the swept slab that dispatched "
    "PromQL window programs emit",
)
# Says the chunk count engaged: scalar rounds of the first-sample search
# in the source of the dispatched programs (promql/engine.py
# ``search_rounds``): run_bits less log2(gcd(table rows, 128)), 0 where
# every run is under a chunk; the whole search would count run_bits.
M_SEARCH_ROUNDS = REGISTRY.counter(
    "greptime_promql_search_rounds_total",
    "Scalar gather rounds of the first-sample search that dispatched "
    "PromQL window programs emit",
)


def count_window_dispatch(selected: int, padded: int, slab_w: int,
                          swept: int, programs: int = 1,
                          wide: bool = False, passes: int = 0,
                          rounds: int = 0) -> None:
    """The counters of one PromQL window dispatch: host integers off
    static shapes and the selection's length.  ``passes`` is the sum
    over the dispatched programs, not one program's; ``rounds`` is one
    program's."""
    M_SWEEP_PASSES.inc(passes)
    M_SEARCH_ROUNDS.inc(programs * rounds)
    M_WINDOW_ROWS.inc(programs * padded * slab_w)
    # by 0 off a narrow layout: the counter is there to be read as 0
    M_WIDE_ROWS.inc(programs * padded * slab_w if wide else 0)
    M_SWEPT_COLUMNS.inc(programs * padded * swept)
    M_SELECTED_SERIES.inc(programs * selected)
    M_PADDED_SERIES.inc(programs * padded)


_TRACE_ANNOTATION = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation``: an atomic load while no
    profiler session is open, a host-plane event of the same
    ``.xplane.pb`` as the device's ``XLA Ops`` while one is.  jax is
    imported on the first stage, never with this module."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


class _Stage:
    """One open stage (see ``Tracer.stage``).  ``seconds`` holds its
    wall time once it has closed."""

    __slots__ = ("_tracer", "_name", "_attrs", "_detached", "_ctx",
                 "_span", "_ann", "_t0", "seconds")

    def __init__(self, tracer, name, attrs, detached=False, ctx=None):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._detached = detached
        self._ctx = ctx
        self._span = None
        self.seconds = 0.0

    def __enter__(self):
        tr = self._tracer
        if tr.enabled and not getattr(tr._tls, "suppress", False):
            self._span = tr._open_span(self._name, self._attrs,
                                       self._detached, self._ctx)
        self._ann = _annotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    @property
    def ctx(self) -> tuple[str, str] | None:
        """The (trace_id, parent_span_id) under which work this stage
        hands to another thread opens: its own span while the tracer
        records it, else the context it was opened under."""
        if self._span is not None:
            return self._span["trace_id"], self._span["span_id"]
        if self._detached:
            return self._ctx
        return getattr(self._tracer._tls, "current", None)

    def set(self, **attrs) -> None:
        """Span attributes known only once the stage has run."""
        if self._span is not None:
            self._span["attributes"].update(attrs)

    def __exit__(self, exc_type, exc, tb):
        self.seconds = dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        self._tracer._stage_child(self._name).observe(dt)
        if self._span is not None:
            self._tracer._close_span(
                self._span, self._span["start_ns"] + int(dt * 1e9),
                exc_type is not None)
        return False


# ---------------------------------------------------------------------------
# Trace-context propagation (W3C Trace Context + the reference's
# x-greptime-trace-id header, src/servers/src/http/header.rs).  Malformed
# values are IGNORED — a bad header falls back to a fresh trace, never an
# error (per the W3C spec's "restart the trace" rule).
# ---------------------------------------------------------------------------

_HEX = frozenset("0123456789abcdef")


def _is_hex(s: str) -> bool:
    return bool(s) and all(c in _HEX for c in s.lower())


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """W3C ``traceparent`` (``version-traceid-parentid-flags``) →
    (trace_id, parent_span_id), lowercased, or None when absent or
    malformed (wrong field length, non-hex, all-zero ids, version
    ``ff``, or a version-00 header with trailing members)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(version) != 2 or not _is_hex(version) or version.lower() == "ff":
        return None
    if version == "00" and len(parts) != 4:
        return None
    if len(trace_id) != 32 or not _is_hex(trace_id):
        return None
    if len(span_id) != 16 or not _is_hex(span_id):
        return None
    if len(flags) != 2 or not _is_hex(flags):
        return None
    trace_id = trace_id.lower()
    span_id = span_id.lower()
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def parse_trace_id(value: str | None) -> tuple[str, str] | None:
    """``x-greptime-trace-id``: a bare 32-hex trace id (no parent span).
    Returns (trace_id, "") or None when absent/malformed."""
    if not value:
        return None
    tid = value.strip().lower()
    if len(tid) != 32 or not _is_hex(tid) or tid == "0" * 32:
        return None
    return tid, ""


# sqlcommenter-style propagation for header-less wire protocols
# (MySQL/PostgreSQL): a SQL comment near the statement head carrying
# traceparent='00-…-…-01'.
_SQL_TRACEPARENT = re.compile(r"traceparent\s*=\s*'([0-9a-fA-F-]{10,80})'")


def extract_sql_trace_context(sql: str) -> tuple[str, str] | None:
    """Trace context from a LEADING SQL comment (sqlcommenter
    convention) — the MySQL/PostgreSQL twin of the HTTP ``traceparent``
    header.  Only comments before the first real token are scanned: a
    traceparent-looking substring inside a string literal must never
    seed the trace context.  A cheap substring gate keeps the
    per-statement cost at one ``in`` check when no context rides along."""
    head = sql[:512]
    if "traceparent" not in head:
        return None
    pos, n = 0, len(head)
    while pos < n:
        while pos < n and head[pos].isspace():
            pos += 1
        if head.startswith("--", pos):
            nl = head.find("\n", pos)
            seg, pos = (head[pos:], n) if nl < 0 else (head[pos:nl], nl + 1)
        elif head.startswith("/*", pos):
            end = head.find("*/", pos)
            seg, pos = (head[pos:], n) if end < 0 else (head[pos:end],
                                                        end + 2)
        else:
            return None  # first real token: stop before any literal
        m = _SQL_TRACEPARENT.search(seg)
        if m is not None:
            return parse_traceparent(m.group(1))
    return None


def _kv(key: str, value: str) -> bytes:
    any_value = _field(1, value.encode())  # AnyValue.string_value
    return _field(1, key.encode()) + _field(2, any_value)


def encode_spans(service_name: str, spans: list[dict]) -> bytes:
    """[span dicts] → ExportTraceServiceRequest bytes."""
    span_msgs = []
    for s in spans:
        msg = _field(1, bytes.fromhex(s["trace_id"]))
        msg += _field(2, bytes.fromhex(s["span_id"]))
        if s.get("parent_span_id"):
            msg += _field(4, bytes.fromhex(s["parent_span_id"]))
        msg += _field(5, s["name"].encode())
        msg += _vint_field(6, s.get("kind", 1))  # SPAN_KIND_INTERNAL
        msg += _fixed64_field(7, s["start_ns"])
        msg += _fixed64_field(8, s["end_ns"])
        for k, v in (s.get("attributes") or {}).items():
            msg += _field(9, _kv(str(k), str(v)))
        msg += _field(15, _vint_field(2, s.get("status_code", 0)))
        span_msgs.append(msg)
    scope_spans = b"".join(_field(2, m) for m in span_msgs)
    resource = _field(1, _kv("service.name", service_name))
    resource_spans = _field(1, resource) + _field(2, scope_spans)
    return _field(1, resource_spans)


class Tracer:
    """Span recorder + OTLP exporter.  One process-wide instance
    (``TRACER``); enable via configure()."""

    def __init__(self):
        self.enabled = False
        self.endpoint: str | None = None
        self.service_name = "greptimedb-tpu"
        self.max_buffer = 2048
        self._spans: list[dict] = []
        self._dropped = 0  # spans trimmed off the buffer head (mark/since)
        self._lock = threading.Lock()
        self._tls = threading.local()  # current span id (parenting)
        self._trace_id_base = os.urandom(12).hex()
        self._counter = 0
        self._stage_children: dict[str, object] = {}

    def configure(self, endpoint: str | None = None,
                  service_name: str | None = None,
                  enabled: bool = True) -> None:
        self.endpoint = endpoint
        if service_name:
            self.service_name = service_name
        self.enabled = enabled

    def disable(self) -> None:
        self.enabled = False
        self.endpoint = None
        with self._lock:
            self._dropped += len(self._spans)
            self._spans.clear()

    def _next_ids(self) -> tuple[str, str]:
        with self._lock:
            self._counter += 1
            c = self._counter
        return (self._trace_id_base + struct.pack(">I", c & 0xFFFFFFFF).hex(),
                os.urandom(8).hex())

    def stage(self, name: str, **attributes) -> _Stage:
        """Hot-path stage boundary.  Tracer off: two clock reads, one
        profiler annotation and one histogram observation, and no span
        record; tracer on: the span as well, child of this thread's
        current span."""
        return _Stage(self, name, attributes)

    def stage_in(self, ctx: tuple[str, str] | None, name: str,
                 **attributes) -> _Stage:
        """A stage of a coroutine: its span hangs under ``ctx``
        ((trace_id, parent_span_id); a fresh trace without one) and this
        thread's current span is left alone, because an event-loop
        thread runs other requests between one request's awaits."""
        return _Stage(self, name, attributes, detached=True, ctx=ctx)

    def _stage_child(self, name: str):
        child = self._stage_children.get(name)
        if child is None:
            child = self._stage_children[name] = M_STAGE.labels(name)
        return child

    def _open_span(self, name: str, attributes: dict,
                   detached: bool = False,
                   ctx: tuple[str, str] | None = None) -> dict:
        """Start a span record: child of this thread's current span, and
        the current span itself until it closes; or, ``detached``, child
        of ``ctx`` with the thread's state untouched."""
        if detached:
            trace_id, parent_id = ctx or (self.new_trace_id(), "")
        else:
            parent = getattr(self._tls, "current", None)
            trace_id, parent_id = parent or (self.new_trace_id(), "")
        span_id = os.urandom(8).hex()
        rec = {
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_span_id": parent_id,
            "name": name,
            "start_ns": time.time_ns(),
            "end_ns": 0,
            "attributes": dict(attributes),
            "status_code": 0,
        }
        if not detached:
            rec["_restore"] = (parent,)  # popped by _close_span
            self._tls.current = (trace_id, span_id)
        return rec

    def _close_span(self, rec: dict, end_ns: int, failed: bool) -> None:
        restore = rec.pop("_restore", None)
        if restore is not None:
            self._tls.current = restore[0]
        rec["end_ns"] = end_ns
        if failed:
            rec["status_code"] = 2  # STATUS_CODE_ERROR
        with self._lock:
            self._spans.append(rec)
            if len(self._spans) > self.max_buffer:
                trim = len(self._spans) - self.max_buffer
                del self._spans[:trim]
                self._dropped += trim

    # ---- trace-context propagation ------------------------------------
    def new_trace_id(self) -> str:
        """A fresh 32-hex trace id (random base + counter suffix)."""
        trace_id, _ = self._next_ids()
        return trace_id

    def current_trace_id(self) -> str:
        """The trace id active on THIS thread ("" when none) — read by
        the slow-query recorder and EXPLAIN ANALYZE so both surfaces
        report the same id the protocol layer returned to the client."""
        cur = getattr(self._tls, "current", None)
        return cur[0] if cur else ""

    @contextlib.contextmanager
    def trace_context(self, ctx: tuple[str, str] | None):
        """Seed this thread's span tree with an external (trace_id,
        parent_span_id) — the protocol servers wrap each statement's
        executor closure in this so a client's W3C ``traceparent``
        parents the whole parse→…→materialize tree.  Installs the
        context even when the tracer is disabled so slow_queries still
        carries the client's trace id.  ``ctx=None`` is a no-op."""
        if ctx is None:
            yield
            return
        prev = getattr(self._tls, "current", None)
        self._tls.current = (ctx[0], ctx[1] or "")
        try:
            yield
        finally:
            self._tls.current = prev

    @contextlib.contextmanager
    def suppressed(self):
        """Recursion guard for the self-monitoring loop: while active on
        this thread, stage() records no span — loopback span/metric
        exports must not observe themselves into the very buffers they
        export (reference export_metrics self_import filters its own
        write path the same way)."""
        prev = getattr(self._tls, "suppress", False)
        self._tls.suppress = True
        try:
            yield
        finally:
            self._tls.suppress = prev

    def drain(self) -> list[dict]:
        with self._lock:
            out = self._spans
            self._spans = []
            self._dropped += len(out)
        return out

    def requeue(self, spans: list[dict]) -> None:
        """Put drained-but-unexported spans back at the buffer head (a
        self-export write failed; they retry next tick).  Reverses
        drain()'s dropped-count bump so mark()/since() offsets stay
        valid; the normal head-trim reclaims any overflow."""
        if not spans:
            return
        with self._lock:
            self._spans[:0] = spans
            self._dropped -= len(spans)
            if len(self._spans) > self.max_buffer:
                trim = len(self._spans) - self.max_buffer
                del self._spans[:trim]
                self._dropped += trim

    # ---- in-process span-tree readback --------------------------------
    # EXPLAIN ANALYZE (and tests) read the spans of ONE query back out of
    # the buffer without draining it away from the OTLP exporter: mark()
    # before, since() after.  Buffer trimming between the two calls can
    # only drop spans older than the mark, so ``mark - dropped`` stays a
    # valid offset.
    def mark(self) -> int:
        with self._lock:
            return self._dropped + len(self._spans)

    def since(self, mark: int) -> list[dict]:
        with self._lock:
            off = max(0, mark - self._dropped)
            return list(self._spans[off:])

    def flush(self, timeout: float = 10.0) -> int:
        """Export buffered spans to the OTLP endpoint; returns count."""
        spans = self.drain()
        if not spans or not self.endpoint:
            return 0
        body = encode_spans(self.service_name, spans)
        req = urllib.request.Request(
            self.endpoint, data=body, method="POST",
            headers={"Content-Type": "application/x-protobuf"})
        urllib.request.urlopen(req, timeout=timeout).read()
        return len(spans)


def render_span_tree(spans: list[dict]) -> str:
    """Indented per-stage text tree from a span list (parent links), with
    wall-ms per span and its recorded attributes — the EXPLAIN ANALYZE
    surface of the query span tree.  Spans arrive in completion order
    (children before parents); siblings render in start order."""
    by_parent: dict[str, list[dict]] = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        parent = s.get("parent_span_id") or ""
        if parent not in ids:
            parent = ""  # orphan (parent outside the capture): root it
        by_parent.setdefault(parent, []).append(s)

    lines: list[str] = []

    def emit(parent: str, depth: int) -> None:
        for s in sorted(by_parent.get(parent, ()),
                        key=lambda x: x["start_ns"]):
            ms = (s["end_ns"] - s["start_ns"]) / 1e6
            attrs = s.get("attributes") or {}
            suffix = "".join(
                f" {k}={v}" for k, v in attrs.items()
                if k not in ("statement",)
            )
            lines.append(f"{'  ' * depth}{s['name']}: {ms:.3f} ms{suffix}")
            emit(s["span_id"], depth + 1)

    emit("", 0)
    return "\n".join(lines)


class _GcPause:
    """``gc.callbacks`` hook: a full collection's pause into
    ``greptime_gc_pause_seconds{generation="2"}`` and a ``gc_pause``
    profiler annotation.  The young generations return at once: they
    run hundreds of times a request and take microseconds.  One slot is
    enough, since the interpreter runs one collection at a time."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            ann = _annotation("gc_pause")
            ann.__enter__()
            self._open = (ann, time.perf_counter())
        elif self._open is not None:
            ann, t0 = self._open
            self._open = None
            M_GC_PAUSE.labels("2").observe(time.perf_counter() - t0)
            ann.__exit__(None, None, None)

    def install(self) -> None:
        """Idempotent; called where a server starts."""
        if self not in gc.callbacks:
            gc.callbacks.append(self)


GC_PAUSE = _GcPause()
TRACER = Tracer()
