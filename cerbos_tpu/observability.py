"""Observability: structured logging, spans, runtime level switching.

Behavioral reference: internal/observability — zap structured logging with
named loggers and SIGUSR1/SIGUSR2 runtime level toggling
(logging/signal.go), span instrumentation at every layer (tracing.StartSpan),
OTLP export configured from OTEL_* env vars. Without egress, spans export to
the structured log (an OTLP exporter slots into SpanExporter when the
collector is reachable); metrics are served by the HTTP listener at
/_cerbos/metrics.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import random
import re
import signal
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional


class JsonFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": self.formatTime(record, "%Y-%m-%dT%H:%M:%S%z"),
            "level": record.levelname,
            "logger": record.name,
            "message": record.getMessage(),
        }
        if record.exc_info:
            out["exception"] = self.formatException(record.exc_info)
        extra = getattr(record, "fields", None)
        if extra:
            out.update(extra)
        return json.dumps(out, default=str)


def init_logging(level: str = "info", fmt: str = "json") -> None:
    root = logging.getLogger("cerbos_tpu")
    root.setLevel(getattr(logging, level.upper(), logging.INFO))
    handler = logging.StreamHandler(sys.stderr)
    if fmt == "json":
        handler.setFormatter(JsonFormatter())
    else:
        handler.setFormatter(logging.Formatter("%(asctime)s %(levelname)s %(name)s %(message)s"))
    root.handlers[:] = [handler]

    # SIGUSR1 raises verbosity, SIGUSR2 restores it (ref: logging/signal.go)
    if hasattr(signal, "SIGUSR1"):
        base_level = root.level

        def to_debug(_sig, _frm):
            root.setLevel(logging.DEBUG)

        def restore(_sig, _frm):
            root.setLevel(base_level)

        with contextlib.suppress(ValueError):  # non-main threads can't set handlers
            signal.signal(signal.SIGUSR1, to_debug)
            signal.signal(signal.SIGUSR2, restore)


# ---------------------------------------------------------------------------
# spans


# One generator a process hands out every identifier a request draws (its call
# id, its trace id, the ids of its spans and of its flight's): seeded from the
# system when this module is imported and again in every forked child, because
# a pool's front ends fork after load and would otherwise hand out the same
# ids in the same order. ``getrandbits`` is one C call under the interpreter
# lock, so threads share the generator with no lock of its own, and no request
# leaves the lock for an identifier (``os.urandom`` drops it around the system
# call, and a request thread that drops it waits out whoever took it). Random,
# not unpredictable, which is what W3C trace-context asks for and what
# OpenTelemetry's own ``RandomIdGenerator`` gives: the ids are not secrets.
_ids = random.Random()  # no argument: seeded from the system's entropy
os.register_at_fork(after_in_child=_ids.seed)


def new_trace_id() -> str:
    """A proper W3C trace id: 32 lowercase hex chars, never all-zero."""
    while True:
        n = _ids.getrandbits(128)
        if n:
            return f"{n:032x}"


def new_span_id() -> str:
    """A proper W3C span id: 16 lowercase hex chars, never all-zero."""
    while True:
        n = _ids.getrandbits(64)
        if n:
            return f"{n:016x}"


# a call id is drawn as a trace id is: 32 lowercase hex digits
new_call_id = new_trace_id


@dataclass(frozen=True)
class SpanContext:
    """Detachable identity of a span: everything needed to parent or link a
    span created on another thread (the batcher hop) or emitted by a remote
    caller (W3C ``traceparent``)."""

    trace_id: str
    span_id: str
    sampled: bool = True

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-{'01' if self.sampled else '00'}"


_TRACEPARENT_RX = re.compile(
    r"^(?P<version>[0-9a-f]{2})-(?P<trace_id>[0-9a-f]{32})-"
    r"(?P<span_id>[0-9a-f]{16})-(?P<flags>[0-9a-f]{2})$"
)


def parse_traceparent(header: Optional[str]) -> Optional[SpanContext]:
    """W3C trace-context ``traceparent`` → SpanContext, or None when the
    header is absent or malformed (per spec, a bad header is ignored and the
    receiver starts a fresh trace)."""
    if not header:
        return None
    m = _TRACEPARENT_RX.match(header.strip().lower())
    if m is None or m.group("version") == "ff":
        return None
    trace_id, span_id = m.group("trace_id"), m.group("span_id")
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return SpanContext(trace_id, span_id, sampled=bool(int(m.group("flags"), 16) & 0x01))


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str = field(default_factory=new_span_id)
    parent_id: str = ""
    start: float = field(default_factory=time.perf_counter)
    # wall-clock capture at span START so a late-flushed OTLP export carries
    # the true start time instead of deriving it backwards from export time
    start_wall_ns: int = field(default_factory=time.time_ns)
    attributes: dict[str, Any] = field(default_factory=dict)
    links: list[SpanContext] = field(default_factory=list)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def add_link(self, ctx: SpanContext) -> None:
        self.links.append(ctx)

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)


class SpanExporter:
    """Export finished spans; the default sink is the debug log. An OTLP
    exporter implements the same single-method interface."""

    _log = logging.getLogger("cerbos_tpu.tracing")

    def export(self, span: Span, duration_ms: float) -> None:
        # runs for every span of every request: build the record only when
        # somebody reads it
        if not self._log.isEnabledFor(logging.DEBUG):
            return
        self._log.debug(
            "span %s", span.name,
            extra={"fields": {"traceId": span.trace_id, "spanId": span.span_id,
                              "parentId": span.parent_id, "durationMs": round(duration_ms, 3),
                              **span.attributes}},
        )


class OTLPSpanExporter(SpanExporter):
    """OTLP/HTTP JSON exporter (ref: internal/observability/otel/{otel,traces}.go
    — the reference configures OTLP from standard OTEL_* env vars; same here:
    OTEL_EXPORTER_OTLP_ENDPOINT, OTEL_SERVICE_NAME). Spans batch in memory
    and flush to {endpoint}/v1/traces on a background thread; export failures
    drop the batch (observability must never block the request path)."""

    def __init__(
        self,
        endpoint: str,
        service_name: str = "cerbos-tpu",
        flush_interval_s: float = 5.0,
        max_batch: int = 512,
    ):
        self.endpoint = endpoint.rstrip("/")
        self.service_name = service_name
        self.max_batch = max_batch
        self._buf: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._interval = flush_interval_s
        self._thread = threading.Thread(target=self._loop, daemon=True, name="otlp-exporter")
        self._thread.start()

    def export(self, span: Span, duration_ms: float) -> None:
        # ids are generated as proper 32/16-hex W3C ids at span creation;
        # export them verbatim (padding short ids here would fabricate ids
        # that collide across spans), and timestamps come from the span's
        # wall-clock START capture, not from flush time
        start_ns = span.start_wall_ns
        otlp_span = {
            "traceId": span.trace_id,
            "spanId": span.span_id,
            "parentSpanId": span.parent_id,
            "name": span.name,
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(start_ns),
            "endTimeUnixNano": str(start_ns + int(duration_ms * 1e6)),
            "attributes": [
                {"key": k, "value": {"stringValue": str(v)}} for k, v in span.attributes.items()
            ],
        }
        if span.links:
            otlp_span["links"] = [
                {"traceId": l.trace_id, "spanId": l.span_id} for l in span.links
            ]
        with self._lock:
            self._buf.append(otlp_span)
            if len(self._buf) > self.max_batch * 4:
                del self._buf[: -self.max_batch]  # bounded: drop oldest

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.flush()

    def flush(self) -> None:
        with self._lock:
            if not self._buf:
                return
            batch, self._buf = self._buf[: self.max_batch], self._buf[self.max_batch:]
        payload = json.dumps(
            {
                "resourceSpans": [
                    {
                        "resource": {
                            "attributes": [
                                {"key": "service.name", "value": {"stringValue": self.service_name}}
                            ]
                        },
                        "scopeSpans": [{"scope": {"name": "cerbos_tpu"}, "spans": batch}],
                    }
                ]
            }
        ).encode()
        import urllib.request

        req = urllib.request.Request(
            f"{self.endpoint}/v1/traces",
            data=payload,
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=5).read()
        except Exception as e:  # noqa: BLE001  (collector down: drop, don't block)
            logging.getLogger("cerbos_tpu.tracing").debug("otlp export failed: %s", e)

    def close(self) -> None:
        self._stop.set()
        # drain everything still buffered, one batch per flush
        while True:
            with self._lock:
                if not self._buf:
                    return
            self.flush()


def init_otlp_from_env() -> bool:
    """Ref: otel.go — standard env wiring. Returns True when enabled."""
    endpoint = os.environ.get("OTEL_EXPORTER_OTLP_TRACES_ENDPOINT") or os.environ.get(
        "OTEL_EXPORTER_OTLP_ENDPOINT"
    )
    if not endpoint:
        return False
    set_exporter(
        OTLPSpanExporter(endpoint, service_name=os.environ.get("OTEL_SERVICE_NAME", "cerbos-tpu"))
    )
    return True


_exporter: SpanExporter = SpanExporter()
_current: dict[int, Span] = {}  # thread id -> active span

# True only while tpu/profiler.py has a capture open; the profiler runs in the
# process that owns the device, so jax is imported there already
capture_open = False
_NO_REGION = contextlib.nullcontext()


def set_capture_open(is_open: bool) -> None:
    global capture_open
    capture_open = bool(is_open)


def region(name: str, **args: Any):
    """A named region on the profiler's trace (``jax.profiler.TraceAnnotation``,
    on the trace's own clock), emitted only while a capture is open: one
    boolean read otherwise, and never an import of jax in a process that owns
    no device. ``start_span`` opens one under the span's name, so the trace
    shows the program's spans between the device's operations."""
    if not capture_open:
        return _NO_REGION
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **args)


def set_exporter(exporter: SpanExporter) -> None:
    global _exporter
    _exporter = exporter


def close_exporter() -> None:
    """Drain + stop the active exporter if it supports it (shutdown path)."""
    close = getattr(_exporter, "close", None)
    if close is not None:
        close()


def current_span() -> Optional[Span]:
    """The calling thread's active span, so a callee can set an attribute on
    the span its caller opened (one dictionary read)."""
    return _current.get(threading.get_ident())


def current_span_context() -> Optional[SpanContext]:
    """Detach the active span's identity so another thread can parent or
    link to it (span parenting via ``_current`` is thread-local; the batcher
    hop carries this snapshot in ``_Pending`` instead)."""
    span = current_span()
    return span.context if span is not None else None


@contextlib.contextmanager
def start_span(
    name: str,
    parent: "SpanContext | Span | None" = None,
    links: Optional[list[SpanContext]] = None,
    **attributes: Any,
) -> Iterator[Span]:
    """Open a span. Parenting is thread-local by default; pass ``parent=`` —
    a SpanContext detached via :func:`current_span_context` or parsed from a
    remote ``traceparent`` — to join a trace across a thread hop or an RPC
    boundary. ``links=`` attaches non-parent causal references (a device
    batch links every co-batched request's trace)."""
    tid = threading.get_ident()
    prev = _current.get(tid)
    eff_parent: "SpanContext | Span | None" = parent if parent is not None else prev
    span = Span(
        name=name,
        trace_id=eff_parent.trace_id if eff_parent else new_trace_id(),
        parent_id=eff_parent.span_id if eff_parent else "",
        attributes=dict(attributes),
        links=list(links or ()),
    )
    _current[tid] = span
    try:
        with region(name):
            yield span
    finally:
        if prev is None:
            _current.pop(tid, None)
        else:
            _current[tid] = prev
        _exporter.export(span, (time.perf_counter() - span.start) * 1000)


def export_span(
    name: str,
    parent: Optional[SpanContext],
    start_wall_ns: int,
    duration_s: float,
    links: Optional[list[SpanContext]] = None,
    **attributes: Any,
) -> Span:
    """Synthesize and export a span for an interval measured elsewhere (the
    gap between a flight's submit returning and its collect starting is
    nobody's code block; the batcher stamps its two ends instead)."""
    span = Span(
        name=name,
        trace_id=parent.trace_id if parent else new_trace_id(),
        parent_id=parent.span_id if parent else "",
        start_wall_ns=start_wall_ns,
        attributes=dict(attributes),
        links=list(links or ()),
    )
    _exporter.export(span, duration_s * 1000)
    return span


class OTLPMetricsExporter:
    """OTLP/HTTP JSON metrics exporter (ref: internal/observability/metrics —
    the reference exports OTel metrics; Prometheus scrape stays at
    /_cerbos/metrics, this pushes the same series to an OTLP collector).
    Metric sources are callables returning {name: value}; gauges snapshot on
    a background interval and POST to {endpoint}/v1/metrics. Export failures
    drop the snapshot — metrics must never block serving."""

    def __init__(
        self,
        endpoint: str,
        service_name: str = "cerbos-tpu",
        interval_s: float = 15.0,
    ):
        self.endpoint = endpoint.rstrip("/")
        self.service_name = service_name
        self._sources: list[Any] = []
        self._stop = threading.Event()
        self._interval = interval_s
        self._thread = threading.Thread(target=self._loop, daemon=True, name="otlp-metrics")
        self._thread.start()

    def add_source(self, fn) -> None:
        self._sources.append(fn)

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.flush()

    def collect(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for fn in list(self._sources):
            try:
                out.update(fn())
            except Exception:  # noqa: BLE001
                logging.getLogger("cerbos_tpu.metrics").debug("metrics source failed", exc_info=True)
        return out

    def flush(self) -> None:
        series = self.collect()
        if not series:
            return
        now_ns = str(time.time_ns())
        metrics = [
            {
                "name": name,
                "gauge": {"dataPoints": [{"asDouble": float(v), "timeUnixNano": now_ns}]},
            }
            for name, v in sorted(series.items())
        ]
        payload = json.dumps(
            {
                "resourceMetrics": [
                    {
                        "resource": {
                            "attributes": [
                                {"key": "service.name", "value": {"stringValue": self.service_name}}
                            ]
                        },
                        "scopeMetrics": [{"scope": {"name": "cerbos_tpu"}, "metrics": metrics}],
                    }
                ]
            }
        ).encode()
        import urllib.request

        req = urllib.request.Request(
            f"{self.endpoint}/v1/metrics",
            data=payload,
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=5).read()
        except Exception as e:  # noqa: BLE001
            logging.getLogger("cerbos_tpu.metrics").debug("otlp metrics export failed: %s", e)

    def close(self) -> None:
        self._stop.set()
        self.flush()


# ---------------------------------------------------------------------------
# metrics registry (Prometheus text exposition)


class Counter:
    """Monotonic counter; rendered as a Prometheus ``counter``."""

    __slots__ = ("name", "help", "_value", "_lock")

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def render(self) -> list[str]:
        with self._lock:
            v = self._value
        return [f"# TYPE {self.name} counter", f"{self.name} {_fmt(v)}"]

    def series(self) -> dict[str, float]:
        with self._lock:
            return {self.name: self._value}


class Gauge:
    """Point-in-time value; ``track_max`` also exports ``<name>_peak``."""

    __slots__ = ("name", "help", "_value", "_peak", "track_max", "_lock")

    def __init__(self, name: str, help: str = "", track_max: bool = False):
        self.name = name
        self.help = help
        self._value = 0.0
        self._peak = 0.0
        self.track_max = track_max
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v
            if v > self._peak:
                self._peak = v

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n
            if self._value > self._peak:
                self._peak = self._value

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value

    @property
    def peak(self) -> float:
        return self._peak

    def render(self) -> list[str]:
        with self._lock:  # value/peak must come from one consistent snapshot
            v, peak = self._value, self._peak
        out = [f"# TYPE {self.name} gauge", f"{self.name} {_fmt(v)}"]
        if self.track_max:
            out += [f"# TYPE {self.name}_peak gauge", f"{self.name}_peak {_fmt(peak)}"]
        return out

    def series(self) -> dict[str, float]:
        with self._lock:
            v, peak = self._value, self._peak
        out = {self.name: v}
        if self.track_max:
            out[f"{self.name}_peak"] = peak
        return out


class Histogram:
    """Fixed-bucket histogram with Prometheus cumulative-bucket exposition."""

    __slots__ = ("name", "help", "buckets", "_counts", "_sum", "_count", "_lock")

    def __init__(self, name: str, help: str = "", buckets: Optional[list[float]] = None):
        self.name = name
        self.help = help
        self.buckets = sorted(buckets or [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0])
        self._counts = [0] * (len(self.buckets) + 1)  # +1 for +Inf
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self._sum += v
            self._count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    def observe_many(self, values: list[float]) -> None:
        """``observe`` for each of ``values`` under ONE take of the lock: for a
        hot loop that gathers its observations and books them once (a flight's
        schema validations)."""
        buckets, counts = self.buckets, self._counts
        with self._lock:
            for v in values:
                self._sum += v
                self._count += 1
                for i, b in enumerate(buckets):
                    if v <= b:
                        counts[i] += 1
                        break
                else:
                    counts[-1] += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def snapshot(self) -> tuple[list[int], float, int]:
        """(bucket counts, sum, count) captured under the lock — a render
        racing observe() must never expose cumulative buckets that don't sum
        to ``_count``."""
        with self._lock:
            return list(self._counts), self._sum, self._count

    def percentile(self, p: float) -> float:
        """Estimate the p-quantile (0..1) by linear interpolation within the
        owning bucket; the +Inf bucket clamps to the largest finite bound."""
        counts, _, count = self.snapshot()
        if count == 0:
            return 0.0
        rank = p * count
        cum = 0
        lo = 0.0
        for i, b in enumerate(self.buckets):
            prev = cum
            cum += counts[i]
            if cum >= rank:
                frac = (rank - prev) / counts[i] if counts[i] else 0.0
                return lo + (b - lo) * frac
            lo = b
        return self.buckets[-1] if self.buckets else 0.0

    def render(self, label: str = "") -> list[str]:
        counts, total, count = self.snapshot()
        sep = "," if label else ""
        out = [] if label else [f"# TYPE {self.name} histogram"]
        cum = 0
        for i, b in enumerate(self.buckets):
            cum += counts[i]
            out.append(f'{self.name}_bucket{{{label}{sep}le="{_fmt(b)}"}} {cum}')
        out.append(f'{self.name}_bucket{{{label}{sep}le="+Inf"}} {count}')
        suffix = f"{{{label}}}" if label else ""
        out.append(f"{self.name}_sum{suffix} {_fmt(total)}")
        out.append(f"{self.name}_count{suffix} {count}")
        return out

    def series(self) -> dict[str, float]:
        _, total, count = self.snapshot()
        return {f"{self.name}_sum": total, f"{self.name}_count": float(count)}


def _label_expr(label, key) -> str:
    """Render a label expression for a vec child. ``label`` is a name or a
    tuple of names (multi-dimension vecs, e.g. ``("stage", "shard")``);
    ``key`` is the matching value or tuple of values."""
    if isinstance(label, tuple):
        vals = key if isinstance(key, tuple) else (key,)
        return ",".join(f'{ln}="{lv}"' for ln, lv in zip(label, vals))
    return f'{label}="{key}"'


def _series_suffix(key) -> str:
    if isinstance(key, tuple):
        return "_".join(str(k) for k in key)
    return str(key)


class CounterVec:
    """Counter with one or more label dimensions; each label value (or value
    tuple, when ``label`` is a tuple of names) gets a child series rendered
    as ``name{label="value"} n``. ``value`` sums all children so callers
    that read the unlabeled total (back-compat with the plain Counter this
    may replace) keep working."""

    __slots__ = ("name", "help", "label", "_children", "_lock")

    def __init__(self, name: str, help: str = "", label: str = "reason"):
        self.name = name
        self.help = help
        self.label = label
        self._children: dict[str, float] = {}
        self._lock = threading.Lock()

    def inc(self, value: str = "", n: float = 1.0) -> None:
        with self._lock:
            self._children[value] = self._children.get(value, 0.0) + n

    def get(self, value: str = "") -> float:
        with self._lock:
            return self._children.get(value, 0.0)

    @property
    def value(self) -> float:
        with self._lock:
            return sum(self._children.values())

    def render(self) -> list[str]:
        with self._lock:
            children = sorted(self._children.items(), key=lambda kv: str(kv[0]))
        out = [f"# TYPE {self.name} counter"]
        if not children:
            out.append(f"{self.name} 0")
        for label_value, v in children:
            out.append(f'{self.name}{{{_label_expr(self.label, label_value)}}} {_fmt(v)}')
        return out

    def series(self) -> dict[str, float]:
        with self._lock:
            children = dict(self._children)
        return {
            f"{self.name}_{_series_suffix(lv)}" if lv else self.name: v
            for lv, v in children.items()
        }


class GaugeVec:
    """Gauge with one label dimension; each label value gets a child Gauge
    rendered as ``name{label="value"} v``. ``labels()`` hands the caller the
    child Gauge itself, so hot paths bind once and then use the plain Gauge
    surface (``set``/``inc``/``value``/``peak``). Used for the per-shard
    occupancy/inflight/breaker-state series."""

    __slots__ = ("name", "help", "label", "track_max", "_children", "_lock")

    def __init__(self, name: str, help: str = "", label: str = "shard", track_max: bool = False):
        self.name = name
        self.help = help
        self.label = label
        self.track_max = track_max
        self._children: dict[str, Gauge] = {}
        self._lock = threading.Lock()

    def labels(self, value: str) -> Gauge:
        with self._lock:
            child = self._children.get(value)
            if child is None:
                child = Gauge(self.name, self.help, track_max=self.track_max)
                self._children[value] = child
            return child

    def set(self, value: str, v: float) -> None:
        self.labels(value).set(v)

    def get(self, value: str) -> float:
        with self._lock:
            child = self._children.get(value)
        return child.value if child is not None else 0.0

    @property
    def value(self) -> float:
        """Sum over children — a read-alias for callers holding the name
        from before a Gauge→GaugeVec upgrade."""
        with self._lock:
            children = list(self._children.values())
        return sum(c.value for c in children)

    def render(self) -> list[str]:
        with self._lock:
            children = sorted(self._children.items(), key=lambda kv: str(kv[0]))
        out = [f"# TYPE {self.name} gauge"]
        peaks: list[str] = []
        for label_value, child in children:
            with child._lock:
                v, peak = child._value, child._peak
            expr = _label_expr(self.label, label_value)
            out.append(f"{self.name}{{{expr}}} {_fmt(v)}")
            if self.track_max:
                peaks.append(f"{self.name}_peak{{{expr}}} {_fmt(peak)}")
        if peaks:
            out.append(f"# TYPE {self.name}_peak gauge")
            out.extend(peaks)
        return out

    def series(self) -> dict[str, float]:
        with self._lock:
            children = sorted(self._children.items(), key=lambda kv: str(kv[0]))
        out: dict[str, float] = {}
        for label_value, child in children:
            with child._lock:
                v, peak = child._value, child._peak
            suffix = _series_suffix(label_value)
            out[f"{self.name}_{suffix}" if suffix else self.name] = v
            if self.track_max:
                out[f"{self.name}_peak_{suffix}" if suffix else f"{self.name}_peak"] = peak
        return out


class HistogramVec:
    """Histogram with one or more label dimensions; each label value (or
    value tuple, when ``label`` is a tuple of names like
    ``("stage", "shard")``) gets a child Histogram rendered as
    ``name_bucket{label="value",le="..."}``. Used for the per-stage
    device-path latency series so Grafana can do
    ``histogram_quantile(..., sum by (le, stage))`` over one instrument."""

    __slots__ = ("name", "help", "label", "buckets", "_children", "_lock")

    def __init__(
        self,
        name: str,
        help: str = "",
        label: str = "stage",
        buckets: Optional[list[float]] = None,
    ):
        self.name = name
        self.help = help
        self.label = label
        self.buckets = buckets
        self._children: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def labels(self, value: str) -> Histogram:
        with self._lock:
            child = self._children.get(value)
            if child is None:
                child = Histogram(self.name, self.help, buckets=self.buckets)
                self._children[value] = child
            return child

    def observe(self, value: str, v: float) -> None:
        self.labels(value).observe(v)

    def render(self) -> list[str]:
        with self._lock:
            children = sorted(self._children.items(), key=lambda kv: str(kv[0]))
        out = [f"# TYPE {self.name} histogram"]
        for label_value, child in children:
            out.extend(child.render(label=_label_expr(self.label, label_value)))
        return out

    def series(self) -> dict[str, float]:
        with self._lock:
            children = sorted(self._children.items(), key=lambda kv: str(kv[0]))
        out: dict[str, float] = {}
        for label_value, child in children:
            _, total, count = child.snapshot()
            suffix = _series_suffix(label_value)
            out[f"{self.name}_{suffix}_sum"] = total
            out[f"{self.name}_{suffix}_count"] = float(count)
        return out


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


class MetricsRegistry:
    """Process-wide named metrics; get-or-create so forked workers and
    re-initialized cores share one instrument per name."""

    def __init__(self):
        self._metrics: dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, name: str, factory, want: tuple = (), help: str = ""):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            elif want and not isinstance(m, want):
                # one name must never serve two instrument types: the second
                # registrant would silently read/write the wrong semantics
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {want[0].__name__}"
                )
            elif help and not m.help:
                # a reader may have touched the name first with no help text;
                # the owning registration backfills it
                m.help = help
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        # CounterVec is an allowed read-alias: its .value sums all children,
        # so code holding the unlabeled total keeps working after an upgrade
        return self._get_or_create(
            name, lambda: Counter(name, help), want=(Counter, CounterVec), help=help
        )

    def counter_vec(self, name: str, help: str = "", label: str = "reason") -> CounterVec:
        with self._lock:
            m = self._metrics.get(name)
            if isinstance(m, Counter):
                # a plain Counter was registered under this name first (e.g.
                # a reader touched it before the owner): upgrade in place,
                # preserving the accumulated total under the empty label
                vec = CounterVec(name, help or m.help, label=label)
                if m.value:
                    vec.inc("", m.value)
                self._metrics[name] = vec
                return vec
            if m is None:
                m = CounterVec(name, help, label=label)
                self._metrics[name] = m
            elif not isinstance(m, CounterVec):
                raise TypeError(
                    f"metric {name!r} already registered as {type(m).__name__}, not CounterVec"
                )
            elif help and not m.help:
                m.help = help
            return m

    def gauge(self, name: str, help: str = "", track_max: bool = False) -> Gauge:
        # GaugeVec is an allowed read-alias: its .value sums all children,
        # so code holding the unlabeled total keeps working after an upgrade
        return self._get_or_create(
            name,
            lambda: Gauge(name, help, track_max=track_max),
            want=(Gauge, GaugeVec),
            help=help,
        )

    def gauge_vec(
        self, name: str, help: str = "", label: str = "shard", track_max: bool = False
    ) -> GaugeVec:
        with self._lock:
            m = self._metrics.get(name)
            if isinstance(m, Gauge):
                # a plain Gauge was registered under this name first (e.g. a
                # reader touched it before the owner): upgrade in place,
                # preserving the current value under the empty label
                vec = GaugeVec(name, help or m.help, label=label, track_max=track_max)
                if m.value or m.peak:
                    child = vec.labels("")
                    child.set(m.value)
                self._metrics[name] = vec
                return vec
            if m is None:
                m = GaugeVec(name, help, label=label, track_max=track_max)
                self._metrics[name] = m
            elif not isinstance(m, GaugeVec):
                raise TypeError(
                    f"metric {name!r} already registered as {type(m).__name__}, not GaugeVec"
                )
            elif help and not m.help:
                m.help = help
            return m

    def histogram(self, name: str, help: str = "", buckets: Optional[list[float]] = None) -> Histogram:
        return self._get_or_create(
            name, lambda: Histogram(name, help, buckets=buckets), want=(Histogram,), help=help
        )

    def histogram_vec(
        self,
        name: str,
        help: str = "",
        label: str = "stage",
        buckets: Optional[list[float]] = None,
    ) -> HistogramVec:
        return self._get_or_create(
            name,
            lambda: HistogramVec(name, help, label=label, buckets=buckets),
            want=(HistogramVec,),
            help=help,
        )

    def instruments(self) -> dict[str, Any]:
        """Snapshot of name → instrument (the metrics-lint walk)."""
        with self._lock:
            return dict(self._metrics)

    def render(self) -> str:
        with self._lock:
            metrics = sorted(self._metrics.items())
        lines: list[str] = []
        for _, m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self) -> dict[str, float]:
        """Flat gauge view for the OTLP metrics exporter sources."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: dict[str, float] = {}
        for m in metrics:
            out.update(m.series())
        return out


_registry = MetricsRegistry()


def metrics() -> MetricsRegistry:
    return _registry


_SAMPLE_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)$")
_FAMILY_COMMENT = re.compile(r"^# (TYPE|HELP) (\S+)")


def relabel_metrics_text(text: str, label: str, value: str) -> str:
    """Inject ``label="value"`` into every sample of a Prometheus text
    exposition. Worker pools use this to stamp each process's scrape with
    its identity: a scrape against the shared SO_REUSEPORT port lands on a
    random sibling, and without the label its series would silently alias
    the others' (docs/OBSERVABILITY.md, pooled scrape semantics). A sample
    that already carries ``label`` keeps its own value as
    ``exported_<label>``, as Prometheus does with a target's colliding
    label: one name twice in a sample makes the whole scrape unparseable."""
    esc = value.replace("\\", "\\\\").replace('"', '\\"')
    own = re.compile(rf'(?<![A-Za-z0-9_]){re.escape(label)}="')
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            out.append(line)
            continue
        m = _SAMPLE_LINE.match(line)
        if m is None:
            out.append(line)
            continue
        name, labels, val = m.groups()
        inner = own.sub(f'exported_{label}="', labels[1:-1]) if labels else ""
        merged = f'{label}="{esc}"' + (f",{inner}" if inner else "")
        out.append(f"{name}{{{merged}}} {val}")
    return "\n".join(out) + ("\n" if out else "")


def merge_metrics_texts(primary: str, *others: str) -> str:
    """Concatenate Prometheus text expositions, dropping ``# TYPE``/``# HELP``
    lines for families the earlier texts already declared (duplicate family
    metadata is invalid exposition). Samples are never dropped — callers must
    have disambiguated them with :func:`relabel_metrics_text` first."""
    seen: set[tuple[str, str]] = set()
    out: list[str] = []
    for text in (primary, *others):
        for line in text.splitlines():
            m = _FAMILY_COMMENT.match(line)
            if m is not None:
                key = (m.group(1), m.group(2))
                if key in seen:
                    continue
                seen.add(key)
            out.append(line)
    return "\n".join(out) + ("\n" if out else "")


_metrics_exporter: "OTLPMetricsExporter | None" = None


def init_otlp_metrics_from_env() -> "OTLPMetricsExporter | None":
    """OTEL_EXPORTER_OTLP_METRICS_ENDPOINT / OTEL_EXPORTER_OTLP_ENDPOINT."""
    global _metrics_exporter
    endpoint = os.environ.get("OTEL_EXPORTER_OTLP_METRICS_ENDPOINT") or os.environ.get(
        "OTEL_EXPORTER_OTLP_ENDPOINT"
    )
    if not endpoint:
        return None
    _metrics_exporter = OTLPMetricsExporter(
        endpoint, service_name=os.environ.get("OTEL_SERVICE_NAME", "cerbos-tpu")
    )
    return _metrics_exporter


def metrics_exporter() -> "OTLPMetricsExporter | None":
    return _metrics_exporter


def close_metrics_exporter() -> None:
    global _metrics_exporter
    if _metrics_exporter is not None:
        _metrics_exporter.close()
        _metrics_exporter = None
