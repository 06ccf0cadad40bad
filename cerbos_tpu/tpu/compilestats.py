"""Compile-economy telemetry: the other half of the device serving cost.

The request path is lit end to end (spans, stage histograms, the flight
recorder), but XLA compilation — seconds per distinct trace, the single
largest latency event a replica can produce — was dark. This module wraps
every ``jit_cache`` population site in :mod:`evaluator` plus the persistent
cache in :mod:`jitcache` and answers, per process:

- how many compiles happened, how long each took, and whether the
  persistent cache absorbed them (``cerbos_tpu_xla_compiles_total{source}``,
  ``cerbos_tpu_xla_compile_seconds``);
- how often the live jit cache hit vs missed
  (``cerbos_tpu_jit_cache_{hits,misses}_total``);
- how many distinct compiled layouts exist
  (``cerbos_tpu_xla_layout_cardinality``) — the figure that bounds both
  device program memory and worst-case warmup time;
- device memory from ``device.memory_stats()`` when a backend exposes it;
- whether the layout keyspace is CHURNING: the recompile-storm detector
  fires when >= N distinct layouts compile within W seconds, meaning the
  shape-bucket ladder or variant budget no longer amortizes and the replica
  is spending its time in XLA instead of serving;
- what the layout preloader (``evaluator._LayoutPreloader``) brought in
  ahead of traffic from the layout manifest
  (``cerbos_tpu_xla_preloads_total{outcome}``,
  ``cerbos_tpu_xla_preload_seconds``, flight event ``xla_preload_done``).
  Its loads are compiles like any other to the three families above, and
  are kept from the storm detector: a deliberate walk is not churn;
- the table's layout class, the ``(K, J, D)`` every batch is packed at
  (``packer.LayoutClass``): ``cerbos_tpu_xla_layout_class{dim}`` and how
  often traffic raised it, ``cerbos_tpu_xla_layout_class_grows_total{dim}``.

Everything is process-global (like the metrics registry it feeds) so the
serving batcher, a direct ``check()`` and bench all account into one place.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Optional

from ..engine.flight import recorder as flight_recorder
from ..observability import metrics

_log = logging.getLogger("cerbos_tpu.compilestats")

# compile latencies span four orders of magnitude: sub-second persistent
# cache loads up to multi-minute cold TPU compiles
_COMPILE_BUCKETS = [0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0]

STORM_THRESHOLD = 8
STORM_WINDOW_S = 120.0

# what became of one manifest entry the preloader walked: brought in from the
# persistent cache, compiled because the cache had no entry for it, already
# in the jit cache (a flight met it first), or not buildable
PRELOAD_OUTCOMES = ("loaded", "fresh", "held", "failed")

# the dimensions of a jit key, in the order a compile is blamed on them
NOVEL_DIMS = ("shape", "class", "variant", "columns")
CLASS_DIMS = ("K", "J", "D")
NOVEL_COMBINATION = "combination"  # every component seen before, never together


def key_components(trace_key: Any) -> Optional[dict]:
    """The seven components of the evaluator's jit key ``(B_pad, BA_pad, K, J,
    D, variant, layout.sig)`` (six on the mesh path, which has no column
    layout), grouped by dimension; None for a key of another shape."""
    if not isinstance(trace_key, tuple) or len(trace_key) not in (6, 7):
        return None
    if not all(isinstance(x, int) for x in trace_key[:5]):
        return None
    return {
        "shape": trace_key[:2],
        "class": trace_key[2:5],
        "variant": trace_key[5],
        "columns": trace_key[6] if len(trace_key) == 7 else None,
    }


def _digest(component: Any) -> Optional[str]:
    """A variant or a column layout is a tuple of hundreds of entries: the
    flight event carries a checksum of it, one that repeats across processes
    (``hash()`` of strings does not), so two events say whether it differed."""
    if component is None:
        return None
    return f"{zlib.crc32(repr(component).encode()):08x}"


class NoveltyClassifier:
    """Which dimension of the jit key made a compile necessary: the first, in
    the order of ``NOVEL_DIMS``, whose value no earlier compile had. A growth
    of the layout class is named as what it is: a program (shape and variant)
    that was built before at ANOTHER class is blamed on ``class``, whether or
    not a new shape brought that class's value first."""

    def __init__(self):
        self._seen: dict[str, set] = {d: set() for d in NOVEL_DIMS}
        self._classes: dict[tuple, set] = {}  # (shape, variant) -> the classes it was built at

    def observe(self, components: dict) -> str:
        novel = NOVEL_COMBINATION
        for dim in reversed(NOVEL_DIMS):
            value = components[dim]
            if value is not None and value not in self._seen[dim]:
                self._seen[dim].add(value)
                novel = dim
        built_at = self._classes.setdefault((components["shape"], components["variant"]), set())
        if built_at and components["class"] not in built_at:
            novel = "class"
        built_at.add(components["class"])
        return novel


class RecompileStormDetector:
    """Sliding-window detector over compile events.

    A healthy replica compiles each dominant layout once and then serves
    from cache; a storm (>= ``threshold`` DISTINCT layout keys compiled
    within ``window_s`` seconds) means traffic shapes are defeating the
    pow2 bucket ladder / variant budget. Fires once per excursion: after
    tripping, it stays quiet until the distinct count falls back below the
    threshold, so a sustained storm is one event, not one per compile.

    ``clock`` is injectable for deterministic tests (same pattern as
    ``engine.health.DeviceHealth``).
    """

    def __init__(
        self,
        threshold: int = STORM_THRESHOLD,
        window_s: float = STORM_WINDOW_S,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.threshold = int(threshold)
        self.window_s = float(window_s)
        self._clock = clock
        self._events: deque[tuple[float, Any]] = deque()
        self._lock = threading.Lock()
        self._in_storm = False
        self.storms = 0

    def observe(self, layout_key: Any) -> Optional[int]:
        """Record one compile; returns the distinct-layout count when this
        observation trips a NEW storm, else None."""
        now = self._clock()
        with self._lock:
            self._events.append((now, layout_key))
            cutoff = now - self.window_s
            while self._events and self._events[0][0] < cutoff:
                self._events.popleft()
            distinct = len({k for _, k in self._events})
            if distinct < self.threshold:
                self._in_storm = False
                return None
            if self._in_storm:
                return None
            self._in_storm = True
            self.storms += 1
            return distinct


class CompileStats:
    """Process-wide compile accounting feeding the shared metrics registry."""

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        storm_threshold: int = STORM_THRESHOLD,
        storm_window_s: float = STORM_WINDOW_S,
    ):
        reg = metrics()
        self.m_compiles = reg.counter_vec(
            "cerbos_tpu_xla_compiles_total",
            "XLA compilations by source: fresh (XLA ran) or persistent (loaded from the on-disk cache)",
            label="source",
        )
        self.m_novel = reg.counter_vec(
            "cerbos_tpu_xla_compile_novel_total",
            "XLA compilations by the first dimension of the jit key that was new: shape (B_pad, BA_pad), "
            "class (K, J, D: a shape and variant built before at another layout class, so the class grew), "
            "variant, columns (the column layout), or combination (all seen, never together)",
            label="dim",
        )
        self.m_compile_seconds = reg.histogram(
            "cerbos_tpu_xla_compile_seconds",
            "Wall time of each XLA compile (first invocation of a new jit trace)",
            buckets=_COMPILE_BUCKETS,
        )
        self.m_hits = reg.counter(
            "cerbos_tpu_jit_cache_hits_total",
            "Device dispatches served by an already-compiled jit trace",
        )
        self.m_misses = reg.counter(
            "cerbos_tpu_jit_cache_misses_total",
            "Device dispatches that had to build (and compile) a new jit trace",
        )
        self.m_cardinality = reg.gauge(
            "cerbos_tpu_xla_layout_cardinality",
            "Distinct compiled device layouts (shape bucket x variant x column layout) this process",
        )
        self.m_storms = reg.counter(
            "cerbos_tpu_recompile_storms_total",
            "Recompile storms: sliding-window excursions of distinct-layout compiles",
        )
        self.m_variant_fallbacks = reg.counter(
            "cerbos_tpu_variant_budget_fallbacks_total",
            "Batches forced onto the full variant because the distinct-variant budget was exhausted",
        )
        self.m_mem_in_use = reg.gauge(
            "cerbos_tpu_device_memory_bytes_in_use",
            "Device memory in use (device.memory_stats, 0 when the backend reports none)",
        )
        self.m_mem_limit = reg.gauge(
            "cerbos_tpu_device_memory_bytes_limit",
            "Device memory capacity (device.memory_stats, 0 when the backend reports none)",
        )
        self.m_mem_peak = reg.gauge(
            "cerbos_tpu_device_memory_peak_bytes_in_use",
            "Peak device memory in use (device.memory_stats, 0 when the backend reports none)",
        )
        self.m_preloads = reg.counter_vec(
            "cerbos_tpu_xla_preloads_total",
            "Layout manifest entries the preloader walked, by outcome: loaded (from the persistent cache), "
            "fresh (XLA compiled it), held (the jit cache already held the layout), failed (not buildable)",
            label="outcome",
        )
        for outcome in PRELOAD_OUTCOMES:
            self.m_preloads.inc(outcome, 0.0)  # every series scrapes as 0 before the walk
        self.m_preload_seconds = reg.histogram(
            "cerbos_tpu_xla_preload_seconds",
            "Wall time the preloader spent on each manifest entry it walked (trace, load or compile, first call)",
            buckets=_COMPILE_BUCKETS,
        )
        self.m_class = reg.gauge_vec(
            "cerbos_tpu_xla_layout_class",
            "The serving table's layout class by dimension: the K (role slots), J (candidates a slot) and D (scope "
            "depth) every batch is packed and dispatched at, the running maximum of what its request shapes needed "
            "(0 before the first device-route pack)",
            label="dim",
        )
        self.m_class_grows = reg.counter_vec(
            "cerbos_tpu_xla_layout_class_grows_total",
            "Times a request shape raised a dimension of the layout class since boot: every shape bucket met after "
            "it is built anew at the grown class, and the layouts of the smaller class are dead",
            label="dim",
        )
        for dim in CLASS_DIMS:
            self.m_class.set(dim, 0.0)
            self.m_class_grows.inc(dim, 0.0)
        self.detector = RecompileStormDetector(
            threshold=storm_threshold, window_s=storm_window_s, clock=clock
        )
        self._layout_class: Optional[tuple] = None
        self._novelty = NoveltyClassifier()
        self._lock = threading.Lock()
        self._layouts: set[Any] = set()
        self._per_layout: dict[str, int] = {}
        self._compiles = 0
        self._compile_seconds = 0.0
        self._persistent = 0
        self._hits = 0
        self._misses = 0

    # -- recording ---------------------------------------------------------

    def record_compile(
        self,
        layout_key: str,
        seconds: float,
        source: str = "fresh",
        trace_key: Any = None,
        storm: bool = True,
        put_bytes: Optional[int] = None,
        fetch_bytes: Optional[int] = None,
    ) -> None:
        """One compile completed. ``layout_key`` is the display shape
        signature (``B64xBA128``-style); ``trace_key`` is the exact jit-cache
        key, so cardinality/storm detection see variant and column-layout
        churn that shares a shape bucket. ``storm=False`` (the preloader's
        loads) keeps the compile from the storm detector. ``put_bytes`` and
        ``fetch_bytes``, where the caller knows them: what a call of this
        layout hands the device and what it fetches back, into the event."""
        tk = trace_key if trace_key is not None else layout_key
        self.m_compiles.inc(source)
        self.m_compile_seconds.observe(seconds)
        parts = key_components(trace_key)
        key_fields: dict[str, Any] = {}
        with self._lock:
            if parts is not None:
                novel = self._novelty.observe(parts)
                (b_pad, ba_pad), (k, j, d) = parts["shape"], parts["class"]
                key_fields = {
                    "B_pad": b_pad, "BA_pad": ba_pad, "K": k, "J": j, "D": d,
                    "variant": _digest(parts["variant"]), "columns": _digest(parts["columns"]),
                    "novel": novel,
                }
                if self._layout_class is not None:
                    # the table's class NOW: where it differs from K, J, D the batch was packed before a growth
                    key_fields["layout_class"] = list(self._layout_class)
            self._compiles += 1
            self._compile_seconds += seconds
            if source == "persistent":
                self._persistent += 1
            self._layouts.add(tk)
            self._per_layout[layout_key] = self._per_layout.get(layout_key, 0) + 1
            card = len(self._layouts)
        self.m_cardinality.set(card)
        if key_fields:
            self.m_novel.inc(key_fields["novel"])
        if put_bytes is not None:
            key_fields["put_bytes"] = int(put_bytes)
        if fetch_bytes is not None:
            key_fields["fetch_bytes"] = int(fetch_bytes)
        # every compile is a flight event: /_cerbos/debug/flight then answers
        # "which layout, how long, fresh or from the persistent cache", and,
        # with the key's seven components, "what about it was new"
        flight_recorder().record_event(
            "xla_compile", layout_key=layout_key, seconds=round(seconds, 4), source=source, **key_fields
        )
        distinct = self.detector.observe(tk) if storm else None
        if distinct is not None:
            self.m_storms.inc()
            _log.warning(
                "recompile storm: %d distinct device layouts compiled within %.0fs "
                "(threshold %d, last layout %s) — shape buckets or variant budget "
                "are churning faster than the cache amortizes",
                distinct,
                self.detector.window_s,
                self.detector.threshold,
                layout_key,
            )
            flight_recorder().record_event(
                "recompile_storm",
                distinct=distinct,
                window_s=self.detector.window_s,
                threshold=self.detector.threshold,
                layout_key=layout_key,
            )
        self.refresh_device_memory()

    def record_hit(self) -> None:
        self.m_hits.inc()
        with self._lock:
            self._hits += 1

    def record_miss(self) -> None:
        self.m_misses.inc()
        with self._lock:
            self._misses += 1

    def record_layout_class(self, kjd: tuple, grown: tuple = ()) -> None:
        """The table's layout class is ``kjd``: restored from the manifest,
        or raised by a request shape in the dimensions ``grown``."""
        self._layout_class = tuple(kjd)
        for dim, extent in zip(CLASS_DIMS, kjd):
            self.m_class.set(dim, float(extent))
        for dim in grown:
            self.m_class_grows.inc(dim)

    def record_variant_fallback(self) -> None:
        self.m_variant_fallbacks.inc()

    def record_preload(self, outcome: str, seconds: float) -> None:
        """The preloader is done with one manifest entry."""
        self.m_preloads.inc(outcome)
        self.m_preload_seconds.observe(seconds)

    def record_preload_done(self, counts: dict, seconds: float, stopped: bool) -> None:
        """The preloader's walk ended: at the manifest's last entry, or
        (``stopped``) because the table it walked for was invalidated."""
        flight_recorder().record_event(
            "xla_preload_done", seconds=round(seconds, 3), stopped=stopped, **counts
        )

    def refresh_device_memory(self) -> None:
        """Update the device memory gauges (summed over this process's local
        devices). Runs only in the process that owns the device
        (``jitcache.open_device``): a front end or pre-fork parent that has
        merely imported jax must never be the one to initialize a backend."""
        from . import jitcache

        per_device = jitcache.device_memory()
        if not per_device:
            return
        self.m_mem_in_use.set(float(sum(d["bytes_in_use"] for d in per_device)))
        self.m_mem_limit.set(float(sum(d["bytes_limit"] for d in per_device)))
        self.m_mem_peak.set(float(sum(d["peak_bytes_in_use"] for d in per_device)))

    # -- reading -----------------------------------------------------------

    def snapshot(self) -> dict:
        """Machine-readable compile economics (bench artifact, jitcache
        status, debug surfaces)."""
        with self._lock:
            return {
                "compiles": self._compiles,
                "compile_seconds_total": round(self._compile_seconds, 6),
                "persistent_loads": self._persistent,
                "cache_hits": self._hits,
                "cache_misses": self._misses,
                "layout_cardinality": len(self._layouts),
                "storms": self.detector.storms,
                "per_layout_compiles": dict(self._per_layout),
            }


_stats = CompileStats()


def stats() -> CompileStats:
    return _stats


def configure(storm_threshold: Optional[int] = None, storm_window_s: Optional[float] = None) -> CompileStats:
    """Re-bound the global detector in place (bootstrap), preserving the
    instance every instrumented module already holds."""
    det = _stats.detector
    if storm_threshold is not None:
        det.threshold = int(storm_threshold)
    if storm_window_s is not None:
        det.window_s = float(storm_window_s)
    return _stats


_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_cache_watch = threading.local()
_cache_listener_lock = threading.Lock()
_cache_listener_on = False


def _on_jax_event(event: str, **_kw: Any) -> None:
    seen = getattr(_cache_watch, "seen", None)
    if seen is not None:
        seen.append(event)


@contextlib.contextmanager
def cache_events():
    """What jax's persistent cache said on THIS thread while the block ran:
    jax announces a cache request and a hit on the thread that compiles, so
    two threads that compile at once (the drain thread and the preloader)
    each read their own, where the cache directory's entry count is one
    number for both. Yields the list the events are appended to."""
    global _cache_listener_on
    with _cache_listener_lock:  # once a compile, never contended for long: no need to look before it
        if not _cache_listener_on:
            import jax.monitoring

            jax.monitoring.register_event_listener(_on_jax_event)
            _cache_listener_on = True
    _cache_watch.seen = seen = []
    try:
        yield seen
    finally:
        _cache_watch.seen = None


def source_of(seen: list) -> Optional[str]:
    """``persistent`` when every executable the thread asked the cache for
    came from it, ``fresh`` when XLA ran for one, None when the cache was not
    asked (it is off, or this jax announces nothing)."""
    asked = seen.count(_CACHE_REQUEST)
    if not asked:
        return None
    return "persistent" if seen.count(_CACHE_HIT) >= asked else "fresh"


def timed_first_call(layout_key: str, fn: Callable[..., Any], kwargs: dict, trace_key: Any = None):
    """Invoke a FRESHLY BUILT jit function, timing its first call.

    ``jax.jit`` defers trace+compile to the first invocation (dispatch of
    the compiled program stays async, so the measured wall time is the
    compile, not the device execution). The source is what jax's cache said
    on this thread (:func:`cache_events`); where it said nothing, the
    persistent-cache entry count before/after: a compile that writes no new
    entry while the cache is enabled was loaded from disk. Where the result is
    one array (the single-device path) the event also says what the call
    weighs: the bytes of ``kwargs`` and of the result."""
    from . import jitcache

    before = jitcache.entry_count()
    t0 = time.perf_counter()
    with cache_events() as seen:
        out = fn(**kwargs)
    dt = time.perf_counter() - t0
    source = source_of(seen)
    if source is None:
        source = "fresh"
        if before is not None:
            after = jitcache.entry_count()
            if after is not None and after <= before:
                source = "persistent"
    fetch_bytes = getattr(out, "nbytes", None)  # the mesh path returns several arrays: no one figure
    put_bytes = sum(a.nbytes for a in kwargs.values()) if fetch_bytes is not None else None
    _stats.record_compile(
        layout_key, dt, source=source, trace_key=trace_key, put_bytes=put_bytes, fetch_bytes=fetch_bytes
    )
    return out
