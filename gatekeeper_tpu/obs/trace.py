"""Span primitive + process tracer: always-on, ~zero-cost tracing for the
two hot paths (admission webhook, batched audit sweep).

Design constraints (ISSUE 2 tentpole):

- Monotonic timings only.  Span start/end come from ``time.perf_counter``;
  a wall-clock anchor is captured ONCE at import so completed traces can
  be rendered with absolute timestamps without any hot-path ``time.time``
  call (tools/check_observability.py enforces this).
- Explicit context passing.  The current span rides a ``contextvars``
  ContextVar per thread; code that hops threads (the webhook
  micro-batcher) captures the span object explicitly and re-establishes
  it on the far side with ``use_span``.
- Batch linkage.  One micro-batched TPU dispatch serves N admission
  requests.  The batch runs under its own (non-exported) trace whose
  root span carries ``links`` to the N request spans; every span of the
  batch trace is MIRRORED into each linked request trace on finish, so a
  request trace is self-contained — its stage spans (queue-wait, pack,
  cache lookup, dispatch, render) are all present and disjoint in time,
  which is what lets their durations sum to the request total.
- Bounded retention.  Completed exported traces land in a ring buffer
  (``/debug/traces`` serves it); any trace slower than the configured
  threshold is ALSO logged with its full stage breakdown (the slow-trace
  sampler).  With the default configuration the only per-span costs are
  a few attribute writes and one deque append per trace.

Stage names are stable strings (the ``stage`` attribute): ``queue_wait``,
``cache_lookup``, ``pack``, ``compile``, ``dispatch``, ``fetch``,
``render``, ``inventory``, ``status_write``.  docs/tracing.md documents
the model.
"""

from __future__ import annotations

import contextvars
import json
import logging
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

log = logging.getLogger("gatekeeper.obs")

# wall-clock anchor for rendering monotonic offsets as absolute time;
# captured once at import, never on a hot path
_WALL_ANCHOR = time.time()  # wall-clock: ok (import-time anchor)
_PERF_ANCHOR = time.perf_counter()

# stable stage names (see module docstring)
QUEUE_WAIT = "queue_wait"
CACHE_LOOKUP = "cache_lookup"
PACK = "pack"
COMPILE = "compile"
DISPATCH = "dispatch"
FETCH = "fetch"
RENDER = "render"
INVENTORY = "inventory"
STATUS_WRITE = "status_write"

_TRACEPARENT_VERSION = "00"


def wall_time(perf_t: float) -> float:
    """Absolute (epoch) time of a perf_counter reading, via the anchor."""
    return _WALL_ANCHOR + (perf_t - _PERF_ANCHOR)


def _new_trace_id() -> str:
    return f"{random.getrandbits(128):032x}"


#: public alias for producers that mint a trace id WITHOUT building a
#: trace — the event-loop edge stamps X-GK-Trace-Id on head-unsampled
#: requests from this, skipping Span/Trace allocation entirely
new_trace_id = _new_trace_id


# span ids only need process-local uniqueness (trace ids carry the global
# entropy); a counter is ~3x cheaper than getrandbits+format per span
_SPAN_SEQ = __import__("itertools").count(1)


def _new_span_id() -> str:
    return f"{next(_SPAN_SEQ):016x}"


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """W3C traceparent -> (trace_id, parent_span_id), or None when the
    header is absent/malformed.  Only version 00 fields are consumed;
    unknown versions still yield ids when the field shapes line up
    (forward compatibility, per the spec)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1], parts[2]
    # W3C: version is exactly two lowercase hex digits and never "ff";
    # unknown (higher) versions still yield ids when the field shapes
    # line up — that is the spec's forward-compatibility rule
    if len(version) != 2 or version == "ff":
        return None
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(version, 16)
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    if version != version.lower() or trace_id != trace_id.lower() \
            or span_id != span_id.lower():
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"{_TRACEPARENT_VERSION}-{trace_id}-{span_id}-01"


class Trace:
    """One trace: a trace_id plus the finished-span records that belong
    to it.  ``mirrors`` receive a copy of every finished span record
    (the batch-trace -> request-trace fan-out)."""

    __slots__ = (
        "trace_id", "spans", "mirrors", "export", "root", "root_record",
        "remote_parent",
    )

    def __init__(self, trace_id: Optional[str] = None, export: bool = True,
                 remote_parent: Optional[str] = None):
        self.trace_id = trace_id or _new_trace_id()
        self.spans: List[dict] = []  # finished span records, end order
        self.mirrors: List["Trace"] = []
        self.export = export
        self.root: Optional["Span"] = None
        self.root_record: Optional[dict] = None
        self.remote_parent = remote_parent

    def to_dict(self) -> dict:
        # the root is tracked explicitly: mirrored batch records may append
        # after the root ended, so "last span" is not a root identity
        root = self.root_record or (self.spans[-1] if self.spans else {})
        return {
            "trace_id": self.trace_id,
            "root": root.get("name", ""),
            "start_ts": round(wall_time(root.get("start", _PERF_ANCHOR)), 6),
            "duration_ms": root.get("duration_ms", 0.0),
            "remote_parent": self.remote_parent,
            "spans": list(self.spans),
        }


def _finished_record(name: str, trace: "Trace", span_id: str,
                     parent_id: Optional[str], start: float, stop: float,
                     attrs: dict) -> dict:
    """The one shape of a finished span's record (keys, rounding): what
    Span.record and the stage clock's ring sink both file."""
    rec = {
        "name": name,
        "trace_id": trace.trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
        "start": start,
        "duration_ms": round((stop - start) * 1e3, 4),
    }
    if attrs:
        rec["attrs"] = attrs
    return rec


def _file_record(trace: "Trace", rec: dict) -> None:
    """A finished record into its trace and the trace's mirrors."""
    trace.spans.append(rec)
    for m in trace.mirrors:
        m.spans.append(rec)


class Span:
    """One timed operation.  Finish with ``end()`` (or use the tracer's
    context managers); a finished span becomes an immutable dict record
    on its trace (and the trace's mirrors)."""

    __slots__ = (
        "name", "trace", "span_id", "parent_id", "start", "stop",
        "attrs", "events", "links",
    )

    def __init__(self, name: str, trace: Trace,
                 parent_id: Optional[str] = None,
                 start: Optional[float] = None, **attrs):
        self.name = name
        self.trace = trace
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.start = time.perf_counter() if start is None else start
        self.stop: Optional[float] = None
        self.attrs: Dict[str, object] = attrs
        self.events: List[dict] = []
        self.links: List[Tuple[str, str]] = []

    def set_attrs(self, **attrs):
        self.attrs.update(attrs)

    def add_event(self, name: str, **attrs):
        self.events.append({
            "name": name,
            "offset_ms": round((time.perf_counter() - self.start) * 1e3, 3),
            **attrs,
        })

    def link(self, trace_id: str, span_id: str):
        self.links.append((trace_id, span_id))

    def record(self) -> dict:
        rec = _finished_record(
            self.name, self.trace, self.span_id, self.parent_id,
            self.start, self.stop or self.start, dict(self.attrs))
        if self.events:
            rec["events"] = list(self.events)
        if self.links:
            rec["links"] = [
                {"trace_id": t, "span_id": s} for t, s in self.links
            ]
        return rec

    def end(self, stop: Optional[float] = None):
        if self.stop is not None:
            return  # idempotent: double-end keeps the first timing
        self.stop = time.perf_counter() if stop is None else stop
        rec = self.record()
        tr = self.trace
        _file_record(tr, rec)
        if tr.root is self:
            tr.root_record = rec
            _TRACER.complete(tr)


# the per-thread (per-context) active span
CURRENT: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "gk_current_span", default=None
)

# Cross-thread mirror of CURRENT for the sampling profiler
# (obs/profiler.py): a sampler thread cannot read another thread's
# contextvars, so span (de)activation also writes this ident-keyed dict.
# GIL-atomic dict ops only — no lock on the span hot path.
_ACTIVE_BY_THREAD: Dict[int, Span] = {}


def _thread_activate(span: Optional[Span]) -> Optional[Span]:
    ident = threading.get_ident()
    prev = _ACTIVE_BY_THREAD.get(ident)
    if span is None:
        _ACTIVE_BY_THREAD.pop(ident, None)
    else:
        _ACTIVE_BY_THREAD[ident] = span
    return prev


def _thread_restore(prev: Optional[Span]) -> None:
    ident = threading.get_ident()
    if prev is None:
        _ACTIVE_BY_THREAD.pop(ident, None)
    else:
        _ACTIVE_BY_THREAD[ident] = prev


def active_spans() -> Dict[int, Span]:
    """Snapshot of {thread_ident: active span} — the profiler's stage-
    correlation input.  A copy: the sampler must never iterate the live
    dict while request threads mutate it."""
    return dict(_ACTIVE_BY_THREAD)


def activate(span: Span):
    """Establish ``span`` as CURRENT for this thread (contextvar AND the
    profiler's thread registry) without a context manager — for code
    that brackets activation across non-lexical scopes (the micro-
    batcher's dispatch loop).  Returns an opaque state for
    :func:`deactivate`."""
    token = CURRENT.set(span)
    prev = _thread_activate(span)
    return (token, prev)


def deactivate(state) -> None:
    token, prev = state
    CURRENT.reset(token)
    _thread_restore(prev)


class Tracer:
    """Process tracer: ring buffer of completed traces + slow sampler."""

    def __init__(self, buffer_size: int = 256,
                 slow_threshold_s: float = 0.25,
                 sample_rate: float = 1.0):
        self._lock = threading.Lock()
        self.configure(buffer_size, slow_threshold_s, sample_rate)

    def configure(self, buffer_size: Optional[int] = None,
                  slow_threshold_s: Optional[float] = None,
                  sample_rate: Optional[float] = None):
        with self._lock:
            if buffer_size is not None:
                self._ring: deque = deque(maxlen=max(int(buffer_size), 1))
            if slow_threshold_s is not None:
                self.slow_threshold_s = float(slow_threshold_s)
            if sample_rate is not None:
                self.sample_rate = min(max(float(sample_rate), 0.0), 1.0)

    def sampled(self) -> bool:
        """Head-sampling decision for high-rate span producers (the
        event-loop edge): decide ONCE at request origination whether
        this trace would be retained, so an un-sampled request skips
        span allocation entirely instead of paying the full per-span
        cost and being dropped at completion anyway.  The trade: the
        slow-trace tail criterion only sees head-sampled requests on
        such producers — at sample_rate 1.0 (the default) nothing
        changes and every trace still completes through the ring."""
        r = self.sample_rate
        return r >= 1.0 or (r > 0.0 and random.random() < r)

    # ---- completion --------------------------------------------------------

    def complete(self, trace: Trace):
        if not trace.export:
            return
        # the explicit root record, never spans[-1]: a mirrored batch
        # record appended concurrently from another thread could
        # otherwise be mistaken for the root
        root = trace.root_record
        dur_s = (root["duration_ms"] / 1e3) if root else 0.0
        slow = (
            self.slow_threshold_s > 0 and dur_s >= self.slow_threshold_s
        )
        if slow or self.sample_rate >= 1.0 or (
            self.sample_rate > 0.0 and random.random() < self.sample_rate
        ):
            with self._lock:
                self._ring.append(trace)
        if slow:
            try:
                log.warning(
                    "slow trace %s (%s, %.1fms >= %.0fms threshold)",
                    trace.trace_id,
                    root.get("name", "?") if root else "?",
                    dur_s * 1e3, self.slow_threshold_s * 1e3,
                    extra={"kv": {
                        "event_type": "slow_trace",
                        "trace_id": trace.trace_id,
                        "duration_ms": root["duration_ms"] if root else 0.0,
                        "stages": stage_breakdown(trace.to_dict()),
                    }},
                )
            except Exception:  # sampling must never break the request
                log.exception("slow-trace sampler failed")

    # ---- retrieval ---------------------------------------------------------

    def traces(self, min_ms: float = 0.0,
               limit: Optional[int] = None) -> List[dict]:
        """Completed traces, newest first, optionally filtered by root
        duration (the ``/debug/traces?min_ms=`` contract)."""
        with self._lock:
            snap = list(self._ring)
        out = []
        for tr in reversed(snap):
            d = tr.to_dict()
            if d["duration_ms"] >= min_ms:
                out.append(d)
            if limit is not None and len(out) >= limit:
                break
        return out

    def clear(self):
        with self._lock:
            self._ring.clear()


_TRACER = Tracer(
    buffer_size=int(os.environ.get("GK_TRACE_BUFFER", "256")),
    slow_threshold_s=float(os.environ.get("GK_SLOW_TRACE_MS", "250")) / 1e3,
    sample_rate=float(os.environ.get("GK_TRACE_SAMPLE", "1.0")),
)


def get_tracer() -> Tracer:
    return _TRACER


def configure(buffer_size: Optional[int] = None,
              slow_threshold_s: Optional[float] = None,
              sample_rate: Optional[float] = None):
    _TRACER.configure(buffer_size, slow_threshold_s, sample_rate)


def stage_breakdown(trace_dict: dict) -> Dict[str, float]:
    """{stage: total_ms} over a trace's stage-tagged spans (disjoint by
    construction, so the values sum toward the root duration)."""
    out: Dict[str, float] = {}
    for s in trace_dict.get("spans", ()):
        stage = (s.get("attrs") or {}).get("stage")
        if stage:
            out[stage] = round(out.get(stage, 0.0) + s["duration_ms"], 4)
    return out


# ---- context helpers --------------------------------------------------------


def current_span() -> Optional[Span]:
    return CURRENT.get()


def current_trace_id() -> Optional[str]:
    sp = CURRENT.get()
    return sp.trace.trace_id if sp is not None else None


def set_attrs(**attrs):
    """Attach attributes to the active span (no-op without one)."""
    sp = CURRENT.get()
    if sp is not None:
        sp.attrs.update(attrs)


def add_event(name: str, **attrs):
    """Record a point-in-time event on the active span (no-op without
    one) — e.g. the fault plane stamping where an injected fault landed."""
    sp = CURRENT.get()
    if sp is not None:
        sp.add_event(name, **attrs)


class _SpanCtx:
    """Context manager for one span; establishes it as CURRENT inside."""

    __slots__ = ("span", "_token", "_prev_active")

    def __init__(self, span: Span):
        self.span = span
        self._token = None
        self._prev_active = None

    def __enter__(self) -> Span:
        self._token = CURRENT.set(self.span)
        self._prev_active = _thread_activate(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            self.span.attrs.setdefault("error", repr(exc))
        CURRENT.reset(self._token)
        _thread_restore(self._prev_active)
        self.span.end()
        return False


def root_span(name: str, traceparent: Optional[str] = None,
              start: Optional[float] = None, **attrs) -> _SpanCtx:
    """Start a new exported trace rooted at this span.  ``traceparent``
    (the W3C header value) adopts the caller's trace id so the deny log
    line and /debug/traces entry correlate with the upstream trace.
    ``start`` backdates the root to an already-measured perf_counter
    anchor (the front door's accept time), so child stage spans recorded
    against that anchor stay inside the root duration."""
    parent = parse_traceparent(traceparent)
    if parent is not None:
        tr = Trace(trace_id=parent[0], remote_parent=parent[1])
        sp = Span(name, tr, parent_id=parent[1], start=start, **attrs)
    else:
        tr = Trace()
        sp = Span(name, tr, start=start, **attrs)
    # fleet identity on every root span: /debug/traces entries from N
    # replicas merged by an aggregator stay attributable (docs/fleet.md)
    from ..util import replica_id

    rid = replica_id()
    if rid:
        sp.attrs.setdefault("replica_id", rid)
    tr.root = sp
    return _SpanCtx(sp)


class _NoopSpan:
    """Inert span for un-traced callers: every method swallows its
    arguments.  One shared instance — the no-active-trace path allocates
    NOTHING, which is what keeps callers outside a trace (bench's direct
    handler drive, embedders) at ~zero cost."""

    __slots__ = ()

    def set_attrs(self, **attrs):
        pass

    def add_event(self, name: str, **attrs):
        pass

    def link(self, trace_id: str, span_id: str):
        pass

    def end(self, stop: Optional[float] = None):
        pass


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return _NOOP_SPAN

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP_SPAN = _NoopSpan()
_NOOP_CTX = _NoopCtx()


def span(name: str, stage: Optional[str] = None, **attrs):
    """Child span of the current span.  Without an active span this is
    the shared no-op context — one ContextVar read and nothing else."""
    cur = CURRENT.get()
    if cur is None:
        return _NOOP_CTX
    sp = Span(name, cur.trace, parent_id=cur.span_id, **attrs)
    if stage:
        sp.attrs["stage"] = stage
    return _SpanCtx(sp)


class _UseCtx:
    """Context manager that re-establishes an explicitly-passed span as
    CURRENT without ending it on exit (cross-thread context passing —
    e.g. the batcher's per-request fallback evaluating under each
    request's own span)."""

    __slots__ = ("_span", "_token", "_prev_active")

    def __init__(self, sp: Span):
        self._span = sp
        self._token = None
        self._prev_active = None

    def __enter__(self) -> Span:
        self._token = CURRENT.set(self._span)
        self._prev_active = _thread_activate(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        CURRENT.reset(self._token)
        _thread_restore(self._prev_active)
        return False


def use_span(sp: Span) -> _UseCtx:
    return _UseCtx(sp)


def detached_span(name: str, parent: Optional[Span] = None,
                  start: Optional[float] = None, **attrs) -> Span:
    """A span NOT established as CURRENT — for callers that hold it
    across threads or end it from another place (the batcher's
    queue-wait span).  Parent defaults to the current span."""
    cur = parent if parent is not None else CURRENT.get()
    if cur is not None:
        return Span(name, cur.trace, parent_id=cur.span_id, start=start,
                    **attrs)
    return Span(name, Trace(export=False), start=start, **attrs)


def batch_span(name: str, link_spans: List[Span], **attrs) -> Span:
    """Root span of a batch trace serving N request spans: linked to each
    request span, and every span of the batch trace mirrors into each
    linked request trace (self-contained request traces).  The batch
    trace itself is never exported — the mirrors are its output."""
    tr = Trace(export=False)
    seen = set()
    for rs in link_spans:
        if rs is None or not rs.trace.export:
            continue
        if id(rs.trace) not in seen:
            seen.add(id(rs.trace))
            tr.mirrors.append(rs.trace)
    sp = Span(name, tr, **attrs)
    tr.root = sp
    for rs in link_spans:
        if rs is not None:
            sp.link(rs.trace.trace_id, rs.span_id)
    sp.attrs.setdefault("batch_size", len(link_spans))
    return sp


def record_span(name: str, start: float, stop: float,
                stage: Optional[str] = None, **attrs):
    """Record an already-measured interval as a finished span under the
    current span (no-op cost without one).  For code that has its own
    perf_counter bracketing (the driver's sweep stats)."""
    cur = CURRENT.get()
    if cur is None:
        return None
    sp = Span(name, cur.trace, parent_id=cur.span_id, start=start, **attrs)
    if stage:
        sp.attrs["stage"] = stage
    sp.end(stop=stop)
    return sp


# ---- stage clock -------------------------------------------------------------
# One contiguous stopwatch per (thread, path): ``mark(stage)`` closes the
# open interval and opens the next, so a thread's stages are adjacent and
# sum to first-mark -> stop exactly.  Every closed interval goes to three
# sinks from the one call: always-on counters (host_stage_*_total,
# accumulated on the clock and flushed per sweep or, by the loops, at
# most every FLUSH_S), the profiler's own clock (a jax.profiler.TraceAnnotation held
# open for the stage, so it lands on the host plane of the same
# .xplane.pb as the device ops), and the ring (record_span under
# CURRENT).  Paths and stages are stable strings; a stage called
# ``wait`` is idle with the GIL released, every other one is busy
# (busy is wall time: under contention it holds the wait for the GIL).
# docs/tracing.md ("Stage clock") is the contract.

PATH_WIRE = "wire"
PATH_BATCH = "batch"
PATH_AUDIT = "audit"
WAIT = "wait"
QUEUED = "queued"   # wire only: a chunk's delay in the worker hand-off

# The fourth path's unit is a review, not a thread: one row per admission
# review answered through the batch lane, adjacent stages from the loop
# thread's wake-up for the recv that completed its request frame to the
# return of the write() of its response frame, built from the instants
# the three thread clocks already take (docs/tracing.md "The review
# path").  Counters only: no annotation, no ring record.
PATH_REVIEW = "review"
REVIEW_STAGES = (
    "frame", "queued", "decode", "prepare", "batch_queue", "batch_pre",
    "dispatch", "render", "batch_post", "wake", "finalize", "encode",
    "handoff", "write",
)
# the batcher's turn, drain -> a member's event.set, is tiled by the
# marks of its clock; each interval goes to the group of the stage that
# was open in it, and a stage named nowhere here to batch_post
REVIEW_BATCH_GROUPS = {
    "collect": "batch_pre", "route": "batch_pre", "pack": "batch_pre",
    "enqueue": "dispatch", "device_wait": "dispatch", "fetch": "dispatch",
    "join_lookup": "render", "render": "render",
}

# thread ident -> the innermost running clock: where the collector's
# hook books a pause (GIL-atomic dict ops, like _ACTIVE_BY_THREAD)
_RUNNING_CLOCKS: Dict[int, "StageClock"] = {}
_CLOCKS = threading.local()  # path -> this thread's StageClock

# the annotation sink: jax.profiler.TraceAnnotation when jax is ALREADY
# imported in the process, else absent.  Never imported for this: the
# door and the benchmark's parent stay jax-free.
_ANNOTATION = None
_STAGE_NAMES: Dict[tuple, str] = {}   # (path, stage) -> span name


def _bind_annotation() -> None:
    global _ANNOTATION
    _ANNOTATION = getattr(sys.modules.get("jax.profiler"),
                          "TraceAnnotation", None)


class StageClock:
    """Contiguous stage stopwatch of one thread on one path (see the
    section comment above).  ``mark(stage)`` names the interval it
    OPENS."""

    __slots__ = ("path", "stage", "t", "totals", "gc_full_s", "_ann",
                 "_outer", "_flushed", "_lapped", "_gc_full_lapped",
                 "flushed_at", "_lap")

    def __init__(self, path: str, start: Optional[float] = None):
        self.path = path
        self.stage: Optional[str] = None   # open stage; None = stopped
        self.t = time.perf_counter() if start is None else start
        # stage -> [seconds, calls, collector seconds], cumulative
        self.totals: Dict[str, list] = {}
        self.gc_full_s = 0.0   # generation-2 pauses inside open stages
        self._ann = None
        self._outer: Optional["StageClock"] = None
        self._flushed: Optional[Dict[str, tuple]] = None
        self._lapped: Optional[Dict[str, tuple]] = None
        self._gc_full_lapped = 0.0
        self._lap: Optional[list] = None   # begin_lap()'s list, if kept
        self.flushed_at = self.t   # perf_counter of the last flush
        if _ANNOTATION is None and "jax.profiler" in sys.modules:
            _bind_annotation()
        if not _GC_HOOKED:
            _install_gc_hook()

    def _name(self, stage: str) -> str:
        """Ring + annotation name: gk.<path>.<stage>."""
        key = (self.path, stage)
        name = _STAGE_NAMES.get(key)
        if name is None:
            name = _STAGE_NAMES[key] = f"gk.{self.path}.{stage}"
        return name

    def _account(self, stage: str, seconds: float) -> None:
        acc = self.totals.get(stage)
        if acc is None:
            acc = self.totals[stage] = [0.0, 0, 0.0]
        acc[0] += seconds
        acc[1] += 1

    def _close(self, stage: str, now: float) -> None:
        self._account(stage, now - self.t)
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        cur = CURRENT.get()
        if cur is not None:
            # the ring sink: the finished record filed as it is (no
            # Span object on the hot threads)
            _file_record(cur.trace, _finished_record(
                self._name(stage), cur.trace, _new_span_id(), cur.span_id,
                self.t, now, {}))

    def mark(self, stage: str) -> float:
        """Close the open stage (if any) and open ``stage`` at *now*."""
        now = time.perf_counter()
        opened = self.stage
        if opened is not None:
            self._close(opened, now)
        self.stage = stage
        self.t = now
        if self._lap is not None:
            self._lap.append((stage, now))
        if opened is None:
            # onto the collector's map only with a stage open
            ident = threading.get_ident()
            self._outer = _RUNNING_CLOCKS.get(ident)
            _RUNNING_CLOCKS[ident] = self
        ann = _ANNOTATION
        if ann is not None and ann.is_enabled():
            self._ann = ann(self._name(stage))
        return now

    def add(self, stage: str, seconds: float) -> None:
        """Book an interval measured elsewhere (a hand-off queue's delay
        before this thread took the work) under ``stage``: counters
        only, and no part of the thread's own contiguous time."""
        self._account(stage, seconds)

    def begin_lap(self) -> list:
        """From now on keep the ``(stage, instant)`` pair of every mark
        in ONE new list, seeded with the stage open now, and return it:
        the batcher hands a turn's list to every member of its batch,
        shared and not copied.  The next begin_lap() starts another;
        stop() ends the keeping.  On a stopped clock: nothing kept."""
        if self.stage is None:
            self._lap = None
            return []
        self._lap = lap = [(self.stage, time.perf_counter())]
        return lap

    def add_timeline(self, row: list, end: float) -> None:
        """Book one review's row (path ``review``): ``row`` is
        ``[(stage, opened_at), ...]`` in time order and tiles
        ``[row[0][1], end]``, so a stage's seconds are the sum of the
        intervals under its name and all of them sum to the span
        exactly.  A stage of zero seconds did not happen and counts no
        call; the last one (``write``) always counts: its calls are the
        reviews booked.  Generation-2 pauses that began inside an
        interval go to its stage's collector seconds (no instant is
        taken during a collection, so an interval holds a pause whole
        or not at all).  Counters only, as add() is."""
        sums: Dict[str, float] = {}
        begin = row[0][1]
        stage, t = row[0]
        for nxt, t_nxt in row[1:]:
            sums[stage] = sums.get(stage, 0.0) + (t_nxt - t)
            stage, t = nxt, t_nxt
        sums[stage] = sums.get(stage, 0.0) + (end - t)
        totals = self.totals
        for name, seconds in sums.items():
            if seconds > 0.0 or name == stage:
                acc = totals.get(name)
                if acc is None:
                    acc = totals[name] = [0.0, 0, 0.0]
                acc[0] += seconds
                acc[1] += 1
        if _GC_FULL_LAST_STOP[0] <= begin:
            return   # the common case: no full collection since it began
        for start, stop in _GC_FULL_RECENT:
            if begin <= start < end:
                name = row[0][0]
                for nxt, t_nxt in row:
                    if t_nxt > start:
                        break
                    name = nxt
                totals[name][2] += stop - start

    def stop(self) -> float:
        """Close the open stage; the clock is stopped until the next
        mark (time until then belongs to no stage)."""
        now = time.perf_counter()
        if self.stage is not None:
            self._close(self.stage, now)
            # off the collector's map first: a collection between the
            # two lines must not find a clock with no open stage
            ident = threading.get_ident()
            if self._outer is not None:
                _RUNNING_CLOCKS[ident] = self._outer
                self._outer = None
            else:
                _RUNNING_CLOCKS.pop(ident, None)
            self.stage = None
            self._lap = None
        return now

    def _since(self, seen: Dict[str, tuple]) -> Dict[str, tuple]:
        out = {}
        for stage, acc in self.totals.items():
            cur = (acc[0], acc[1], acc[2])
            old = seen.get(stage)
            if old != cur:
                o = old or (0.0, 0, 0.0)
                out[stage] = (cur[0] - o[0], cur[1] - o[1], cur[2] - o[2])
                seen[stage] = cur
        return out

    def gc_full_unlapsed(self) -> float:
        """Generation-2 pause seconds inside this clock's stages since
        the previous lapse(): what the sweep about to end has seen."""
        return self.gc_full_s - self._gc_full_lapped

    def lapse(self) -> Tuple[Dict[str, tuple], float]:
        """({stage: (seconds, calls, collector seconds)}, generation-2
        pause seconds) closed since the previous lapse() — one sweep's
        share of the clock."""
        if self._lapped is None:
            self._lapped = {}
        gc_full = self.gc_full_s - self._gc_full_lapped
        self._gc_full_lapped = self.gc_full_s
        return self._since(self._lapped), gc_full

    FLUSH_S = 0.25   # flush_due's gate (the wire telemetry's cadence)

    def flush_due(self, now: float) -> None:
        """flush(), at most every FLUSH_S: for the loops that turn
        hundreds of times a second (batcher, wire workers, add_data)."""
        if now - self.flushed_at >= self.FLUSH_S:
            self.flush()

    def flush(self) -> None:
        """Push what closed since the last flush to the counters
        (host_stage_seconds_total / _calls_total / _gc_seconds_total):
        per sweep, or per loop turn / chunk through flush_due — never
        per mark."""
        if self._flushed is None:
            self._flushed = {}
        self.flushed_at = time.perf_counter()
        rows = self._since(self._flushed)
        if rows:
            from ..metrics.catalog import record_host_stages

            record_host_stages(self.path, rows)


class _NoopClock:
    """The clock of a thread nobody is timing: marks cost one
    perf_counter read (callers use the returned instants)."""

    __slots__ = ()
    path = ""
    stage = None

    def mark(self, stage: str) -> float:
        return time.perf_counter()

    def stop(self) -> float:
        return time.perf_counter()

    def add(self, stage: str, seconds: float) -> None:
        pass

    def begin_lap(self) -> tuple:
        return ()

    def lapse(self) -> tuple:
        return {}, 0.0

    def flush(self) -> None:
        pass

    def flush_due(self, now: float) -> None:
        pass


NOOP_CLOCK = _NoopClock()


def stage_clock(path: str) -> StageClock:
    """This thread's clock for ``path`` (made on first use)."""
    clock = _CLOCKS.__dict__.get(path)
    if clock is None:
        clock = _CLOCKS.__dict__[path] = StageClock(path)
    return clock


def running_clock(path: str):
    """This thread's clock for ``path`` if a stage is open on it, else
    the no-op clock: the driver marks stages on whatever clock its
    caller (batcher loop, wire worker, Client) started, and on nothing
    when called bare."""
    clock = _CLOCKS.__dict__.get(path)
    if clock is None or clock.stage is None:
        return NOOP_CLOCK
    return clock


# ---- the collector -----------------------------------------------------------
# One gc.callbacks hook, installed the first time a StageClock is made in
# the process.  A pause is booked per generation, and to the stage open
# on the thread that collected (or to path "gc", stage "background" —
# the replica's webhook-gc thread); plain adds under the GIL, pushed to
# the registry by collect_hook at scrape time.

GC_PATH = "gc"
GC_BACKGROUND = "background"
_GC_HOOKED = False
_GC_PAUSE_S = [0.0, 0.0, 0.0]
_GC_RUNS = [0, 0, 0]
_GC_BACKGROUND_S = [0.0]
_GC_OPEN: list = []   # (start, annotation) of the collection in progress
# the last few generation-2 pauses as (start, stop), for the review
# path's booking (StageClock.add_timeline): a fixed-length ring and the
# newest stop, plain stores under the GIL
_GC_FULL_RECENT: list = [(0.0, 0.0)] * 8
_GC_FULL_LAST_STOP = [0.0]
_GC_PUSHED = {"pause": [0.0, 0.0, 0.0], "runs": [0, 0, 0], "bg": 0.0,
              "cpu": 0.0, "heap": 0}
_GC_PUSH_LOCK = threading.Lock()   # two scrapes must not push one growth


def _gc_callback(phase: str, info: dict) -> None:
    gen = info.get("generation", 2)
    if phase == "start":
        ann = None
        cls = _ANNOTATION
        if gen == 2 and cls is not None and cls.is_enabled():
            ann = cls("gk.gc.gen2")
        _GC_OPEN.append((time.perf_counter(), ann))
        return
    if not _GC_OPEN:
        return
    t0, ann = _GC_OPEN.pop()
    pause = time.perf_counter() - t0
    if ann is not None:
        ann.__exit__(None, None, None)
    _GC_PAUSE_S[gen] += pause
    if gen == 2:
        _GC_FULL_RECENT[_GC_RUNS[2] % len(_GC_FULL_RECENT)] = (t0, t0 + pause)
        _GC_FULL_LAST_STOP[0] = t0 + pause
    _GC_RUNS[gen] += 1
    clock = _RUNNING_CLOCKS.get(threading.get_ident())
    if clock is None:
        _GC_BACKGROUND_S[0] += pause
        return
    acc = clock.totals.get(clock.stage)
    if acc is None:
        acc = clock.totals[clock.stage] = [0.0, 0, 0.0]
    acc[2] += pause
    if gen == 2:
        clock.gc_full_s += pause


def _install_gc_hook() -> None:
    global _GC_HOOKED
    if not _GC_HOOKED:
        _GC_HOOKED = True
        import gc

        gc.callbacks.append(_gc_callback)


def collect_hook(registry=None) -> None:
    """Scrape-time push (MetricsExporter collect hook): the collector's
    pauses and collections per generation, the background share,
    process_cpu_seconds_total, and what the sweeps' heap discipline did
    (util/heap.py) — read when asked, no refresher thread."""
    from ..metrics.catalog import record_heap, record_process_counters
    from ..util import heap

    pushed = _GC_PUSHED
    with _GC_PUSH_LOCK:
        pause = [_GC_PAUSE_S[g] - pushed["pause"][g] for g in range(3)]
        runs = [_GC_RUNS[g] - pushed["runs"][g] for g in range(3)]
        bg = _GC_BACKGROUND_S[0] - pushed["bg"]
        cpu = time.process_time()
        record_process_counters(pause, runs, bg, cpu - pushed["cpu"])
        for g in range(3):
            pushed["pause"][g] += pause[g]
            pushed["runs"][g] += runs[g]
        pushed["bg"] += bg
        pushed["cpu"] = cpu
        collections, frozen = heap.counters()
        if collections:   # engaged at least once
            record_heap(collections - pushed["heap"], frozen)
            pushed["heap"] = collections


def dump_stacks() -> dict:
    """Thread-stack snapshot for /debug/stacks: every live thread's name,
    ident, daemon flag, and current frames — the hang-diagnosis view the
    fault plane's hang mode needs."""
    import traceback

    frames = sys._current_frames()
    threads = []
    for t in threading.enumerate():
        frame = frames.get(t.ident)
        stack = traceback.format_stack(frame) if frame is not None else []
        threads.append({
            "name": t.name,
            "ident": t.ident,
            "daemon": t.daemon,
            "alive": t.is_alive(),
            "stack": [ln.rstrip() for ln in stack],
        })
    return {"thread_count": len(threads), "threads": threads}


def traces_json(min_ms: float = 0.0, limit: Optional[int] = None) -> str:
    return json.dumps({"traces": _TRACER.traces(min_ms=min_ms, limit=limit)})
