"""The front door's control plane (docs/fleet.md): which backends
exist, which one takes the next request, and which are out of rotation.

:class:`Roster` owns the balancing and ejection policy and holds no
client socket, so it is testable without one; the event door
(fleet/evdoor.py) holds a Roster and keeps the data plane.

Choice — ``choose()`` picks AND RESERVES a backend by

- ``round_robin`` — strict rotation, or
- ``least_inflight`` (default) — the backend with the fewest requests
  currently in flight, ties broken by rotation order; under mixed
  request costs this tracks per-replica service speed without any
  backend-side signal.

Overload (docs/failure-modes.md overload section):

- **bounded inflight + fast shed** — with ``max_inflight`` set, the
  slot is taken under the backend's lock, and a request arriving while
  every live backend sits at its bound raises ``OverloadShed`` (the door
  answers a single-digit-ms **429 + Retry-After**) instead of queueing
  into a socket: congestive collapse is queues, and the door refuses to
  build one.
- **retry budget** — the door's bounded single retry is additionally
  gated on a process-wide token bucket (:class:`RetryBudget`), so
  retries cannot amplify a brownout into a storm.

Resilience (docs/failure-modes.md fleet failure matrix):

- **health-based ejection** — a connection-REFUSED backend (nothing
  listening: the replica is dead) is ejected immediately; other
  failures eject after ``EJECT_ERROR_STREAK`` consecutive errors.
  Ejected backends take no traffic.
- **probing readmission** — a background prober GETs each ejected
  backend's ``/readyz`` on a short cadence and readmits on the first
  success, so a restarted replica rejoins without operator action.
  ``/readyz`` (not ``/healthz``): a DRAINING replica keeps ``/healthz``
  at 200 by design but reports ``/readyz`` 503 — probing liveness would
  readmit a suspended backend mid-drain and route admissions into its
  503s.
- **backend swap** — ``set_backend(replica_id, host, port,
  probe_port)`` re-points a named backend (the supervisor calls it
  after restarting a replica on fresh ephemeral ports) and readmits it;
  ``suspend(replica_id)`` ejects administratively (the drain step of a
  rolling restart).

Per-backend served/error/inflight/ejected counters — plus a decaying
p50/p99 latency window per backend, so ejection decisions are
explainable without scraping traces — are exposed via :meth:`Roster.stats`
(the door's ``/fleetz``).
"""

from __future__ import annotations

import http.client
import itertools
import logging
import threading
import time
from collections import deque
from typing import List, Optional, Sequence, Set, Tuple

from .. import deadline as _deadline
from .. import logging as gklog
from ..metrics.catalog import record_retry_budget, record_retry_denied
from ..util import join_thread

log = gklog.get("fleet.roster")

ROUND_ROBIN = "round_robin"
LEAST_INFLIGHT = "least_inflight"


class RetryBudget:
    """Token-bucket retry budget (ISSUE 12): the door's bounded retry is
    additionally gated on a PROCESS-WIDE bucket, so per-request retries
    cannot multiply offered load during a brownout — the classic retry
    storm.  Refills at `rate_per_s` up to `cap`; each retry takes one
    token; an empty bucket denies the retry (the request proceeds to the
    explicit 502, it does not wait for tokens)."""

    def __init__(self, cap: float = 10.0, rate_per_s: float = 1.0):
        self.cap = float(cap)
        self.rate_per_s = float(rate_per_s)
        self._tokens = float(cap)
        self._t = time.monotonic()
        self._lock = threading.Lock()
        self.denied = 0

    def _refill_locked(self, now: float):
        self._tokens = min(
            self.cap, self._tokens + (now - self._t) * self.rate_per_s
        )
        self._t = now

    def take(self) -> bool:
        now = time.monotonic()
        with self._lock:
            self._refill_locked(now)
            if self._tokens >= 1.0:
                self._tokens -= 1.0
                granted = True
            else:
                self.denied += 1
                granted = False
            tokens = self._tokens
        record_retry_budget(tokens)
        if not granted:
            record_retry_denied()
        return granted

    def tokens(self) -> float:
        now = time.monotonic()
        with self._lock:
            self._refill_locked(now)
            return self._tokens


class Backend:
    # decaying latency window (satellite: /fleetz explainability):
    # bounded samples, summarized over the trailing LATENCY_WINDOW_S
    LATENCY_SAMPLES = 1024

    __slots__ = ("host", "port", "probe_port", "replica_id", "inflight",
                 "served", "errors", "consecutive_errors", "ejected",
                 "ejected_at", "readmissions", "lock", "lat")

    def __init__(self, host: str, port: int, replica_id: str = "",
                 probe_port: int = 0):
        self.host = host
        self.port = int(port)
        # readmission probes GET /readyz over HTTP; the data port speaks
        # the wire protocol, so a backend names the replica's HTTP
        # listener here.  0 = probe the data port.
        self.probe_port = int(probe_port)
        self.replica_id = replica_id or f"{host}:{port}"
        self.inflight = 0
        self.served = 0
        self.errors = 0
        self.consecutive_errors = 0
        self.ejected = False
        self.ejected_at = 0.0
        self.readmissions = 0
        self.lock = threading.Lock()
        self.lat: deque = deque(maxlen=self.LATENCY_SAMPLES)  # (mono, ms)

    def latency_summary(self, window_s: float) -> dict:
        cutoff = time.monotonic() - window_s
        with self.lock:
            xs = sorted(ms for t, ms in self.lat if t >= cutoff)
        if not xs:
            return {"n": 0, "p50_ms": None, "p99_ms": None,
                    "window_s": window_s}
        def pct(q: float) -> float:
            return round(xs[min(int(q * len(xs)), len(xs) - 1)], 3)
        return {"n": len(xs), "p50_ms": pct(0.50), "p99_ms": pct(0.99),
                "window_s": window_s}


class Roster:
    # /healthz counts a backend live until it fails this many requests
    # in a row with no success in between
    LIVE_ERROR_STREAK = 3
    # non-refused failures eject after this many consecutive errors
    # (refused connections eject immediately: nothing is listening)
    EJECT_ERROR_STREAK = 3
    # readmission probe cadence for ejected backends
    PROBE_INTERVAL_S = 0.25
    PROBE_TIMEOUT_S = 2.0
    # stats() latency summaries decay over this trailing window
    LATENCY_WINDOW_S = 60.0

    def __init__(self, backends: Sequence[Tuple[str, int]] | Sequence[dict],
                 policy: str = LEAST_INFLIGHT, max_inflight: int = 0,
                 probe_interval_s: Optional[float] = None):
        if policy not in (ROUND_ROBIN, LEAST_INFLIGHT):
            raise ValueError(f"unknown front-door policy: {policy!r}")
        self.policy = policy
        # per-backend inflight bound; 0 = unbounded.  Past the bound on
        # every live backend, choose() sheds instead of queueing into a
        # socket
        self.max_inflight = int(max_inflight)
        self.probe_interval_s = (
            probe_interval_s if probe_interval_s is not None
            else self.PROBE_INTERVAL_S
        )
        self.backends: List[Backend] = []
        for b in backends:
            if isinstance(b, dict):
                self.backends.append(Backend(
                    b.get("host", "127.0.0.1"), b["port"],
                    b.get("replica_id", ""),
                    probe_port=b.get("probe_port", 0),
                ))
            else:
                host, bport = b
                self.backends.append(Backend(host, bport))
        if not self.backends:
            raise ValueError("front door needs at least one backend")
        self._rr = itertools.count()
        self._mu = threading.Lock()      # guards backend re-pointing
        # (replica_id, how) -> n since the last take_choices(): how a
        # choice fell, "least" (one backend had strictly the fewest in
        # flight) or "tie" (rotation decided).  The door drains it once
        # a reactor tick into frontdoor_choice_total; the hot path pays
        # a dict increment under the chosen backend's lock, as for the
        # request outcomes (one drainer: the door's loop thread)
        self._choices: dict = {}
        self._prober: Optional[threading.Thread] = None
        self._prober_stop = threading.Event()

    # ---- choice ----------------------------------------------------------

    def has_capacity(self) -> bool:
        """False when EVERY live backend sits at the inflight bound —
        the door-accept fast-path shed predicate.  Advisory (lock-free
        reads): the HARD bound is choose()'s per-backend reservation,
        which takes the slot under the backend's lock — this check just
        refuses the obvious case before any routing work.  With no
        bound configured, or with every backend ejected (the
        fail-static path owns that case), capacity is never the reason
        to refuse."""
        if not self.max_inflight:
            return True
        # the roster list is append-only during __init__, so lock-free
        # iteration is safe (the advisory inflight reads always were)
        live = [b for b in self.backends if not b.ejected]
        if not live:
            return True
        return any(b.inflight < self.max_inflight for b in live)

    def choose(self, exclude: Optional[Set[Backend]] = None
               ) -> Optional[Backend]:
        """Pick AND RESERVE a backend not in ``exclude`` (the ones this
        request already tried): the inflight slot is taken under the
        chosen backend's lock before this returns, so max_inflight
        holds under concurrent callers — no check-then-act window.  The
        caller owns the reservation and gives it back exactly once,
        through release(), served() or failed().  Raises OverloadShed
        when live backends exist but every one is at its bound (the
        caller answers the fast 429 — a saturated-but-healthy fleet must
        never be queued into); returns None only when nothing is
        choosable at all."""
        candidates = self.backends  # append-only after __init__; no copy
        if not exclude:
            # healthy-path fast lanes: reserve with no intermediate
            # list builds.  Fall through to the general path when
            # ejections or reservation races complicate the picture
            # (live-subset rotation fairness, fail-static probing).
            n = len(candidates)
            start = next(self._rr)
            if self.policy == ROUND_ROBIN:
                saw_ejected = False
                for k in range(n):
                    b = candidates[(start + k) % n]
                    if b.ejected:
                        saw_ejected = True
                        continue
                    with b.lock:
                        if (
                            self.max_inflight
                            and b.inflight >= self.max_inflight
                        ):
                            continue
                        b.inflight += 1
                    return b
                if not saw_ejected:
                    raise _deadline.OverloadShed(
                        "every live backend is at its inflight bound"
                    )
            else:
                # least-inflight: lock-free argmin over the rotation
                # (advisory reads, like the sort the general path
                # does), then a locked re-check on the winner only.
                # Starting the scan at the rotation point keeps ties
                # shared the way the stable sort did.
                best = None
                best_in = 0
                tied = False
                for k in range(n):
                    b = candidates[(start + k) % n]
                    if b.ejected:
                        continue
                    if best is None or b.inflight < best_in:
                        best = b
                        best_in = b.inflight
                        tied = False
                    elif b.inflight == best_in:
                        tied = True
                if best is not None:
                    with best.lock:
                        if not (
                            self.max_inflight
                            and best.inflight >= self.max_inflight
                        ):
                            best.inflight += 1
                            self._note_choice(best, tied)
                            return best
                # at-bound or all-ejected: the general path below owns
                # the shed/fail-static decision
        untried = [
            b for b in candidates if not exclude or b not in exclude
        ]
        live = [b for b in untried if not b.ejected]
        if live:
            start = next(self._rr) % len(live)
            ordered = live[start:] + live[:start]
            if self.policy != ROUND_ROBIN:
                # least inflight, rotation as tiebreak (stable sort
                # over the rotated order) so equal backends share
                ordered.sort(key=lambda b: b.inflight)
            for k, b in enumerate(ordered):
                with b.lock:
                    if (
                        self.max_inflight
                        and b.inflight >= self.max_inflight
                    ):
                        continue
                    if self.policy != ROUND_ROBIN:
                        nxt = ordered[k + 1:k + 2]
                        self._note_choice(
                            b, bool(nxt) and nxt[0].inflight <= b.inflight)
                    b.inflight += 1
                return b
            raise _deadline.OverloadShed(
                "every live backend is at its inflight bound"
            )
        # every untried backend is ejected: try one anyway
        # (fail-static) rather than 502ing while a backend may have
        # just come back — its success readmits it on the spot.  The
        # inflight bound deliberately does not apply here: with zero
        # live capacity the choice is between refusing everything and
        # probing the ejected set with real traffic
        if not untried:
            return None
        b = untried[next(self._rr) % len(untried)]
        with b.lock:
            b.inflight += 1
        return b

    def _note_choice(self, backend: Backend, tied: bool) -> None:
        key = (backend.replica_id, "tie" if tied else "least")
        self._choices[key] = self._choices.get(key, 0) + 1

    def take_choices(self) -> dict:
        """The least-inflight choices made since the last call, as
        {(replica_id, how): n}, and forget them (the door's tick flush:
        metrics/catalog.record_frontdoor_choices).  round_robin counts
        nothing: there rotation decides every choice by definition."""
        out, self._choices = self._choices, {}
        return out

    # ---- giving a reservation back -----------------------------------------

    def release(self, backend: Backend) -> None:
        """Give back a reservation that was never used or whose caller
        left (a denied retry, a client gone mid-flight): no error
        charge — the replica did nothing wrong."""
        with backend.lock:
            backend.inflight -= 1

    def served(self, backend: Backend, latencies_ms: Sequence[float],
               live: bool) -> None:
        """``backend`` answered ``len(latencies_ms)`` reserved requests:
        one lock hold for the whole chunk.  ``live`` (some answer was
        not a 503) readmits an ejected backend — the fail-static path
        proved it back; a 503 is a draining/not-ready replica answering
        honestly and must NOT re-enter rotation."""
        mono = time.monotonic()
        n = len(latencies_ms)
        with backend.lock:
            backend.inflight -= n
            backend.served += n
            backend.consecutive_errors = 0
            for ms in latencies_ms:
                backend.lat.append((mono, ms))
        if backend.ejected and live:
            self.readmit(backend, "served while ejected")

    def failed(self, backend: Backend, exc: Optional[BaseException],
               what: str = "") -> None:
        """A reserved attempt on ``backend`` failed: release the slot
        and charge the error streak.  Connection refused means nothing
        is listening — the replica is DEAD, not slow — so it ejects now
        instead of taxing the next streak's requests; anything else
        ejects at EJECT_ERROR_STREAK.  A deadline-induced timeout
        charges the streak too: one tight-budget expiry is forgiven by
        the next success, but a backend that times out every request in
        a row is indistinguishable from wedged (the /readyz prober
        readmits a healthy one within a probe interval)."""
        with backend.lock:
            backend.inflight -= 1
            backend.errors += 1
            backend.consecutive_errors += 1
            streak = backend.consecutive_errors
        if isinstance(exc, ConnectionRefusedError):
            self.eject(backend, "connection refused")
        elif streak >= self.EJECT_ERROR_STREAK:
            self.eject(backend, f"{streak} consecutive errors{what}")

    # ---- ejection / readmission ------------------------------------------

    def eject(self, backend: Backend, why: str):
        with backend.lock:
            if backend.ejected:
                return
            backend.ejected = True
            backend.ejected_at = time.monotonic()
        # log_event: the active wire trace id (when ejection happens on
        # a request path) is injected automatically, so wire logs join
        # replica logs on trace_id
        gklog.log_event(
            log, f"backend {backend.replica_id} ejected ({why}); probing "
            "for readmission", level=logging.WARNING,
            event_type="frontdoor_eject", backend=backend.replica_id,
            reason=why,
        )

    def readmit(self, backend: Backend, why: str):
        with backend.lock:
            if not backend.ejected:
                return
            backend.ejected = False
            backend.consecutive_errors = 0
            backend.readmissions += 1
        gklog.log_event(
            log, f"backend {backend.replica_id} readmitted ({why})",
            event_type="frontdoor_readmit", backend=backend.replica_id,
            reason=why,
        )

    def suspend(self, replica_id: str) -> bool:
        """Administrative ejection (the supervisor's drain/restart step):
        the backend takes no NEW traffic until set_backend or a probe
        readmits it.  The prober keeps running, so a suspend that was
        never followed by a swap self-heals once the replica answers."""
        b = self.find(replica_id)
        if b is None:
            return False
        self.eject(b, "suspended")
        return True

    def set_backend(self, replica_id: str, host: str, port: int,
                    probe_port: int = 0) -> bool:
        """Re-point a named backend (a supervised replica restarted on
        fresh ephemeral ports) and readmit it.  The door's connection to
        the old port dies on its next use and re-establishes against
        the new one."""
        b = self.find(replica_id)
        if b is None:
            return False
        with self._mu, b.lock:
            b.host = host
            b.port = int(port)
            b.probe_port = int(probe_port)
            b.ejected = False
            b.consecutive_errors = 0
        log.info("backend %s re-pointed to %s:%d", replica_id, host, port)
        return True

    def find(self, replica_id: str) -> Optional[Backend]:
        with self._mu:
            for b in self.backends:
                if b.replica_id == replica_id:
                    return b
        return None

    def live_count(self) -> int:
        """Backends /healthz counts live.  Liveness must be RECENT: a
        backend that once served but now fails every request is dead,
        so the predicate is ejection + the current error streak, not a
        sticky served counter."""
        return sum(
            1 for b in self.backends
            if not b.ejected
            and b.consecutive_errors < self.LIVE_ERROR_STREAK
        )

    def probe_once(self) -> None:
        """One /readyz GET per ejected backend; the first success
        readmits.  Readiness, not liveness: a draining (or warming)
        replica answers /healthz 200 but /readyz 503, and readmitting
        it would route admissions into its 503s."""
        with self._mu:
            ejected = [b for b in self.backends if b.ejected]
        for b in ejected:
            try:
                conn = http.client.HTTPConnection(
                    b.host, b.probe_port or b.port,
                    timeout=self.PROBE_TIMEOUT_S,
                )
                conn.request("GET", "/readyz")
                resp = conn.getresponse()
                resp.read()
                conn.close()
                if resp.status == 200:
                    self.readmit(b, "readiness probe succeeded")
            except (OSError, http.client.HTTPException):
                pass  # still down; next interval probes again

    def _probe_loop(self):
        while not self._prober_stop.wait(self.probe_interval_s):
            self.probe_once()

    def start(self) -> None:
        """Start the readmission prober (daemon; stop() ends it)."""
        if self._prober is not None:
            return
        self._prober_stop.clear()
        self._prober = threading.Thread(
            target=self._probe_loop, name="evdoor-probe", daemon=True
        )
        self._prober.start()

    def stop(self) -> None:
        self._prober_stop.set()
        if self._prober is not None:
            join_thread(self._prober, 5.0, "front-door prober")
            self._prober = None

    # ---- stats -----------------------------------------------------------

    def stats(self) -> List[dict]:
        return [
            {
                "replica_id": b.replica_id,
                "host": b.host, "port": b.port,
                "inflight": b.inflight,
                "served": b.served,
                "errors": b.errors,
                "consecutive_errors": b.consecutive_errors,
                "ejected": b.ejected,
                "readmissions": b.readmissions,
                "latency": b.latency_summary(self.LATENCY_WINDOW_S),
            }
            for b in self.backends
        ]
