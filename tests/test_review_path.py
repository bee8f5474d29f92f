"""The stage clock's fourth path, `review` (ISSUE 38): one row per
admission review answered through the batch lane, adjacent stages from
the loop thread's wake-up for the recv that completed its request frame
to the return of the write() of its response frame.  A real
WireListener + ValidationHandler + MicroBatcher over a client that
speaks GKW1 on a plain socket and an engine that allows everything
(and marks, sleeps or collects where a case asks it to)."""

import gc
import json
import socket
import threading
import time

import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.fleet import wireproto
from gatekeeper_tpu.fleet.wirelistener import WireListener
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.metrics import Reporters
from gatekeeper_tpu.metrics.views import Registry, global_registry
from gatekeeper_tpu.obs import trace as obs
from gatekeeper_tpu.webhook import MicroBatcher, ValidationHandler

from .test_controllers import CONSTRAINT, TEMPLATE
from .test_tracing import ns_request
from .wirestub import wait_until

STAGES = obs.REVIEW_STAGES


class _Answer:
    """What review_batch returns per review: no violations."""

    def results(self):
        return []


class _Engine:
    """The client under the batcher: every review allowed; `inside`
    runs in the middle of review_batch, between the marks a device
    dispatch makes on the batcher's clock (none when `tier` is the
    interpreter's)."""

    def __init__(self, tier="device", inside=None, fail_batch=False):
        self.tier = tier
        self.inside = inside
        self.fail_batch = fail_batch
        self.batches = []

    def review_batch(self, objs):
        clock = obs.running_clock(obs.PATH_BATCH)
        self.batches.append(len(objs))
        if self.fail_batch:
            raise RuntimeError("poisoned batch")
        clock.mark("route")
        clock.mark("pack")
        if self.tier == "device":
            clock.mark("enqueue")
            clock.mark("device_wait")
            if self.inside:
                self.inside("dispatch")
            clock.mark("fetch")
            clock.mark("account")
        clock.mark("render")
        if self.inside:
            self.inside("render")
        clock.mark("account")
        return [_Answer() for _ in objs]

    def review(self, obj, tracing=False):
        return _Answer()


class _Keeping(obs.StageClock):
    """The listener's review clock, keeping what it is given to book."""

    def __init__(self, booked):
        super().__init__(obs.PATH_REVIEW)
        self.booked = booked

    def add_timeline(self, row, end):
        self.booked.append((list(row), end))
        super().add_timeline(row, end)


class _Harness:
    """Listener + handler + batcher over an engine, and every row the
    loop thread books, kept with its end as booked."""

    def __init__(self, engine=None, handler_cls=ValidationHandler,
                 label_handler=None, **listener_kw):
        self.engine = engine or _Engine()
        self.batcher = MicroBatcher(self.engine)
        self.handler = handler_cls(self.batcher, kube=InMemoryKube(),
                                   reporter=Reporters(Registry()))
        self.lis = WireListener(handler=self.handler,
                                label_handler=label_handler,
                                host="127.0.0.1", **listener_kw).start()
        self.booked = []   # (row, end)
        self.lis._rclock = _Keeping(self.booked)
        self.sock = socket.create_connection(("127.0.0.1", self.lis.port))
        self.sock.settimeout(10.0)
        self.decoder = wireproto.FrameDecoder()
        self._next = 0

    def records(self, n, path="/v1/admit", deadline_ms=None):
        out = []
        for _ in range(n):
            self._next += 1
            body = json.dumps(
                {"request": ns_request(f"rp-{self._next}")}).encode()
            out.append(wireproto.RequestRecord(
                self._next, path, body, deadline_ms))
        return out

    def send(self, records):
        self.sock.sendall(wireproto.encode_request_chunk(records))

    def answers(self, n):
        """Read until `n` response records have come back."""
        got = []
        while len(got) < n:
            for kind, recs in self.decoder.feed(self.sock.recv(1 << 20)):
                assert kind == wireproto.KIND_RESPONSE
                got.extend(recs)
        return got

    def ask(self, n, **kw):
        self.send(self.records(n, **kw))
        return self.answers(n)

    def close(self):
        self.sock.close()
        self.lis.stop()
        self.batcher.stop()


@pytest.fixture()
def harness():
    made = []

    def make(*a, **kw):
        h = _Harness(*a, **kw)
        made.append(h)
        return h

    yield make
    for h in made:
        h.close()


def _sums(row, end):
    out = {}
    for (stage, t), (_nxt, t_nxt) in zip(row, row[1:] + [("", end)]):
        out[stage] = out.get(stage, 0.0) + (t_nxt - t)
    return out


def _counters():
    reg = global_registry()
    return {name: {k: v for k, v in reg.view_rows(
        f"host_stage_{name}_total").items() if k[0] == obs.PATH_REVIEW}
        for name in ("seconds", "calls", "gc_seconds")}


def _grown(before, after, name, stage):
    key = (obs.PATH_REVIEW, stage)
    return after[name].get(key, 0) - before[name].get(key, 0)


def _flushed(h):
    h.lis._flush_wire(force=True)
    return _counters()


def _queue_seconds():
    rows = global_registry().view_rows("webhook_batch_queue_seconds")
    return sum(d.sum for d in rows.values())


# ---- (a) contiguity, the count, and who is booked ------------------------


@pytest.mark.parametrize("chunk", [1, 3])
def test_a_reviews_stages_tile_its_service_time_exactly(harness, chunk):
    h = harness()
    before, queue_before = _counters(), _queue_seconds()
    assert [r.status for r in h.ask(chunk)] == [200] * chunk
    assert wait_until(lambda: len(h.booked) == chunk)
    after = _flushed(h)
    # `batch_queue` is the interval webhook_batch_queue_seconds records
    assert _grown(before, after, "seconds", "batch_queue") == pytest.approx(
        _queue_seconds() - queue_before, abs=1e-9)
    total = 0.0
    for row, end in h.booked:
        # in order, from `frame` to `write`, nothing outside the list
        names = [s for s, _t in row]
        assert names[0] == "frame" and names[-1] == "write"
        assert set(names) <= set(STAGES)
        order = [STAGES.index(s) for s in names]
        # the batcher's four repeat (account opens twice); the ten
        # others stand once and in the table's order
        once = [s for s in names if s not in (
            "batch_pre", "dispatch", "render", "batch_post")]
        assert once == [s for s in STAGES if s in once]
        assert order[:5] == [0, 1, 2, 3, 4] and order[-5:] == [9, 10, 11,
                                                                12, 13]
        instants = [t for _s, t in row] + [end]
        assert instants == sorted(instants)
        sums = _sums(row, end)
        assert sum(sums.values()) == pytest.approx(end - row[0][1],
                                                   abs=1e-9)
        total += end - row[0][1]
    # every member of the chunk carries the chunk's shared instants
    for row, end in h.booked[1:]:
        first = h.booked[0]
        assert row[:4] == first[0][:4] and end == first[1]
        assert row[-3:] == first[0][-3:]   # encode, handoff, write
    assert _grown(before, after, "calls", "write") == chunk
    seconds = sum(_grown(before, after, "seconds", s) for s in STAGES)
    assert seconds == pytest.approx(total, abs=1e-9)
    # all fourteen happened on the device tier
    for stage in STAGES:
        assert _grown(before, after, "calls", stage) == chunk, stage


def test_every_member_of_a_batch_carries_the_whole_turn(harness):
    h = harness()
    h.ask(3)
    assert wait_until(lambda: len(h.booked) == 3)
    assert h.engine.batches == [3]
    rows = [row for row, _end in h.booked]
    turn = [[e for e in row if e[0] in (
        "batch_pre", "dispatch", "render", "batch_post")] for row in rows]
    # one lap list, shared: the same marks in every member's row
    assert turn[0] == turn[1] == turn[2]
    sets = [next(t for s, t in row if s == "wake") for row in rows]
    assert sets == sorted(sets) and len(set(sets)) == 3


class _Refusing(ValidationHandler):
    """handle_many that fails whole: the listener answers the chunk
    through _failure_chunk."""

    def handle_many(self, items, timeline=None):
        raise MemoryError("the chunk cannot be processed")


def _shed(h):
    h.batcher.max_pending = 1
    gate = threading.Event()
    h.engine.inside = lambda where: gate.wait(5.0)
    h.send(h.records(1))          # in flight, held in review_batch
    assert wait_until(lambda: h.engine.batches == [1])
    h.send(h.records(1))          # queued behind it: the bound is met
    assert wait_until(lambda: len(h.batcher._pending) == 1)
    h.send(h.records(1))          # refused at the bound
    assert wait_until(lambda: h.batcher.sheds == 1)
    gate.set()
    answers = h.answers(3)
    h.engine.inside = None
    return answers, 2


def _deadline_refusal(h):
    return h.ask(1, deadline_ms=-5.0), 0


def _label_admission(h):
    return h.ask(1, path="/v1/admitlabel"), 0


def _failure_chunk(h):
    h.lis._process = lambda *a, **k: 1 / 0
    return h.ask(2), 0


@pytest.mark.parametrize("case", [_shed, _deadline_refusal,
                                  _label_admission, _failure_chunk],
                         ids=lambda f: f.__name__.strip("_"))
def test_what_the_batch_lane_did_not_answer_books_nothing(harness, case):
    h = harness()
    before = _counters()
    answers, served = case(h)
    assert all(a.status == 200 for a in answers)
    time.sleep(0.05)
    after = _flushed(h)
    assert len(h.booked) == served
    assert _grown(before, after, "calls", "write") == served
    # and the lane still books the next review it answers
    h.lis.__dict__.pop("_process", None)
    h.ask(1)
    assert wait_until(lambda: len(h.booked) == served + 1)


def test_a_handler_defect_answers_the_chunk_and_books_nothing(harness):
    h = harness(handler_cls=_Refusing)
    assert [r.status for r in h.ask(2)] == [200, 200]
    time.sleep(0.05)
    assert h.booked == []


def test_a_failed_batch_is_booked_as_one_render_interval(harness):
    h = harness(engine=_Engine(fail_batch=True))
    assert [r.status for r in h.ask(2)] == [200, 200]
    assert wait_until(lambda: len(h.booked) == 2)
    for row, end in h.booked:
        turn = [s for s, _t in row if s in (
            "batch_pre", "dispatch", "render", "batch_post")]
        assert turn == ["render"]
        assert sum(_sums(row, end).values()) == pytest.approx(
            end - row[0][1], abs=1e-9)


# ---- (b) where a delay lands ----------------------------------------------


@pytest.mark.parametrize("where", ["dispatch", "render"])
def test_a_sleep_in_review_batch_is_the_engines_and_not_the_wake(
        harness, where):
    h = harness(engine=_Engine(
        inside=lambda at: time.sleep(0.05) if at == where else None))
    h.ask(1)
    assert wait_until(lambda: len(h.booked) == 1)
    sums = _sums(*h.booked[0])
    assert sums[where] >= 0.05
    other = "render" if where == "dispatch" else "dispatch"
    assert sums["wake"] < 0.04 and sums[other] < 0.04
    assert sums["batch_queue"] < 0.04


class _SlowFinalize(ValidationHandler):
    def _finalize_verdict(self, req, *a, **k):
        time.sleep(0.05)
        return super()._finalize_verdict(req, *a, **k)


def test_a_worker_busy_with_a_sibling_shows_in_the_later_members_wake(
        harness):
    h = harness(handler_cls=_SlowFinalize)
    h.ask(2)
    assert wait_until(lambda: len(h.booked) == 2)
    first, second = (_sums(*b) for b in h.booked)
    assert first["wake"] < 0.04
    assert second["wake"] >= 0.05      # the sibling's finalize
    # and each waited for the chunk's later finalizes before `encode`
    assert first["finalize"] >= 0.1 and second["finalize"] >= 0.05


def test_a_loop_thread_held_busy_shows_in_handoff(harness):
    h = harness()
    h.ask(1)                     # the loop, workers and batcher are up
    assert wait_until(lambda: len(h.booked) == 1)
    real = h.handler.handle_many

    def slow_loop(items, timeline=None):
        # while this worker evaluates, the loop thread is given 80 ms
        # of work that ends after the frame is posted
        h.lis._loop.call_soon_threadsafe(lambda: time.sleep(0.08))
        return real(items, timeline=timeline)

    h.handler.handle_many = slow_loop
    h.ask(1)
    assert wait_until(lambda: len(h.booked) == 2)
    sums = _sums(*h.booked[1])
    assert sums["handoff"] >= 0.04
    assert sums["wake"] < 0.04 and sums["write"] < 0.04


# ---- (c) the collector -----------------------------------------------------


@pytest.mark.parametrize("where", ["dispatch", "render"])
def test_a_full_collection_lands_in_the_stage_it_fell_in(harness, where):
    h = harness(engine=_Engine(
        inside=lambda at: gc.collect() if at == where else None))
    gc.collect()      # a pause before the review began: in no stage
    time.sleep(0.002)
    before = _counters()
    h.ask(1)
    assert wait_until(lambda: len(h.booked) == 1)
    after = _flushed(h)
    row, end = h.booked[0]
    held = {s: _grown(before, after, "gc_seconds", s) for s in STAGES}
    assert held[where] > 0
    assert {s for s, v in held.items() if v} == {where}
    assert held[where] <= _sums(row, end)[where]


def test_a_pause_before_the_review_is_in_no_stage(harness):
    h = harness()
    gc.collect()
    time.sleep(0.002)
    before = _counters()
    h.ask(2)
    assert wait_until(lambda: len(h.booked) == 2)
    after = _flushed(h)
    assert not any(_grown(before, after, "gc_seconds", s) for s in STAGES)


def test_the_collectors_hook_keeps_the_last_full_pauses():
    obs.StageClock("probe")       # the hook is installed by the first clock
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    newest = max(obs._GC_FULL_RECENT, key=lambda p: p[1])
    assert t0 <= newest[0] <= newest[1] <= t1
    assert obs._GC_FULL_LAST_STOP[0] == newest[1]
    assert len(obs._GC_FULL_RECENT) == 8
    gc.collect(0)                  # a young collection is not kept
    assert obs._GC_FULL_LAST_STOP[0] == newest[1]


@pytest.mark.parametrize("pause,stage", [
    ((1.5, 1.7), "decode"),       # begins inside decode: decode's whole
    ((3.0, 3.2), "write"),        # at the very instant `write` opens
    ((0.2, 0.9), None),           # before the review began
    ((4.0, 4.1), None),           # after its last byte
])
def test_add_timeline_books_a_pause_whole_or_not_at_all(monkeypatch, pause,
                                                        stage):
    row = [("frame", 1.0), ("decode", 1.25), ("batch_pre", 2.0),
           ("decode", 2.5), ("write", 3.0)]
    monkeypatch.setattr(obs, "_GC_FULL_RECENT", [(0.0, 0.0)] * 7 + [pause])
    monkeypatch.setattr(obs, "_GC_FULL_LAST_STOP", [pause[1]])
    clock = obs.StageClock(obs.PATH_REVIEW)
    clock.add_timeline(row, 3.5)
    assert clock.totals["decode"][:2] == [1.25, 1]   # 0.75 + 0.5, one call
    assert clock.totals["write"][:2] == [0.5, 1]
    held = {s: acc[2] for s, acc in clock.totals.items() if acc[2]}
    assert held == ({} if stage is None else {
        stage: pytest.approx(pause[1] - pause[0])})


# ---- (d) the interpreter tier ----------------------------------------------


def test_the_interpreter_tier_books_no_dispatch_call(harness):
    h = harness(engine=_Engine(tier="interp"))
    before = _counters()
    h.ask(2)
    assert wait_until(lambda: len(h.booked) == 2)
    after = _flushed(h)
    assert _grown(before, after, "calls", "dispatch") == 0
    assert _grown(before, after, "seconds", "dispatch") == 0
    assert _grown(before, after, "calls", "render") == 2
    assert _grown(before, after, "calls", "write") == 2


def test_a_real_client_on_the_interpreter_tier(harness):
    """The stages the driver really marks: a Client over the
    interpreter driver serves under `render` and opens no dispatch."""
    client = Client()
    client.add_template(TEMPLATE)
    client.add_constraint(CONSTRAINT)
    h = harness(engine=client)
    before = _counters()
    answers = h.ask(2)
    assert wait_until(lambda: len(h.booked) == 2)
    after = _flushed(h)
    verdicts = [json.loads(a.body)["response"]["allowed"] for a in answers]
    assert verdicts == [False, False]
    assert _grown(before, after, "calls", "dispatch") == 0
    assert _grown(before, after, "calls", "write") == 2
    for row, end in h.booked:
        assert sum(_sums(row, end).values()) == pytest.approx(
            end - row[0][1], abs=1e-9)


# ---- (e) the lap ------------------------------------------------------------


def test_a_lap_of_the_noop_clock_and_of_a_stopped_clock():
    assert obs.NOOP_CLOCK.begin_lap() == ()
    clock = obs.StageClock("batch")
    assert clock.begin_lap() == []          # stopped: nothing kept
    clock.mark("pack")
    assert clock._lap is None               # nobody asked for a lap
    t_open = clock.mark("collect")
    lap = clock.begin_lap()
    assert lap[0][0] == "collect" and lap[0][1] >= t_open
    t_route = clock.mark("route")
    t_pack = clock.mark("pack")
    assert lap[1:] == [("route", t_route), ("pack", t_pack)]
    again = clock.begin_lap()               # the next turn's
    clock.mark("render")
    assert len(lap) == 3 and [s for s, _t in again] == ["pack", "render"]
    clock.stop()
    clock.mark("wait")
    assert len(again) == 2                  # the keeping ended at stop()
    clock.stop()


# ---- (f) the one-writer rule -------------------------------------------------


def test_two_workers_and_the_loop_booking_at_once_lose_no_review(harness):
    h = harness(workers=2)
    before = _counters()
    chunks, per = 40, 3

    def client(n):
        s = socket.create_connection(("127.0.0.1", h.lis.port))
        s.settimeout(10.0)
        dec, got = wireproto.FrameDecoder(), 0
        for i in range(chunks):
            recs = [wireproto.RequestRecord(
                n * 10000 + i * per + k, "/v1/admit", json.dumps(
                    {"request": ns_request(f"c{n}-{i}-{k}")}).encode())
                for k in range(per)]
            s.sendall(wireproto.encode_request_chunk(recs))
            if i % 4 == 3:        # several chunks in flight a connection
                while got < (i + 1) * per:
                    got += sum(len(r) for _k, r in dec.feed(s.recv(1 << 20)))
        while got < chunks * per:
            got += sum(len(r) for _k, r in dec.feed(s.recv(1 << 20)))
        s.close()

    threads = [threading.Thread(target=client, args=(n,)) for n in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    want = 3 * chunks * per
    assert wait_until(lambda: len(h.booked) == want)
    after = _flushed(h)
    assert _grown(before, after, "calls", "write") == want
    total = sum(end - row[0][1] for row, end in h.booked)
    seconds = sum(_grown(before, after, "seconds", s) for s in STAGES)
    assert seconds == pytest.approx(total, rel=1e-9)


def test_the_loops_tick_flushes_the_review_clock(harness):
    h = harness()
    before = _counters()
    h.ask(1)
    # no forced flush: the loop's own flush_due tick, within FLUSH_S
    assert wait_until(lambda: _grown(
        before, _counters(), "calls", "write") == 1, timeout_s=3.0)


# ---- (g) the stage list and its documents ------------------------------------


def _tool():
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import check_observability
    finally:
        sys.path.pop(0)
    return check_observability


def test_the_stage_list_and_the_documents_agree():
    check_observability = _tool()
    assert check_observability.check_review_stages() == []
    assert len(STAGES) == 14 and len(set(STAGES)) == 14
    assert set(obs.REVIEW_BATCH_GROUPS.values()) == {
        "batch_pre", "dispatch", "render"}
    assert set(obs.REVIEW_BATCH_GROUPS.values()) < set(STAGES)


def test_a_missing_or_an_extra_stage_in_the_document_is_found(monkeypatch):
    check_observability = _tool()
    monkeypatch.setattr(obs, "REVIEW_STAGES", STAGES + ("unwritten",))
    assert any("unwritten" in p
               for p in check_observability.check_review_stages())
    monkeypatch.setattr(obs, "REVIEW_STAGES", STAGES[:-1])
    assert any("write" in p
               for p in check_observability.check_review_stages())


def test_a_deadline_that_lapses_in_the_queue_books_nothing(harness):
    """A member the batcher refuses at the drain has no marks."""
    gate = threading.Event()
    h = harness(engine=_Engine(inside=lambda at: gate.wait(5.0)))
    h.send(h.records(1))                       # holds the batcher
    assert wait_until(lambda: h.engine.batches == [1])
    h.send(h.records(1, deadline_ms=30.0))     # lapses while it is queued
    time.sleep(0.08)
    gate.set()
    h.engine.inside = None
    answers = h.answers(2)
    assert len(answers) == 2
    time.sleep(0.05)
    assert len(h.booked) == 1
