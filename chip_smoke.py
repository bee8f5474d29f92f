#!/usr/bin/env python3
"""chip_smoke.py — does the main path run ON THE CHIP?

By design a gatekeeper-tpu answer cannot show a dead device: the device
result is only a pruning mask, the interpreter is the oracle, and every
device failure degrades to a host tier that returns the same bytes
(breaker, async compile, cost router, Python packer, AOT miss).  All of
that is legitimate fault handling in production — and together it means
the whole program could run on the host, pass every test it has, and
exit 0.  This script drives the two hot paths at the size the README
names (500 constraints x 100,000 resources, from --seed) through the
entry points a user calls, and fails on the surfaces that DO show where
the work ran: /statusz, /debug/routez, /debug/compilez, /debug/traces,
/metrics, the replica's ready line, and the driver's own sweep stats.

Three phases, each a child that owns the chip alone and has exited
before the next starts; this process never imports jax (asserted at
exit).  All share one compile cache (ops/xlacache.resolve_cache_dir):

  P  the engine at the package boundary, in-process:
     Client(driver=TpuDriver()), full corpus, audit_capped, churn,
     audit_capped again (delta path), review_batch at every row bucket,
     the full-size device mask held to the host numpy side over the same
     packed rows, an uncapped audit held to the interpreter on a subset.
     A compile error is a traceback here, not a warning behind a
     fallback — so it runs first.
  A  one pod, all roles: `python -m gatekeeper_tpu --driver tpu` against
     kube/apiserver.py served from THIS process over HTTP — controllers
     ingest the templates, sync replicates the pods, audit sweeps (full,
     then delta after churn), status is written back, AdmissionReviews
     are POSTed to the pod's own webhook, the audit role writes a
     snapshot.
  B  the served admission path: an EventFrontDoor in this process in
     front of ONE `python -m gatekeeper_tpu.fleet.replica` restored from
     A's snapshot; reviews through door -> GKW1 -> wirelistener ->
     batcher -> engine -> verdict, at least one batch on the device tier.

Stdout is two lines, printed only when every check passed: the summary
(per-phase device, mesh width, set-up/compile seconds, cache hits and
misses; also <out>/summary.json), then the result, exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
Any failed check, a missing accelerator, or a directory without the repo
exits non-zero and prints no result.

Sizes are arguments so tier-1 runs the same script tiny on the CPU
(tests/test_chip_smoke.py, --platform cpu); at the default size the
platform must be "tpu" and nothing continues on the CPU.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import re
import shutil
import signal
import socket
import ssl
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_TEMPLATES = 500
DEFAULT_RESOURCES = 100_000
CAP = 20  # --constraint-violations-limit, the reference default

# admission batch sizes phase P walks: one per row bucket the packer pads
# to (8, 16, ... 256 = the micro-batcher's max batch), so whatever way
# A's and B's batchers split their traffic, the executable is cached
BATCH_SIZES = (5, 12, 24, 48, 100, 200)


# one batch before any calibration, large enough (at 500 constraints) for
# the router's uncalibrated prior to send it to the device by itself
NATURAL_BATCH = 12


def batch_sizes(args) -> tuple:
    """The sizes whose row bucket a batch of phase B's burst can land
    in (a bucket's lower edge is half its width; 8 is the smallest)."""
    def lower_edge(n):
        b = 8
        while b < n:
            b *= 2
        return 0 if b == 8 else b // 2
    return tuple(n for n in BATCH_SIZES if lower_edge(n) < args.burst)

# every executable the driver persists, by its aot_jit tag
TAGS_AUDIT = ("fused-audit", "fused", "delta")
TAG_REVIEW = "fused-packed"

FALLBACK_LOG = re.compile(
    r"device .*failed.*serving .*from the\s+interpreter tier", re.S)


class SmokeFailure(Exception):
    """One or more checks failed; the message lists them."""


# ---------------------------------------------------------------------------
# the corpus, from the seed (parent, oracle and every child agree on it)
# ---------------------------------------------------------------------------


def corpus(args):
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    templates, constraints = make_templates(args.templates, args.seed)
    pods = make_pods(args.resources, args.seed + 1)
    return templates, constraints, pods


def churn_indices(args):
    rng = random.Random(args.seed + 2)
    return sorted(rng.sample(range(args.resources), args.churn))


def churned(pod: dict) -> dict:
    """The churn: an image retag — content changes (and with it the
    imageprefix verdict) without widening any padded dimension."""
    pod = json.loads(json.dumps(pod))
    ctr = pod["spec"]["containers"][0]
    ctr["image"] = str(ctr.get("image", "")) + "-churned"
    return pod


def subset_indices(args):
    """Rows the interpreter oracle audits in full: a seeded sample plus
    some churned rows (the interpreter covers ~10k cells/s, so it cannot
    cover the corpus)."""
    rng = random.Random(args.seed + 3)
    idx = set(rng.sample(range(args.resources), args.subset))
    idx.update(churn_indices(args)[: max(1, args.subset // 64)])
    return sorted(idx)


def review_pods(n: int, seed: int):
    """n unique pods of ONE shape class: 3 containers, 5 labels, at most
    one port (0 and 1 share a slot width, as do 0 and 1 volumes).  Every
    padded slot width of a review batch is the maximum over its members,
    and those widths key the compiled executable: with a single class,
    any split a batcher makes of these reviews — down to one review —
    lands on the executable phase P compiled for that row bucket."""
    from gatekeeper_tpu.util.synthetic import make_pods

    def compliant(p) -> bool:
        # the generator's compliant pods satisfy every constraint clone
        spec = p["spec"]
        return not spec.get("hostPID") and all(
            "nfs" not in v for v in spec.get("volumes", ())) and all(
            c["image"].startswith("registry.corp/")
            and "securityContext" not in c
            and all(pt.get("hostPort") == 8080 for pt in c.get("ports", ()))
            for c in spec["containers"])

    good, bad = [], []
    for p in make_pods(32 * n + 512, seed=seed, violation_rate=0.5):
        ctrs = p["spec"]["containers"]
        if (len(ctrs) == 3 and len(p["metadata"]["labels"]) == 5
                and sum(len(c.get("ports", ())) for c in ctrs) <= 1):
            (good if compliant(p) else bad).append(p)
    # allowed and denied alternate, so every phase posts both
    out = [p for pair in zip(good, bad) for p in pair][:n]
    if len(out) < n:
        raise SmokeFailure(f"review generator produced {len(out)} < {n}")
    for i, p in enumerate(out):
        p["metadata"]["name"] = f"smoke-{seed}-{i}"
    return out


def review_sets(args):
    """{phase: [pod, ...]} — unique content everywhere, so no request
    memo (ops/driver.py _request_memo) ever answers."""
    n_p = NATURAL_BATCH + sum(batch_sizes(args))
    n_a = args.reviews
    n_b = args.reviews + args.burst
    pods = review_pods(n_p + n_a + n_b, args.seed + 4)
    return {
        "P": pods[:n_p],
        "A": pods[n_p:n_p + n_a],
        "B": pods[n_p + n_a:],
    }


def admission_request(pod: dict, uid: str) -> dict:
    return {
        "uid": uid,
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": pod["metadata"]["name"],
        "namespace": pod["metadata"]["namespace"],
        "operation": "CREATE",
        "userInfo": {"username": "chip-smoke"},
        "object": pod,
    }


def verdict_of(results) -> list:
    """[allowed, sorted messages] of one review's results."""
    return [not results, sorted(r.msg for r in results)]


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def _write_result(args, payload: dict):
    tmp = args.result + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, args.result)


def child_oracle(args) -> int:
    """The interpreter oracle (no jax): expected verdicts + messages of
    every review any phase posts, and the full audit of the subset."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.client.drivers import InterpDriver
    from gatekeeper_tpu.util.synthetic import audit_result_sig

    templates, constraints, pods = corpus(args)
    for i in churn_indices(args):
        pods[i] = churned(pods[i])
    client = Client(driver=InterpDriver())
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    verdicts = {}
    for phase, rpods in review_sets(args).items():
        verdicts[phase] = [
            verdict_of(client.review(
                admission_request(p, f"oracle-{i}")).results())
            for i, p in enumerate(rpods)
        ]
    for i in subset_indices(args):
        client.add_data(pods[i])
    sig = audit_result_sig(client.audit().results())
    assert "jax" not in sys.modules
    _write_result(args, {"verdicts": verdicts,
                         "subset_sig": [list(s) for s in sig]})
    return 0


def _device_or_die(args) -> dict:
    """First touch of jax in a chip-holding child: the platform must be
    the one asked for — at the default size that is 'tpu', and nothing
    continues on the CPU."""
    from gatekeeper_tpu.parallel.mesh import device_info

    info = device_info()
    if info["platform"] != args.platform:
        print(f"chip_smoke: jax reports platform {info['platform']!r} "
              f"({info['device_kind']} x{info['count']}), need "
              f"{args.platform!r}: refusing to run", file=sys.stderr)
        sys.exit(3)
    return info


def child_engine(args) -> int:
    """Phase P (see module docstring)."""
    t_start = time.monotonic()
    dev = _device_or_die(args)
    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.obs import compilestats
    from gatekeeper_tpu.ops import deltasweep
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.ops.xlacache import enable_caches
    from gatekeeper_tpu.util.synthetic import audit_result_sig

    cache_dir = enable_caches()
    out = {"device": dev, "cache_dir": cache_dir, "timings_s": {}}
    tm = out["timings_s"]

    def lap(name, t0):
        tm[name] = round(time.monotonic() - t0, 3)

    def settle():
        # production sweeps are interval-spaced, so the background
        # base-mask resolve + delta-executable compile always land
        # between them; back to back we wait (bench.py settle_warmups)
        for t in list(deltasweep._BG_THREADS):
            t.join(timeout=600)

    t0 = time.monotonic()
    templates, constraints, pods = corpus(args)
    lap("corpus", t0)
    t0 = time.monotonic()
    driver = TpuDriver()
    client = Client(driver=driver)
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    for p in pods:
        client.add_data(p)
    lap("ingest", t0)
    out["mesh_width"] = driver.mesh_layout()

    rpods = review_sets(args)["P"]
    verdicts, routes = [], []
    prior = driver.DEVICE_MIN_CELLS

    def review_batch(pods_, tag, pin):
        """One admission batch through the package boundary; `pin` sends
        it to the device with the documented knob (said in the output)."""
        reqs = [admission_request(p, f"{tag}-{j}")
                for j, p in enumerate(pods_)]
        driver.DEVICE_MIN_CELLS = 0 if pin else prior
        try:
            for resp in client.review_batch(reqs):
                verdicts.append(verdict_of(resp.results()))
        finally:
            driver.DEVICE_MIN_CELLS = prior
        tier, reason = driver.route_ledger.last_decision or (None, None)
        routes.append({"size": len(pods_), "pinned": pin,
                       "tier": tier, "reason": reason})

    # ---- one batch the router places by itself, before any calibration
    t0 = time.monotonic()
    review_batch(rpods[:NATURAL_BATCH], "P-nat",
                 pin=NATURAL_BATCH * args.templates < prior)
    lap("first_review_batch_incl_compile", t0)

    # ---- the startup calibration main.py runs.  It also settles the
    # vocabulary: its probe reviews intern ~1,100 unique strings, and the
    # vocabulary's power-of-two bucket is part of every executable's key
    # — a pod calibrates before its first sweep, so everything below runs
    # (and is cached) at the bucket phases A and B will be in
    t0 = time.monotonic()
    driver.calibrate_routing()
    lap("calibrate_routing_first", t0)

    # ---- full sweep, then churn, then the delta sweep
    t0 = time.monotonic()
    res, totals = client.audit_capped(CAP)
    lap("first_sweep_incl_compile", t0)
    out["sweep_full"] = dict(driver.last_sweep_stats)
    out["violations_kept"] = len(res.results())
    t0 = time.monotonic()
    settle()
    lap("delta_warmup", t0)
    for i in churn_indices(args):
        pods[i] = churned(pods[i])
        client.add_data(pods[i])
    t0 = time.monotonic()
    res, totals = client.audit_capped(CAP)
    lap("delta_sweep", t0)
    out["sweep_delta"] = dict(driver.last_sweep_stats)
    out["totals"] = {f"{k}/{n}": [int(c), how]
                     for (k, n), (c, how) in totals.items()}

    if out["mesh_width"] > 1:
        # several chips: the rows must actually live on all of them, and
        # the sharded sweep must equal the single-device one
        placed = driver._audit_dev_mesh[2]
        leaf = placed[0]["valid"]
        out["rows_per_device"] = sorted(
            (str(s.device), int(s.data.shape[0]))
            for s in leaf.addressable_shards)
        st = driver._delta_state
        wide = (st.counts.tolist(), [list(c) for c in st.cand])
        width = out["mesh_width"]
        driver.set_mesh(True, width=1)
        client.audit_capped(CAP)
        st = driver._delta_state
        out["width1_equal"] = wide == (
            st.counts.tolist(), [list(c) for c in st.cand])
        out["sweep_width1"] = dict(driver.last_sweep_stats)
        settle()
        driver.set_mesh(True, width=width)
        client.audit_capped(CAP)
        settle()

    # ---- uncapped audit (fetches the full mask) vs the oracle's subset
    t0 = time.monotonic()
    names = {pods[i]["metadata"]["name"] for i in subset_indices(args)}
    full = client.audit().results()
    sig = [s for s in audit_result_sig(full) if s[3] in names]
    out["subset_sig"] = [list(s) for s in sig]
    out["audit_results"] = len(full)
    lap("uncapped_audit", t0)

    # ---- the full-size device mask vs the host numpy side, same rows
    t0 = time.monotonic()
    with driver._lock:
        ap = driver._audit_pack
        dev_mask = driver._delta_state.host_mask[:, :ap.n_rows]
        driver._np_side.sync(driver)
        mismatches = 0
        step = 8192
        for lo in range(0, ap.n_rows, step):
            hi = min(lo + step, ap.n_rows)
            rv = {k: a[lo:hi] for k, a in ap.rp.items()}
            cols = {ck: {leaf: a[lo:hi] for leaf, a in leaves.items()}
                    for ck, leaves in ap.cols.items()}
            _ord, np_mask, _rej = driver._np_side.eval_packed(
                driver, rv, cols, hi - lo)
            mismatches += int(np.count_nonzero(
                np_mask != dev_mask[:, lo:hi]))
    out["mask_parity"] = {
        "cells": int(dev_mask.size), "mismatches": mismatches,
        "positives": int(np.count_nonzero(dev_mask)),
    }
    lap("mask_parity", t0)

    # ---- admission batches on the device, one per row bucket.  Pinned:
    # the calibrated router prices these shapes for a host tier
    t0 = time.monotonic()
    at = NATURAL_BATCH
    for size in batch_sizes(args):
        review_batch(rpods[at:at + size], f"P-{size}", pin=True)
        at += size
    out["verdicts"] = verdicts
    out["routes"] = routes
    lap("review_batches", t0)

    # ---- calibration again, at the settled vocabulary (the one recorded)
    t0 = time.monotonic()
    cal = driver.calibrate_routing()
    out["calibration"] = {k: round(v, 4) for k, v in (cal or {}).items()}
    lap("calibrate_routing", t0)

    # ---- the warm-up dispatch a restarted pod's background compiler
    # runs first (ops/asynccompile.py): its probe review is a shape of
    # its own, and run here a compile error is a traceback
    from gatekeeper_tpu.ops.asynccompile import AsyncCompiler

    t0 = time.monotonic()
    AsyncCompiler(driver)._compile_epoch(driver._cs_epoch)
    lap("restart_warmup_dispatch", t0)
    out["vocabulary"] = driver.interner.snapshot_size()

    from gatekeeper_tpu import native

    out["native_loaded"] = native.load() is not None
    out["breaker"] = driver.breaker_status()
    out["route_counts"] = driver.route_ledger.snapshot(limit=0)["counts"]
    out["compilez"] = compilestats.get_stats().snapshot(limit=64)
    tm["total"] = round(time.monotonic() - t_start, 3)
    _write_result(args, out)
    return 0


CHILDREN = {"oracle": child_oracle, "P": child_engine}


# ---------------------------------------------------------------------------
# parent-side plumbing
# ---------------------------------------------------------------------------


class Procs:
    """Every process this script starts, so that it stops them all on
    every exit path (each runs in its own session: the whole group
    dies)."""

    def __init__(self):
        self.live = []

    def popen(self, cmd, log_path, env):
        logf = open(log_path, "ab")
        proc = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdin=subprocess.DEVNULL,
            stdout=logf, stderr=subprocess.STDOUT, start_new_session=True,
        )
        logf.close()
        self.live.append(proc)
        return proc

    @staticmethod
    def stop(proc, grace_s: float = 20.0):
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGINT)  # App.stop() runs
                proc.wait(timeout=grace_s)
            except (ProcessLookupError, PermissionError):
                pass
            except subprocess.TimeoutExpired:
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait(timeout=10)

    def stop_all(self):
        for proc in self.live:
            try:
                self.stop(proc, grace_s=2.0)
            except Exception as e:  # keep stopping the others
                print(f"chip_smoke: could not stop pid {proc.pid}: {e!r}",
                      file=sys.stderr)


def child_env() -> dict:
    env = dict(os.environ)
    env["GK_NATIVE"] = "require"  # a silent Python packer is a failure
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def self_cmd(args, child: str, result: str) -> list:
    return [
        sys.executable, os.path.abspath(__file__), "--child", child,
        "--result", result, "--platform", args.platform,
        "--templates", str(args.templates),
        "--resources", str(args.resources), "--seed", str(args.seed),
        "--churn", str(args.churn), "--subset", str(args.subset),
        "--reviews", str(args.reviews), "--burst", str(args.burst),
    ]


def log_tail(path: str, n: int = 25, width: int = 400) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(
                (ln if len(ln) <= width else ln[:width] + "...\n")
                for ln in f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def wait_child(proc, what: str, log_path: str, timeout_s: float):
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        Procs.stop(proc, grace_s=1.0)
        raise SmokeFailure(
            f"{what} did not finish in {timeout_s:.0f}s; log tail:\n"
            + log_tail(log_path))
    if rc != 0:
        raise SmokeFailure(
            f"{what} exited rc={rc}; log tail:\n" + log_tail(log_path))


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Http:
    """GET/POST against a child's listener, HTTPS (the App's rotated
    self-signed cert) or plain HTTP, whichever it serves."""

    def __init__(self, port: int):
        self.port = port
        self.tls = None  # learned on first contact

    def _conn(self, tls: bool, timeout: float):
        if tls:
            return http.client.HTTPSConnection(
                "127.0.0.1", self.port, timeout=timeout,
                context=ssl._create_unverified_context())
        return http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=timeout)

    def request(self, method: str, path: str, body: bytes = None,
                timeout: float = 60.0):
        order = [self.tls] if self.tls is not None else [True, False]
        last = None
        for tls in order:
            conn = self._conn(tls, timeout)
            try:
                conn.request(method, path, body=body, headers=(
                    {"Content-Type": "application/json"} if body else {}))
                resp = conn.getresponse()
                data = resp.read()
                self.tls = tls
                return resp.status, data
            except (ssl.SSLError, http.client.HTTPException,
                    ConnectionError, socket.timeout, OSError) as e:
                last = e
            finally:
                conn.close()
        raise ConnectionError(f"port {self.port}: {last!r}")

    def get_json(self, path: str):
        status, data = self.request("GET", path)
        if status != 200:
            raise ConnectionError(f"GET {path} -> {status}: {data[:200]!r}")
        return json.loads(data)


def poll(what: str, timeout_s: float, fn, proc=None, log_path=None,
         every_s: float = 0.25):
    """fn() until it returns something truthy; a dead child or the
    timeout is a failure that names what was being waited for."""
    deadline = time.monotonic() + timeout_s
    last_err = None
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise SmokeFailure(
                f"child exited rc={proc.returncode} while waiting for "
                f"{what}; log tail:\n" + log_tail(log_path))
        try:
            got = fn()
            if got:
                return got
        except (ConnectionError, OSError, ValueError, KeyError) as e:
            last_err = e
        time.sleep(every_s)
    raise SmokeFailure(
        f"timed out after {timeout_s:.0f}s waiting for {what}"
        + (f" (last error: {last_err!r})" if last_err else "")
        + (("; log tail:\n" + log_tail(log_path)) if log_path else ""))


class Checks:
    """Named pass/fail checks of one phase; every failure is kept so one
    run reports them all."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed = []

    def check(self, ok, what: str, detail=""):
        if not ok:
            self.failed.append(
                f"[{self.phase}] {what}" + (f": {detail}" if detail else ""))
        return bool(ok)


def check_breaker(ck: Checks, breaker: dict):
    ck.check(breaker.get("trips") == 0
             and breaker.get("consecutive_failures") == 0
             and breaker.get("last_error") is None,
             "tpu_breaker never tripped", json.dumps(breaker))


def check_log(ck: Checks, log_path: str):
    with open(log_path, "r", errors="replace") as f:
        text = f.read()
    m = FALLBACK_LOG.search(text)
    ck.check(m is None, "no device-failed-interpreter-fallback log line",
             m.group(0)[:300] if m else "")


def check_device(ck: Checks, args, dev: dict):
    ck.check(dev.get("platform") == args.platform,
             f"child reports platform {args.platform!r}", json.dumps(dev))


def cold_tags(compilez: dict) -> list:
    return sorted(k for k in compilez.get("provenance_mix", {})
                  if k.endswith("|cold"))


def check_tags(ck: Checks, compilez: dict, tags, mesh_width: int):
    """Every fused executable the phase needed was built or loaded here
    (under a mesh the sharded executables go through plain jit and carry
    no tag — only the cache counters speak then)."""
    ck.check(compilez.get("xlacache", {}).get("counters_available") is True,
             "xlacache.counters_available")
    if mesh_width > 1:
        return
    have = {k.split("|")[0] for k in compilez.get("provenance_mix", {})}
    missing = [t for t in tags if t not in have]
    ck.check(not missing, "every fused tag compiled",
             f"missing {missing} in {sorted(have)}")


def cache_counts(compilez: dict) -> dict:
    mix = compilez.get("provenance_mix", {})
    x = compilez.get("xlacache", {})
    return {
        "xla_hits": x.get("hits"), "xla_misses": x.get("misses"),
        "aot_loads": sum(n for k, n in mix.items() if k.endswith("|aot")),
        "persistent": sum(n for k, n in mix.items()
                          if k.endswith("|persistent")),
        "cold": sum(n for k, n in mix.items() if k.endswith("|cold")),
    }


def check_verdicts(ck: Checks, got: list, want: list, what: str):
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    ck.check(len(got) == len(want) and not bad,
             f"{what}: verdicts+messages equal the interpreter oracle's",
             f"{len(got)} answers vs {len(want)}; first diffs "
             + json.dumps([(got[i], want[i]) for i in bad[:2]])[:600])
    ck.check(any(v[0] for v in got) and any(not v[0] for v in got),
             f"{what}: both allowed and denied reviews were posted")


def response_verdict(status: int, data: bytes) -> list:
    """[allowed, sorted messages] of one AdmissionReview answer, the
    webhook's `[denied by <constraint>] ` prefix stripped
    (util/overloadcheck.py)."""
    from gatekeeper_tpu.util.overloadcheck import (
        ACCEPTED, classify_response, normalize_deny_messages)

    cls, out = classify_response(status, data)
    if cls != ACCEPTED:
        return [None, [f"{cls}: HTTP {status} {data[:200]!r}"]]
    return [bool(out["allowed"]), normalize_deny_messages(out)]


# ---------------------------------------------------------------------------
# phase P (parent side)
# ---------------------------------------------------------------------------


def phase_engine(args, procs: Procs, oracle_wait) -> dict:
    ck = Checks("P")
    result = os.path.join(args.out, "P.json")
    log_path = os.path.join(args.out, "logs", "P.log")
    proc = procs.popen(self_cmd(args, "P", result), log_path, child_env())
    wait_child(proc, "phase P", log_path, args.phase_timeout)
    r = read_json(result)
    check_device(ck, args, r["device"])
    width = r["mesh_width"]
    ck.check(width == r["device"]["count"], "mesh spans every device",
             f"width {width} vs {r['device']['count']} devices")
    check_breaker(ck, r["breaker"])
    check_log(ck, log_path)
    ck.check(r["native_loaded"], "native packer loaded")
    full, delta = r["sweep_full"], r["sweep_delta"]
    ck.check("device_ms" in full and full.get("rows") == args.resources
             and full.get("shards") == width and not full.get("cached"),
             "first sweep was a full device sweep", json.dumps(full))
    ck.check(delta.get("delta_rows") == args.churn
             and delta.get("rows") == args.resources
             and delta.get("shards") == width,
             "second sweep was a device delta sweep of exactly the churn",
             json.dumps(delta))
    if width > 1:
        ck.check(len(r["rows_per_device"]) == width
                 and all(n > 0 for _d, n in r["rows_per_device"]),
                 "rows resident on every device",
                 json.dumps(r["rows_per_device"]))
        ck.check(r["width1_equal"], "sharded sweep equals width 1")
    mp = r["mask_parity"]
    ck.check(mp["mismatches"] == 0 and mp["positives"] > 0
             and mp["cells"] >= args.templates * args.resources,
             "full-size device mask equals the host numpy side's",
             json.dumps(mp))
    ck.check(all(x["tier"] == "device" for x in r["routes"]),
             "every review batch was served by the device tier",
             json.dumps(r["routes"]))
    n_dev = sum(n for k, n in r["route_counts"].items()
                if k.startswith("device|"))
    ck.check(n_dev >= 1 + len(batch_sizes(args)), "route ledger counts the "
             "device decisions", json.dumps(r["route_counts"]))
    check_tags(ck, r["compilez"], TAGS_AUDIT + (TAG_REVIEW,), width)
    oracle = oracle_wait()
    check_verdicts(ck, r["verdicts"], oracle["verdicts"]["P"],
                   "review_batch")
    ck.check(r["subset_sig"] == oracle["subset_sig"]
             and len(r["subset_sig"]) > 0,
             "uncapped audit equals the interpreter's on the subset",
             f"{len(r['subset_sig'])} vs {len(oracle['subset_sig'])} "
             "results")
    summary = {
        "device": r["device"], "mesh_width": width,
        "timings_s": r["timings_s"],
        "sweep_full": full, "sweep_delta": delta,
        "mask_parity": mp, "routes": r["routes"],
        "calibration": r["calibration"], "vocabulary": r["vocabulary"],
        "violations_kept": r["violations_kept"],
        "audit_results": r["audit_results"],
        "cache": cache_counts(r["compilez"]),
        "provenance_mix": r["compilez"]["provenance_mix"],
        "compile_seconds_total": r["compilez"]["compile_seconds_total"],
        "cold_tags": cold_tags(r["compilez"]),
    }
    return {"summary": summary, "failed": ck.failed, "totals": r["totals"]}


# ---------------------------------------------------------------------------
# phase A (parent side): one pod, all roles, against our own API server
# ---------------------------------------------------------------------------

GK_NS = "gatekeeper-system"
POD_GVK = ("", "v1", "Pod")
CONSTRAINTS_GV = ("constraints.gatekeeper.sh", "v1beta1")


def build_cluster(args):
    """An API store holding what the cluster of a RESTARTED pod holds:
    the gatekeeper CRDs, its namespace, the Config that syncs Pods, the
    templates, the constraint CRDs the previous incarnation created for
    them, their constraints, the namespaces and the pods."""
    import yaml

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.client.drivers import InterpDriver
    from gatekeeper_tpu.kube.apiserver import KubeApiServer
    from gatekeeper_tpu.kube.inmem import InMemoryKube

    templates, constraints, pods = corpus(args)
    kube = InMemoryKube()
    with open(os.path.join(HERE, "deploy", "gatekeeper.yaml")) as f:
        for doc in yaml.safe_load_all(f):
            if doc and doc.get("kind") == "CustomResourceDefinition":
                kube.create(doc)
    namespaces = sorted({p["metadata"]["namespace"] for p in pods} | {GK_NS})
    for ns in namespaces:
        kube.create({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": ns}})
    kube.create({
        "apiVersion": "config.gatekeeper.sh/v1alpha1", "kind": "Config",
        "metadata": {"name": "config", "namespace": GK_NS},
        "spec": {"sync": {"syncOnly": [
            {"group": "", "version": "v1", "kind": "Pod"}]}},
    })
    synth = Client(driver=InterpDriver())  # CRD synthesis only, no jax
    for t in templates:
        kube.create(t)
        crd = synth.add_template(t)
        kind = t["spec"]["crd"]["spec"]["names"]["kind"]
        # what controllers/constrainttemplate.py applies on ingest
        kube.create({
            "apiVersion": "apiextensions.k8s.io/v1",
            "kind": "CustomResourceDefinition",
            "metadata": {"name": f"{kind.lower()}.{CONSTRAINTS_GV[0]}"},
            "spec": crd.get("spec", crd),
            "status": {"conditions": [
                {"type": "Established", "status": "True"}]},
        })
    for c in constraints:
        kube.create(c)
    for p in pods:
        kube.create(p)
    # history deep enough that the churn's watch events are never
    # compacted away under a slow consumer
    srv = KubeApiServer(kube, watch_history=max(4096, 8 * args.churn))
    srv.start()
    return kube, srv, constraints, pods


def audit_traces(http: Http) -> list:
    """Root-span attrs of the audit traces still in the ring, oldest
    first."""
    out = []
    for tr in http.get_json("/debug/traces?min_ms=0")["traces"]:
        if tr.get("root") != "audit":
            continue
        for sp in tr["spans"]:
            if sp["name"] == "audit" and sp.get("parent_id") is None:
                out.append(dict(sp.get("attrs") or {},
                                duration_ms=sp["duration_ms"]))
    return out


def tpu_dispatch_samples(http: Http) -> int:
    status, data = http.request("GET", "/metrics")
    if status != 200:
        raise ConnectionError(f"/metrics -> {status}")
    n = 0
    for m in re.finditer(
            r'tpu_dispatch_seconds_count\{([^}]*)\}\s+([0-9.e+]+)',
            data.decode("utf-8", "replace")):
        if 'tier="tpu"' in m.group(1):
            n += int(float(m.group(2)))
    return n


def post_reviews(http: Http, pods: list, tag: str) -> list:
    out = []
    for i, p in enumerate(pods):
        body = json.dumps(
            {"request": admission_request(p, f"{tag}-{i}")}).encode()
        out.append(response_verdict(
            *http.request("POST", "/v1/admit", body=body)))
    return out


def newest_snapshot(snap_dir: str, since_s: float):
    """The newest COMPLETE snapshot (`snap-<unix ms>-<pid>`: the writer
    renames a finished one into place; anything else is a temp or a
    quarantine) written after since_s, or None."""
    best = None
    try:
        names = os.listdir(snap_dir)
    except OSError:
        return None
    for n in names:
        m = re.fullmatch(r"snap-(\d+)-\d+", n)
        if m and int(m.group(1)) >= since_s * 1e3:
            best = max(best or n, n)
    return best


def phase_pod(args, procs: Procs, oracle_wait, p_totals) -> dict:
    ck = Checks("A")
    t_phase = time.monotonic()
    log_path = os.path.join(args.out, "logs", "A.log")
    snap_dir = os.path.join(args.out, "snapshot")
    os.makedirs(snap_dir, exist_ok=True)
    timings = {}
    t0 = time.monotonic()
    kube, srv, constraints, pods = build_cluster(args)
    timings["cluster_build"] = round(time.monotonic() - t0, 3)
    proc = None
    try:
        port, mport = free_port(), free_port()
        cmd = [
            sys.executable, "-m", "gatekeeper_tpu", "--driver", "tpu",
            "--api-server", srv.url, "--audit-from-cache",
            "--audit-interval", str(args.audit_interval),
            "--constraint-violations-limit", str(CAP),
            "--snapshot-dir", snap_dir, "--snapshot-interval", "1",
            "--port", str(port), "--prometheus-port", str(mport),
            "--health-addr", ":0",
            "--cert-dir", os.path.join(args.out, "certs"),
            "--trace-buffer-size", "4096",
        ]
        t_spawn = time.monotonic()
        proc = procs.popen(cmd, log_path, child_env())
        http = Http(port)
        waitkw = dict(proc=proc, log_path=log_path)

        st = poll("the pod's /statusz", 300, lambda: http.get_json(
            "/statusz").get("device"), **waitkw)
        check_device(ck, args, st)
        width = st["count"]  # GK_MESH default: every visible device

        def full_sweep():
            for a in audit_traces(http):
                if (a.get("rows") == args.resources
                        and "delta_rows" not in a):
                    return a
        full = poll("a full device sweep of every resource in "
                    "/debug/traces", args.phase_timeout, full_sweep,
                    every_s=1.0, **waitkw)
        timings["spawn_to_first_full_sweep"] = round(
            time.monotonic() - t_spawn, 3)
        ck.check(full.get("shards") == width
                 and full.get("mode") == "from-cache",
                 "audit trace carries the full sweep's shape",
                 json.dumps(full))

        # the background warm-up (base mask + delta executable) must
        # land before the churn, or the next sweep is a full one
        def delta_ready():
            cz = http.get_json("/debug/compilez")
            if width > 1:
                return cz
            tags = {k.split("|")[0] for k in cz["provenance_mix"]}
            return cz if "delta" in tags else None
        poll("the delta executable in /debug/compilez",
             args.phase_timeout, delta_ready, every_s=1.0, **waitkw)

        # every constraint's status was written by that sweep
        def statuses():
            got = {}
            for c in constraints:
                gvk = CONSTRAINTS_GV + (c["kind"],)
                st_ = kube.get(gvk, c["metadata"]["name"]).get("status") or {}
                if "auditTimestamp" not in st_:
                    return None
                got[f"{c['kind']}/{c['metadata']['name']}"] = st_
            return got
        poll("every constraint's status.auditTimestamp", 120, statuses,
             **waitkw)

        n_before = len([a for a in audit_traces(http) if "delta_rows" in a])
        for i in churn_indices(args):
            cur = kube.get(POD_GVK, pods[i]["metadata"]["name"],
                           pods[i]["metadata"]["namespace"])
            new = churned(cur)
            kube.update(new)

        def delta_sweeps():
            ds = [a for a in audit_traces(http) if "delta_rows" in a]
            ds = ds[n_before:]
            if sum(a["delta_rows"] for a in ds) >= args.churn:
                return ds
        deltas = poll("delta sweeps covering the churn in /debug/traces",
                      args.phase_timeout, delta_sweeps, every_s=1.0,
                      **waitkw)
        ck.check(sum(a["delta_rows"] for a in deltas) == args.churn
                 and all(a.get("rows") == args.resources
                         and a.get("shards") == width for a in deltas),
                 "the churn was swept by device delta sweeps, row for row",
                 json.dumps(deltas))
        later_full = [a for a in audit_traces(http)
                      if a.get("rows") == args.resources
                      and "delta_rows" not in a]
        ck.check(len(later_full) <= 1 or args.platform != "tpu",
                 "no second full sweep was needed", json.dumps(later_full))

        # violations read back from constraint status, after the churn
        def final_statuses():
            got = statuses()
            if got is None:
                return None
            if p_totals is not None:
                for key, (n, _how) in p_totals.items():
                    if got[key].get("totalViolations") != n:
                        return None
            return got
        try:
            got = poll("constraint status totals equal to phase P's",
                       3 * args.audit_interval + 60, final_statuses, **waitkw)
        except SmokeFailure as e:
            got = statuses() or {}
            ck.check(False, "status totals equal phase P's", str(e)[:300])
        n_viol = sum(len(s.get("violations") or []) for s in got.values())
        ck.check(n_viol > 0 and all(
            len(s.get("violations") or []) <= CAP for s in got.values()),
            "violations were written back to constraint status",
            f"{n_viol} kept")

        ck.check(tpu_dispatch_samples(Http(mport)) > 0,
                 'tpu_dispatch_seconds has tier="tpu" samples')

        # admission through the pod's own webhook
        poll("routing calibration in /debug/routez", 300,
             lambda: http.get_json("/debug/routez?limit=0")["calibration"],
             **waitkw)
        verdicts = post_reviews(http, review_sets(args)["A"], "A")
        check_verdicts(ck, verdicts, oracle_wait()["verdicts"]["A"],
                       "webhook")

        # a snapshot from AFTER the calibration: its interner then holds
        # the settled vocabulary phase B's executables are keyed by
        t_mark = time.time()
        snap = poll("a snapshot written by the audit role",
                    3 * args.audit_interval + 120,
                    lambda: newest_snapshot(snap_dir, t_mark), **waitkw)
        compilez = http.get_json("/debug/compilez?limit=64")
        routez = http.get_json("/debug/routez?limit=0")
        st = http.get_json("/statusz")
        check_breaker(ck, st["tpu_breaker"])
        check_tags(ck, compilez, TAGS_AUDIT + (TAG_REVIEW,), width)
        cache = cache_counts(compilez)
        if p_totals is not None:
            ck.check((cache["aot_loads"] or 0) + (cache["xla_hits"] or 0)
                     > 0, "compile-cache hits from phase P",
                     json.dumps(cache))
        timings["total"] = round(time.monotonic() - t_phase, 3)
        summary = {
            "api_store": "kube/apiserver.py over HTTP from this process",
            "device": st["device"], "mesh_width": width,
            "timings_s": timings, "sweep_full": full,
            "sweeps_delta": deltas, "violations_in_status": n_viol,
            "route_counts": routez["counts"],
            "calibration": routez["calibration"],
            "snapshot": snap, "cache": cache,
            "provenance_mix": compilez["provenance_mix"],
            "compile_seconds_total": compilez["compile_seconds_total"],
            "cold_tags": cold_tags(compilez),
        }
        with open(os.path.join(args.out, "A.json"), "w") as f:
            json.dump({"compilez": compilez, "routez": routez,
                       "statusz": st, "audit_traces": audit_traces(http)}, f)
    finally:
        if proc is not None:
            Procs.stop(proc)
        srv.stop()
    check_log(ck, log_path)
    return {"summary": summary, "failed": ck.failed,
            "snapshot_dir": snap_dir}


# ---------------------------------------------------------------------------
# phase B (parent side): door -> GKW1 -> one restored replica
# ---------------------------------------------------------------------------


def pipelined_posts(port: int, bodies: list, timeout: float = 120.0):
    """Write every request on ONE connection before reading any answer:
    the door coalesces what a reactor tick finds into GKW1 chunks, which
    is how a batch large enough for the device tier forms."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        wire = b"".join(
            b"POST /v1/admit HTTP/1.1\r\nHost: door\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(b)).encode() + b"\r\n\r\n" + b for b in bodies)
        threading.Thread(target=sock.sendall, args=(wire,),
                         daemon=True).start()
        f = sock.makefile("rb")
        out = []
        for _ in bodies:
            status_line = f.readline()
            if not status_line:
                raise ConnectionError("door closed the connection")
            status = int(status_line.split()[1])
            length = 0
            while True:
                line = f.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.partition(b":")
                if k.strip().lower() == b"content-length":
                    length = int(v)
            out.append((status, f.read(length)))
        return out
    finally:
        sock.close()


def phase_served(args, procs: Procs, oracle_wait, snap_dir: str) -> dict:
    from gatekeeper_tpu.fleet import EventFrontDoor
    from gatekeeper_tpu.fleet.replica import spawn_replica

    ck = Checks("B")
    t_phase = time.monotonic()
    rpods = review_sets(args)["B"]
    singles, burst = rpods[:args.reviews], rpods[args.reviews:]
    want = oracle_wait()["verdicts"]["B"]
    log_path = os.path.join(args.out, "logs", "B.log")

    def run(replica_id: str, env: dict, pods_: list, tag: str):
        """One replica behind one door: post, then read its surfaces."""
        h = spawn_replica(replica_id, snapshot_dir=snap_dir, env=env,
                          timeout_s=args.phase_timeout)
        procs.live.append(h.proc)
        door = None
        try:
            door = EventFrontDoor(
                [h.wire_backend()], probe_interval_s=3600.0).start()
            bodies = [json.dumps({"request": admission_request(
                p, f"{tag}-{i}")}).encode() for i, p in enumerate(pods_)]
            # the burst goes FIRST: until the replica's background
            # calibration lands, the router's uncalibrated prior sends a
            # batch of >= DEVICE_MIN_CELLS cells to the device by itself
            n1 = min(args.reviews, len(bodies)) if tag == "B" else 0
            answers = [None] * n1
            if bodies[n1:]:
                answers += pipelined_posts(door.port, bodies[n1:])
            dh = Http(door.port)
            dh.tls = False
            for i, b in enumerate(bodies[:n1]):
                answers[i] = dh.request("POST", "/v1/admit", body=b)
            rh = Http(h.port)
            surfaces = {
                "routez": rh.get_json("/debug/routez?limit=64"),
                "compilez": rh.get_json("/debug/compilez?limit=64"),
                "statusz": rh.get_json("/statusz"),
                "ready": h.ready, "spawn_s": h.spawn_s,
            }
            return [response_verdict(*a) for a in answers], surfaces
        finally:
            if door is not None:
                door.stop()
            h.stop()
            with open(log_path, "a") as f:
                f.write(f"---- replica {replica_id} stderr tail ----\n")
                f.write("".join(h._stderr_tail))

    env = {k: v for k, v in child_env().items()
           if k in ("GK_NATIVE", "PYTHONPATH")}
    env["GK_REPLICA_LOG_LEVEL"] = "INFO"
    verdicts, s = run("r0", env, rpods, "B")
    ready = s["ready"]
    ck.check(ready.get("restore_outcome") == "restored",
             "replica restored phase A's snapshot", json.dumps(ready))
    check_device(ck, args, ready.get("device") or {})
    check_verdicts(ck, verdicts, want, "door -> GKW1 -> replica")

    def device_served(routez):
        return {k: n for k, n in routez["counts"].items()
                if k.startswith("device|") and n > 0}

    with open(os.path.join(args.out, "B.json"), "w") as f:
        json.dump(s, f)
    dev = device_served(s["routez"])
    how = "router"
    forced = None
    if not dev:
        # no chunk was large enough for the router to pick the device by
        # itself: one more replica with the existing GK_DEVICE_MIN_CELLS
        # pin, one batch — and the output says so
        how = "forced (GK_DEVICE_MIN_CELLS=0)"
        env2 = dict(env, GK_DEVICE_MIN_CELLS="0")
        v2, forced = run("r0-forced", env2, burst, "Bf")
        check_verdicts(ck, v2, want[args.reviews:],
                       "door -> GKW1 -> replica (device pinned)")
        dev = device_served(forced["routez"])
        check_breaker(ck, forced["statusz"]["tpu_breaker"])
        # (no cold-compile check here: the pin also sends the replica's
        # own ready probe — a Namespace review, a shape no earlier phase
        # compiled — to the device)
    ck.check(bool(dev), "at least one device-tier admission was served",
             json.dumps((forced or s)["routez"]["counts"]))
    check_breaker(ck, s["statusz"]["tpu_breaker"])
    width = ready.get("device", {}).get("count", 1)
    used = (forced or s)["compilez"]
    check_tags(ck, used, (TAG_REVIEW,), width)
    ck.check(not cold_tags(s["compilez"]),
             "no cold compile (same snapshot, same shapes as A)",
             json.dumps(s["compilez"]["provenance_mix"]))
    cache = cache_counts(used)
    ck.check((cache["aot_loads"] or 0) + (cache["xla_hits"] or 0) > 0,
             "compile-cache hits from the phases before",
             json.dumps(cache))
    check_log(ck, log_path)
    summary = {
        "device": ready.get("device"), "mesh_width": width,
        "restore_outcome": ready.get("restore_outcome"),
        "ready_s": ready.get("ready_s"), "spawn_s": s["spawn_s"],
        "reviews": len(verdicts), "device_tier": how,
        "device_decisions": dev,
        "route_counts": s["routez"]["counts"],
        "calibration": s["routez"]["calibration"],
        "cache": cache,
        "provenance_mix": used["provenance_mix"],
        "cold_tags": cold_tags(used),
        "timings_s": {"total": round(time.monotonic() - t_phase, 3)},
    }
    return {"summary": summary, "failed": ck.failed}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--templates", type=int, default=DEFAULT_TEMPLATES)
    p.add_argument("--resources", type=int, default=DEFAULT_RESOURCES)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--churn", type=int, default=200,
                   help="rows churned between the sweeps (<= the driver's "
                        "DELTA_MAX_ROWS, 256, or the sweep is a full one)")
    p.add_argument("--subset", type=int, default=2000,
                   help="resources the interpreter oracle audits in full")
    p.add_argument("--reviews", type=int, default=12,
                   help="AdmissionReviews posted one by one in A and B")
    p.add_argument("--burst", type=int, default=96,
                   help="AdmissionReviews pipelined on one connection in B")
    p.add_argument("--audit-interval", type=float, default=None,
                   help="phase A's --audit-interval (default: by size, so "
                        "the first sweep comes after the ingest)")
    p.add_argument("--phases", default="P,A,B",
                   help="comma list out of P,A,B (B needs A's snapshot)")
    p.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                   help="the platform every child must report; cpu is "
                        "accepted only below the default size")
    p.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="logs, the snapshot and summary.json (emptied first)")
    p.add_argument("--phase-timeout", type=float, default=900.0)
    p.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    p.add_argument("--result", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if (args.platform != "tpu"
            and args.templates * args.resources
            >= DEFAULT_TEMPLATES * DEFAULT_RESOURCES):
        p.error("--platform cpu is for sizes below the default; at "
                f"{DEFAULT_TEMPLATES}x{DEFAULT_RESOURCES} the smoke runs "
                "on a TPU or not at all")
    if args.audit_interval is None:
        args.audit_interval = max(1.0, min(30.0, args.resources / 3000.0))
    args.phases = [x for x in args.phases.split(",") if x]
    if (set(args.phases) - {"P", "A", "B"}
            or ("B" in args.phases and "A" not in args.phases)):
        p.error("--phases: a comma list out of P,A,B; B needs A")
    return args


def main(argv=None) -> int:
    if not os.path.isdir(os.path.join(HERE, "gatekeeper_tpu")):
        print("chip_smoke: gatekeeper_tpu/ is not beside this script — it "
              "drives the repository and cannot run without it",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    args = parse_args(argv)
    if args.child:
        return CHILDREN[args.child](args)

    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(os.path.join(args.out, "logs"))
    # phase B's door runs in this process: its log goes beside the
    # children's, so stderr ends with this script's verdict and not with
    # the door's slow-trace warnings
    from gatekeeper_tpu import logging as gklog
    gklog.setup("WARNING", stream=open(
        os.path.join(args.out, "logs", "smoke.log"), "a", buffering=1))
    procs = Procs()
    summary = {"ok": False, "size": {
        "templates": args.templates, "resources": args.resources,
        "churn": args.churn, "seed": args.seed}, "phases": {}}
    failed = []
    t_all = time.monotonic()
    try:
        oracle_json = os.path.join(args.out, "oracle.json")
        oracle_log = os.path.join(args.out, "logs", "oracle.log")
        oracle_proc = procs.popen(
            self_cmd(args, "oracle", oracle_json), oracle_log,
            dict(child_env(), JAX_PLATFORMS="cpu"))
        oracle_box = {}

        def oracle_wait():
            if "r" not in oracle_box:
                wait_child(oracle_proc, "the interpreter oracle",
                           oracle_log, args.phase_timeout)
                oracle_box["r"] = read_json(oracle_json)
            return oracle_box["r"]

        p_totals, snap_dir = None, None
        if "P" in args.phases:
            r = phase_engine(args, procs, oracle_wait)
            summary["phases"]["P"] = r["summary"]
            failed += r["failed"]
            p_totals = r["totals"]
        if "A" in args.phases and not failed:
            r = phase_pod(args, procs, oracle_wait, p_totals)
            summary["phases"]["A"] = r["summary"]
            failed += r["failed"]
            snap_dir = r["snapshot_dir"]
        if "B" in args.phases and not failed:
            r = phase_served(args, procs, oracle_wait, snap_dir)
            summary["phases"]["B"] = r["summary"]
            failed += r["failed"]
    except SmokeFailure as e:
        failed.append(str(e))
    finally:
        procs.stop_all()
        # the snapshot is bulky and has served its purpose; logs and the
        # summary are what a reader needs from the output directory
        shutil.rmtree(os.path.join(args.out, "snapshot"), ignore_errors=True)
        shutil.rmtree(os.path.join(args.out, "certs"), ignore_errors=True)

    summary["seconds"] = round(time.monotonic() - t_all, 1)
    summary["failed_checks"] = failed
    devices = [ph["device"] for ph in summary["phases"].values()
               if ph.get("device")]
    if devices:
        d = devices[0]
        summary["device"] = {"platform": d["platform"],
                             "kind": d["device_kind"], "count": d["count"]}
        summary["mesh_width"] = next(iter(
            summary["phases"].values()))["mesh_width"]
        if any(x != devices[0] for x in devices):
            failed.append(f"children disagree on the device: {devices}")
    if "jax" in sys.modules:
        failed.append("the smoke's own process imported jax")
    summary["claim"] = None
    summary["ok"] = not failed and bool(devices)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if not summary["ok"]:
        print("chip_smoke: FAILED\n  " + "\n  ".join(failed or [
            "no phase reported a device"]), file=sys.stderr)
        return 1
    # the summary (also <out>/summary.json) on its own line, then the
    # result line: exactly {"ok", "device": {"platform", "kind", "count"}}
    # and nothing else, the last thing on stdout
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
