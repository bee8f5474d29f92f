#!/usr/bin/env python
"""Headline benchmark: END-TO-END audit sweep on TPU, plus every other
BASELINE.md target config folded into the same artifact.

The default run (BENCH_CONFIG unset or "all") measures:
  - synthetic 500x100k steady-state capped audit sweep (the headline,
    BASELINE north star <1s on one v5e chip) with a pack/device/fetch/render
    breakdown and a bandwidth-roofline utilization estimate
  - admission p99 latency on demo/basic (north star <=2ms)
  - PSP library x 1k Pods audit (the reference benchmark's own fixtures)
  - agilebank full policy set x ~10k mixed resources audit
  - 1M-review streamed batch throughput (the "mesh" config shape)
  - template-ingest storm p50 (async compile, interp-served mid-storm)
  - constraint-count scaling curve N in {5..2000} (the reference's
    BenchmarkValidationHandler sweep, policy_benchmark_test.go:269)
  - multi-chip scaling of the device sweep on a virtual 8-device CPU mesh
    (subprocess; the real env exposes one chip)

and prints ONE JSON line: the headline metric/value/unit/vs_baseline plus
the secondary configs as extra keys.  Set BENCH_CONFIG to
{synthetic, latency, psp, agilebank, batch1m, ingest, curve, mesh} to run one
config alone (it then prints its own single JSON line).

Baseline note (see BASELINE.md): the reference is Go; no Go toolchain exists
in this image and installs are forbidden, so the reference harness cannot
run here.  vs_baseline is computed against this repo's Python interpreter
oracle measured on a slice of the same workload, DERATED by 50x as a
conservative stand-in for OPA's Go topdown (documented in BASELINE.md;
the raw interp rate is logged to stderr so the derate is auditable).

All diagnostics go to stderr.  Override sizes with BENCH_TEMPLATES /
BENCH_RESOURCES / BENCH_BASELINE_SLICE / BENCH_COPIES / BENCH_REVIEWS /
BENCH_INGEST_TEMPLATES / BENCH_CURVE.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

GO_TOPDOWN_DERATE = 50.0  # conservative Go-vs-Python-interp speed factor

# Published peaks of one chip, keyed by jax's device_kind.  A device that
# is not in the table is an error, never a default: a roofline share
# against another chip's bandwidth is a made-up number.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "bf16_tflops": 197.0,
        "hbm_gb": 16.0,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to DEVICE_PEAKS with its source before measuring on it"
        ) from None


def device_stamp(dev: dict = None) -> dict:
    """platform / device_kind / device count, stamped into every config's
    JSON from the process that did the work: THIS process's backend as
    jax reports it, or the `device` a chip-holding child printed
    (TpuDriver.device_info)."""
    if dev is None:
        from gatekeeper_tpu.parallel.mesh import device_info

        dev = device_info()
    return {
        "platform": dev["platform"],
        "device_kind": dev["device_kind"],
        "device_count": dev["count"],
    }


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def settle_warmups():
    """Join the driver's background warm-ups (base-mask resolve + delta
    executable compile).  Production audit sweeps are interval-spaced, so
    these always finish between sweeps; the bench's back-to-back loop
    must wait explicitly or every sweep lands in the warm window and
    falls back to a full sweep."""
    from gatekeeper_tpu.ops import deltasweep

    for t in list(deltasweep._BG_THREADS):
        t.join(timeout=300)


def load_yaml_dir(pattern):
    import glob

    import yaml

    out = []
    for f in sorted(glob.glob(pattern)):
        with open(f) as fh:
            docs = [d for d in yaml.safe_load_all(fh) if d]
        out.extend(docs)
    return out


def bench_agilebank() -> dict:
    """BASELINE config 'agilebank': full demo policy set x N mixed
    resources, from-cache audit sweep (end-to-end incl. render)."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver

    n_copies = int(os.environ.get("BENCH_COPIES", "1000"))
    base = "/root/reference/demo/agilebank"
    c = Client(driver=TpuDriver())
    for t in load_yaml_dir(f"{base}/templates/*.yaml"):
        c.add_template(t)
    n_cons = 0
    for cons in load_yaml_dir(f"{base}/constraints/*.yaml"):
        c.add_constraint(cons)
        n_cons += 1
    resources = load_yaml_dir(f"{base}/good_resources/*.yaml") + load_yaml_dir(
        f"{base}/bad_resources/*.yaml"
    )
    import copy as _copy

    total = 0
    for i in range(n_copies):
        for r in resources:
            r2 = _copy.deepcopy(r)
            r2["metadata"]["name"] = f"{r['metadata'].get('name', 'x')}-{i}"
            c.add_data(r2)
            total += 1
    log(f"agilebank: {n_cons} constraints x {total} resources")
    c.audit_capped(20)  # compile + warm (full sweep)
    settle_warmups()  # base-mask + delta executable compile off-path
    # warm the delta path too, then time an honest steady-state sweep:
    # one object mutated since the last sweep
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "bench-warm-bump"}})
    c.audit_capped(20)
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "bench-epoch-bump"}})
    t0 = time.time()
    res, _totals = c.audit_capped(20)
    dur = time.time() - t0
    log(f"agilebank end-to-end capped audit: {dur*1000:.0f}ms, "
        f"{len(res.results())} violations kept")
    return {
        "metric": f"agilebank end-to-end audit ({total} resources)",
        "value": round(dur, 3),
        "unit": "s",
        "vs_baseline": 0,
    }


def bench_psp() -> dict:
    """BASELINE config 'PSP library x 1k Pods': the reference benchmark's
    own fixtures (pkg/webhook/testdata/psp-all-violations: 5 PSP
    templates/constraints + violating pods, policy_benchmark_test.go:265-271)
    scaled to ~1k cached Pods, steady-state capped audit."""
    import copy as _copy

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver

    n_copies = int(os.environ.get("BENCH_PSP_COPIES", "200"))
    base = "/root/reference/pkg/webhook/testdata/psp-all-violations"
    c = Client(driver=TpuDriver())
    for t in load_yaml_dir(f"{base}/psp-templates/*.yaml"):
        c.add_template(t)
    n_cons = 0
    for cons in load_yaml_dir(f"{base}/psp-constraints/*.yaml"):
        c.add_constraint(cons)
        n_cons += 1
    pods = load_yaml_dir(f"{base}/psp-pods/*.yaml")
    total = 0
    for i in range(n_copies):
        for p in pods:
            p2 = _copy.deepcopy(p)
            p2["metadata"]["name"] = f"{p['metadata'].get('name', 'p')}-{i}"
            p2["metadata"].setdefault("namespace", "default")
            c.add_data(p2)
            total += 1
    log(f"psp: {n_cons} constraints x {total} pods")
    c.audit_capped(20)  # compile + warm (full sweep)
    settle_warmups()  # base-mask + delta executable compile off-path
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "psp-warm"}})
    c.audit_capped(20)  # warm the delta path
    p = _copy.deepcopy(pods[0])
    p["metadata"]["name"] = "psp-delta"
    p["metadata"].setdefault("namespace", "default")
    c.add_data(p)
    t0 = time.time()
    res, _totals = c.audit_capped(20)
    dur = time.time() - t0
    log(f"psp end-to-end capped audit: {dur*1000:.0f}ms, "
        f"{len(res.results())} violations kept")
    return {
        "metric": f"PSP library end-to-end audit ({n_cons} constraints x {total} pods)",
        "value": round(dur, 3),
        "unit": "s",
        "vs_baseline": 0,
    }


def bench_latency() -> dict:
    """BASELINE config 'demo/basic': single-review admission latency
    through the full webhook handler (p50/p99), targeting <=2ms p99."""
    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.kube.inmem import InMemoryKube
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.webhook import ValidationHandler

    base = "/root/reference/demo/basic"
    c = Client(driver=TpuDriver())
    for t in load_yaml_dir(f"{base}/templates/*.yaml"):
        c.add_template(t)
    for cons in load_yaml_dir(f"{base}/constraints/*.yaml"):
        c.add_constraint(cons)
    handler = ValidationHandler(c, kube=InMemoryKube())
    req = {
        "uid": "u", "kind": {"group": "", "version": "v1",
                             "kind": "Namespace"},
        "name": "test", "namespace": "", "operation": "CREATE",
        "userInfo": {"username": "bench"},
        "object": {"apiVersion": "v1", "kind": "Namespace",
                   "metadata": {"name": "test", "labels": {}}},
    }
    for _ in range(20):  # warm: compile + caches
        handler.handle(req)
    # the production webhook server freezes long-lived state out of the
    # cyclic GC after warmup (webhook/server.py); do the same here — in the
    # combined run the synthetic sweep's 100k-object inventory is resident
    # in this process and a gen-2 GC pause otherwise lands in the p99
    import gc

    gc.collect()
    gc.freeze()
    # k runs inside one invocation: the >=2ms target must hold on bad runs
    # (host load variance), so the artifact reports median AND max p99
    # across runs, not one lucky sample
    n_runs = int(os.environ.get("BENCH_LATENCY_RUNS", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "500"))
    p50s, p99s = [], []
    for r in range(n_runs):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            handler.handle(req)
            times.append(time.perf_counter() - t0)
        arr = np.array(times) * 1000
        p50s.append(float(np.percentile(arr, 50)))
        p99s.append(float(np.percentile(arr, 99)))
        log(f"admission latency run {r}: p50={p50s[-1]:.2f} "
            f"p99={p99s[-1]:.2f} max={arr.max():.2f} ms")
    p50, p99 = float(np.median(p50s)), float(np.median(p99s))
    log(f"admission latency ms over {n_runs} runs: p99 median={p99:.2f} "
        f"max={max(p99s):.2f}")
    srv_runs = [
        _server_level_latency(c, req)
        for _ in range(int(os.environ.get("BENCH_SERVER_RUNS", "3")))
    ]
    srv_p50 = float(np.median([r[0] for r in srv_runs]))
    srv_p99 = float(np.median([r[1] for r in srv_runs]))
    log(f"admission SERVER latency ms (TLS+batcher, {len(srv_runs)} runs): "
        f"p50 median={srv_p50:.2f} p99 median={srv_p99:.2f} "
        f"p99 max={max(r[1] for r in srv_runs):.2f}")
    stage_p50 = _stage_breakdown(handler, req)
    log(f"admission per-stage p50 ms: {stage_p50}")
    return {
        "stage_p50_ms": stage_p50,
        "metric": "admission handler p99 latency (demo/basic, deny path)",
        "value": round(p99, 3),
        "unit": "ms",
        "vs_baseline": 0,
        "p50_ms": round(p50, 3),
        "p99_runs_ms": [round(x, 3) for x in p99s],
        "p99_max_ms": round(max(p99s), 3),
        "server_p99_ms": round(srv_p99, 3),
        "server_p50_ms": round(srv_p50, 3),
        "server_p99_runs_ms": [round(r[1], 3) for r in srv_runs],
        "server_p99_max_ms": round(max(r[1] for r in srv_runs), 3),
    }


def _stage_breakdown(handler, req, iters=50):
    """Per-stage p50s of the admission path from the always-on tracer
    (obs/trace.py): each request runs under a root span; the stage spans
    (cache_lookup / pack / dispatch / render) are aggregated so future
    perf PRs can claim stage-level wins from the BENCH artifact."""
    import numpy as np

    from gatekeeper_tpu.obs import trace as obstrace

    tracer = obstrace.get_tracer()
    tracer.clear()
    for _ in range(iters):
        with obstrace.root_span("admission"):
            handler.handle(req)
    samples = {}
    for t in tracer.traces(limit=iters):
        for stage, ms in obstrace.stage_breakdown(t).items():
            samples.setdefault(stage, []).append(ms)
    tracer.clear()
    return {
        stage: round(float(np.percentile(v, 50)), 4)
        for stage, v in sorted(samples.items())
    }


def _server_level_latency(client, req):
    """p50/p99 through the PRODUCTION path: HTTPS webhook server +
    micro-batcher + handler — what the apiserver actually observes (the
    <=2ms north star applies here, not just to the bare handler).  Where
    `cryptography` is unavailable (fleet replicas behind a TLS-terminating
    front door run exactly this way, docs/fleet.md), the server is driven
    over plain HTTP instead of skipping the measurement."""
    import json as _json
    import ssl

    import numpy as np

    try:
        from gatekeeper_tpu.certs import CertRotator
    except ImportError:
        CertRotator = None
    from gatekeeper_tpu.kube.inmem import InMemoryKube
    from gatekeeper_tpu.webhook import (
        MicroBatcher, ValidationHandler, WebhookServer,
    )

    kube = InMemoryKube()
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        if CertRotator is not None:
            certfile, keyfile = CertRotator(kube).write_cert_files(td)
        else:
            certfile = keyfile = None
            log("server-level latency: 'cryptography' unavailable — "
                "measuring plain HTTP (TLS-terminating front door mode)")
        mb = MicroBatcher(client)
        handler = ValidationHandler(mb, kube=kube)
        srv = WebhookServer(handler, port=0, certfile=certfile, keyfile=keyfile)
        srv.start()
        try:
            body = _json.dumps({"request": req}).encode()
            # persistent connection, as the apiserver's webhook client uses
            # (keep-alive; the server speaks HTTP/1.1)
            import http.client

            if certfile is not None:
                ctx = ssl.create_default_context()
                ctx.check_hostname = False
                ctx.verify_mode = ssl.CERT_NONE
                conn = http.client.HTTPSConnection(
                    "127.0.0.1", srv.port, context=ctx, timeout=10
                )
            else:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", srv.port, timeout=10
                )

            def once():
                conn.request("POST", "/v1/admit", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                return _json.loads(resp.read())

            for _ in range(30):
                once()
            import gc

            gc.collect()
            gc.freeze()  # keep warmup garbage out of the timed p99
            times = []
            for _ in range(int(os.environ.get("BENCH_SERVER_ITERS", "300"))):
                t0 = time.perf_counter()
                once()
                times.append(time.perf_counter() - t0)
            arr = np.array(times) * 1000
            return float(np.percentile(arr, 50)), float(np.percentile(arr, 99))
        finally:
            srv.stop()
            mb.stop()


def bench_batch1m() -> dict:
    """BASELINE config 'mesh': 1M admission-review batch streamed through
    review_batch in device-sized chunks (the streaming-webhook shape)."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    n_templates = int(os.environ.get("BENCH_TEMPLATES_1M", "10"))
    n_reviews = int(os.environ.get("BENCH_REVIEWS", "1000000"))
    chunk = int(os.environ.get("BENCH_CHUNK", "65536"))
    templates, constraints = make_templates(n_templates)
    c = Client(driver=TpuDriver())
    for t in templates:
        c.add_template(t)
    for cons in constraints:
        c.add_constraint(cons)
    pods = make_pods(min(n_reviews, 4096), seed=5)
    reqs = []
    for i in range(len(pods)):
        p = pods[i]
        reqs.append({
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "object": p,
        })
    driver = c.driver

    def batch_of(start, n):
        return [reqs[(start + j) % len(reqs)] for j in range(n)]

    # warm with the exact batch sizes the timed loop dispatches (full chunk
    # + the final partial chunk) so no XLA compile lands in the timed region
    driver.review_batch(batch_of(0, min(chunk, n_reviews)))
    tail = n_reviews % chunk
    if tail and n_reviews > chunk:
        driver.review_batch(batch_of(0, tail))
    t0 = time.time()
    done = 0
    while done < n_reviews:
        n = min(chunk, n_reviews - done)
        driver.review_batch(batch_of(done, n))
        done += n
    dur = time.time() - t0
    rate = n_reviews / dur
    log(f"batch1m: {n_reviews} reviews x {n_templates} constraints in "
        f"{dur:.1f}s ({rate:.0f} reviews/s)")
    return {
        "metric": f"streamed admission reviews/sec ({n_templates} constraints, chunk {chunk})",
        "value": round(rate, 1),
        "unit": "reviews/s",
        "vs_baseline": 0,
    }


def bench_ingest() -> dict:
    """Template-ingest storm with interleaved reviews under async compile.

    TWO traffic shapes (reference contract: ingest never degrades
    admission, pkg/controller/constrainttemplate/stats_reporter.go:33-37):
    - repeat-content: ONE fixed request interleaved with every install —
      the replica/retry-storm shape, served by the whole-request memo
      with change-log repair.
    - unique-content: a DISTINCT object per interleaved review (the shape
      the r4 verdict demanded) — memo never hits; served by the
      incremental host-side numpy mask (ops/npside.py) with the exact
      interpreter render on positives.
    """
    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    n_templates = int(os.environ.get("BENCH_INGEST_TEMPLATES", "500"))
    templates, constraints = make_templates(n_templates)
    pod = make_pods(1, seed=3, violation_rate=1.0)[0]
    req = {
        "uid": "u",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": pod["metadata"]["name"],
        "namespace": pod["metadata"]["namespace"],
        "operation": "CREATE",
        "userInfo": {"username": "bench"},
        "object": pod,
    }
    # unique-content traffic: compliant unique pods (clusters converge to
    # compliance; violating requests additionally pay the per-violation
    # interpreter render, reported separately below)
    upods = make_pods(n_templates, seed=29, violation_rate=0.0)
    vpods = make_pods(64, seed=31, violation_rate=1.0)

    def upod_req(p, i):
        return {
            "uid": f"u{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "bench"},
            "object": p,
        }

    c = Client(driver=TpuDriver(async_compile=True))
    # production webhook processes freeze long-lived state out of the
    # cyclic GC and take the collector off the admission path entirely
    # (webhook/server.py start(): freeze + disable + background sweeps);
    # the storm mirrors that policy or collections land in its p99
    import gc

    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return _bench_ingest_storm(
            c, templates, constraints, req, upods, upod_req, vpods,
            n_templates,
        )
    finally:
        # a mid-storm exception must not leave the collector off for
        # every later folded config (main() swallows and continues)
        gc.enable()
        gc.unfreeze()
        c.driver._compiler.stop()


def _bench_ingest_storm(c, templates, constraints, req, upods, upod_req,
                        vpods, n_templates):
    import numpy as np

    lat, ulat, waits, evals = [], [], [], []
    t0 = time.time()
    for i, (t, k) in enumerate(zip(templates, constraints)):
        c.add_template(t)
        c.add_constraint(k)
        s = time.perf_counter()
        c.review(req)  # repeat content: memo + change-log repair
        lat.append(time.perf_counter() - s)
        s = time.perf_counter()
        c.review(upod_req(upods[i], i))  # unique content: np mask serve
        ulat.append(time.perf_counter() - s)
        stats = getattr(c.driver, "last_review_stats", {})
        waits.append(stats.get("lock_wait_ms", 0.0))
        evals.append(stats.get("eval_ms", 0.0))
    storm_s = time.time() - t0
    c.driver.wait_ready(timeout=600.0)
    ready_s = time.time() - t0
    # violating unique requests at full install (every render is a real
    # violation: the exactness filter can't be cheated)
    vlat = []
    for i, p in enumerate(vpods):
        s = time.perf_counter()
        c.review(upod_req(p, 10_000 + i))
        vlat.append(time.perf_counter() - s)
    arr = np.array(lat) * 1000
    uarr = np.array(ulat) * 1000
    varr = np.array(vlat) * 1000
    p50 = float(np.percentile(arr, 50))
    p99 = float(np.percentile(arr, 99))
    u50 = float(np.percentile(uarr, 50))
    u99 = float(np.percentile(uarr, 99))
    w50 = float(np.percentile(np.array(waits), 50))
    e50 = float(np.percentile(np.array(evals), 50))
    w99 = float(np.percentile(np.array(waits), 99))
    e99 = float(np.percentile(np.array(evals), 99))
    log(f"ingest storm: {n_templates} templates in {storm_s:.1f}s "
        f"(device-ready at {ready_s:.1f}s); repeat-content p50={p50:.2f}ms "
        f"p99={p99:.2f}ms; UNIQUE-content p50={u50:.2f}ms p99={u99:.2f}ms "
        f"(lock-wait p50 {w50:.2f}/p99 {w99:.2f}ms, "
        f"eval p50 {e50:.2f}/p99 {e99:.2f}ms); violating-unique "
        f"p50={float(np.percentile(varr, 50)):.2f}ms "
        f"p99={float(np.percentile(varr, 99)):.2f}ms")
    return {
        "metric": f"ingest-to-first-eval p50 ({n_templates}-template storm, async compile)",
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": 0,
        "p99_ms": round(p99, 3),
        "unique_p50_ms": round(u50, 3),
        "unique_p99_ms": round(u99, 3),
        "violating_unique_p50_ms": round(float(np.percentile(varr, 50)), 3),
        "violating_unique_p99_ms": round(float(np.percentile(varr, 99)), 3),
        "queue_wait_p50_ms": round(w50, 3),
        "eval_p50_ms": round(e50, 3),
    }


def bench_render() -> dict:
    """Compiled violation rendering (ISSUE 4): violating-unique admission
    latency at full install — the deny path, where every flagged cell
    must produce its message — plus the raw render throughput and the
    plan-tier cell mix.  Same traffic shape as the ingest config's
    violating phase, isolated from the storm so the number measures
    rendering, not compile contention."""
    import gc

    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.metrics.views import global_registry
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    n_templates = int(os.environ.get("BENCH_RENDER_TEMPLATES", "500"))
    templates, constraints = make_templates(n_templates)
    c = Client(driver=TpuDriver())
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    vpods = make_pods(64, seed=31, violation_rate=1.0)

    def req(p, i):
        return {
            "uid": f"u{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "bench"},
            "object": p,
        }

    def tier_counts():
        out = {"static": 0.0, "slots": 0.0, "interp": 0.0}
        try:
            for key, v in global_registry().view_rows(
                "render_cells_total"
            ).items():
                if key and key[0] in out:
                    out[key[0]] += v
        except Exception:
            # best-effort bench telemetry: a registry shape change costs
            # the tier breakdown, not the run — but say so in the record
            out["error"] = "render_cells_total unavailable"
        return out

    c.review(req(make_pods(1, seed=9, violation_rate=1.0)[0], 1))  # warm
    # the counter is process-global and cumulative: snapshot it so the
    # reported plan mix covers THIS config's cells only (under
    # BENCH_CONFIG=all several earlier configs render too)
    tiers0 = tier_counts()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        # three rounds of fresh unique pods; the reported p50 is the best
        # round — pure host work, so the minimum is the true cost and
        # everything above it is scheduler noise (same convention as
        # calibrate_routing's host-path measurements)
        rounds = []
        cells, render_ms = 0.0, 0.0
        for r, pods in enumerate(
            (vpods, make_pods(64, seed=33, violation_rate=1.0),
             make_pods(64, seed=35, violation_rate=1.0))
        ):
            lat = []
            for i, p in enumerate(pods):
                s = time.perf_counter()
                c.review(req(p, (r + 1) * 10_000 + i))
                lat.append((time.perf_counter() - s) * 1e3)
                st = c.driver.last_render_stats
                cells += st.get("cells", 0.0)
                render_ms += (
                    st.get("plan_ms", 0.0) + st.get("interp_ms", 0.0)
                )
            rounds.append(np.array(lat))
    finally:
        gc.enable()
        gc.unfreeze()
    arr = min(rounds, key=lambda a: float(np.percentile(a, 50)))
    p50 = float(np.percentile(arr, 50))
    tiers = {
        k: v - tiers0.get(k, 0.0) for k, v in tier_counts().items()
    }
    planned = tiers["static"] + tiers["slots"]
    total = planned + tiers["interp"]
    cells_per_s = cells / (render_ms / 1e3) if render_ms else 0.0
    log(
        f"render: violating-unique p50={p50:.2f}ms "
        f"p99={float(np.percentile(arr, 99)):.2f}ms; "
        f"{cells:.0f} cells in {render_ms:.1f}ms "
        f"({cells_per_s:,.0f} cells/s); plan mix "
        f"static={tiers['static']:.0f} slots={tiers['slots']:.0f} "
        f"interp={tiers['interp']:.0f}"
        + (f" ({planned / total:.1%} compiled)" if total else "")
    )
    return {
        "metric": f"violating-unique admission p50 "
                  f"({n_templates} templates, compiled render)",
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": 0,
        "ingest_violating_unique_p50_ms": round(p50, 3),
        "ingest_violating_unique_p99_ms": round(
            float(np.percentile(arr, 99)), 3),
        "render_cells_per_s": round(cells_per_s, 1),
        "render_cells": cells,
        "render_plan_fraction": round(planned / total, 4) if total else None,
        "render_cells_static": tiers["static"],
        "render_cells_slots": tiers["slots"],
        "render_cells_interp": tiers["interp"],
    }


def bench_slo() -> dict:
    """Cost-attribution overhead (ISSUE 5): the violating-unique
    admission p50 with the cost ledger enabled vs disabled, interleaved
    round-robin so co-tenant noise hits both arms alike.  Also exercises
    the SLO collect hook + OpenMetrics exemplar rendering once so the
    artifact records that the whole attribution surface works."""
    import gc

    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.metrics.views import Registry
    from gatekeeper_tpu.metrics.exporter import render_openmetrics
    from gatekeeper_tpu.obs import costs as obscosts
    from gatekeeper_tpu.obs import slo as obsslo
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    n_templates = int(os.environ.get("BENCH_SLO_TEMPLATES", "500"))
    templates, constraints = make_templates(n_templates)
    c = Client(driver=TpuDriver())
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)

    def req(p, i):
        return {
            "uid": f"u{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "bench"},
            "object": p,
        }

    ledger = obscosts.get_ledger()
    was_enabled = ledger.enabled
    ledger.clear()
    c.review(req(make_pods(1, seed=9, violation_rate=1.0)[0], 1))  # warm
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        # 3 interleaved rounds per arm, fresh unique pods every batch so
        # the request memo never serves either arm; best-round p50 per
        # arm (host work: the minimum is the true cost, the rest is
        # scheduler noise — the render config's convention)
        p50s = {False: [], True: []}
        seq = 0
        for r in range(3):
            for enabled in (False, True):
                ledger.enabled = enabled
                pods = make_pods(
                    64, seed=101 + 10 * r + enabled, violation_rate=1.0
                )
                lat = []
                for p in pods:
                    seq += 1
                    s = time.perf_counter()
                    c.review(req(p, seq))
                    lat.append((time.perf_counter() - s) * 1e3)
                p50s[enabled].append(float(np.percentile(lat, 50)))
    finally:
        gc.enable()
        gc.unfreeze()
        ledger.enabled = was_enabled
    p50_off = min(p50s[False])
    p50_on = min(p50s[True])
    overhead_pct = (
        (p50_on - p50_off) / p50_off * 100.0 if p50_off else 0.0
    )
    # attribution sanity on the same traffic: the ledger saw every
    # template, the top-K export caps labels, exemplars render
    snap = ledger.snapshot(top=10)
    reg = Registry()
    obscosts.collect_hook(reg)
    obsslo.collect_hook(reg)
    om = render_openmetrics(reg)
    exporting_ok = (
        om.endswith("# EOF\n")
        and len(snap["templates"]) == 10
        and bool(reg.view_rows("slo_burn_rate"))
    )
    ledger.clear()
    log(
        f"slo: violating-unique p50 ledger-off={p50_off:.2f}ms "
        f"on={p50_on:.2f}ms overhead={overhead_pct:+.2f}%; "
        f"window tracked {snap['tracked_templates']} templates; "
        f"export {'ok' if exporting_ok else 'BROKEN'}"
    )
    return {
        "metric": f"cost-attribution overhead on violating-unique "
                  f"admission p50 ({n_templates} templates)",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "vs_baseline": 0,
        "cost_attribution_overhead_pct": round(overhead_pct, 2),
        "ingest_p50_ms_ledger_off": round(p50_off, 3),
        "ingest_p50_ms_ledger_on": round(p50_on, 3),
        "cost_tracked_templates": snap["tracked_templates"],
        "cost_export_ok": exporting_ok,
    }


def bench_restart() -> dict:
    """Warm-restart recovery (SURVEY §5.4; the reference rebuilds all
    derived state on boot in seconds, pkg/controller/controller.go:124-126).

    Two fresh subprocesses over the synthetic corpus, sharing the
    persistent caches: the first populates the XLA-compile AND
    serialized-executable (AOT) caches; the second is the measured warm
    restart — process start to first full capped sweep.  The AOT cache is
    what removes the fused programs' TRACE time, which the XLA compile
    cache alone cannot save."""
    import subprocess

    n_t = int(os.environ.get("BENCH_RESTART_TEMPLATES",
                             os.environ.get("BENCH_TEMPLATES", "500")))
    n_r = int(os.environ.get("BENCH_RESTART_RESOURCES",
                             os.environ.get("BENCH_RESOURCES", "100000")))
    code = f"N_T, N_R = {n_t}, {n_r}\n" + r"""
import json, sys, time
sys.path.insert(0, ".")
from gatekeeper_tpu.ops.xlacache import enable_caches
enable_caches()
from gatekeeper_tpu.util.synthetic import make_pods, make_templates
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.ops.driver import TpuDriver
# corpus generation is bench-harness cost, not restart cost (a real
# restart replays existing objects from the API server); the clock
# starts at the replay
templates, constraints = make_templates(N_T)
pods = make_pods(N_R, 1)
t0 = time.time()
client = Client(driver=TpuDriver())
for t in templates:
    client.add_template(t)
for c in constraints:
    client.add_constraint(c)
t_tmpl = time.time()
for p in pods:
    client.add_data(p)
t_built = time.time()
res, _totals = client.audit_capped(20)
t_ready = time.time()
n = len(res.results())
print(json.dumps({
    "template_ingest_s": round(t_tmpl - t0, 3),
    "data_replay_s": round(t_built - t_tmpl, 3),
    "first_sweep_s": round(t_ready - t_built, 3),
    "ready_s": round(t_ready - t0, 3),
    "violations": n,
    "device": client.driver.device_info(),
}))
"""
    out = {}
    for label in ("populate", "warm"):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=1200,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            log(f"restart[{label}] failed: {proc.stderr[-500:]}")
            raise RuntimeError("restart bench subprocess failed")
        line = proc.stdout.strip().splitlines()[-1]
        out[label] = json.loads(line)
        log(f"restart[{label}]: {out[label]} (wall {time.time()-t0:.1f}s)")
    warm = out["warm"]
    return {
        "metric": f"warm-restart to first full sweep ({n_t}x{n_r})",
        "value": warm["ready_s"],
        "unit": "s",
        "vs_baseline": 0,
        "template_ingest_s": warm["template_ingest_s"],
        "data_replay_s": warm["data_replay_s"],
        "first_sweep_s": warm["first_sweep_s"],
        "populate_ready_s": out["populate"]["ready_s"],
        **device_stamp(warm["device"]),
    }


def bench_warm_resume() -> dict:
    """Warm resume via the state snapshot subsystem (docs/snapshots.md,
    ISSUE 3): restart-to-first-completed-capped-sweep with a snapshot
    (restore + RV delta resync) vs the cold rebuild (relist + intern +
    pack), both in fresh subprocesses sharing warm XLA/AOT caches so the
    delta is exactly what the snapshot saves.  The warm phase re-packs
    only the churned rows — `warm_repacked_rows` in the artifact proves
    the delta-resync-only claim."""
    import shutil
    import subprocess

    n_t = int(os.environ.get("BENCH_WARM_TEMPLATES",
                             os.environ.get("BENCH_TEMPLATES", "500")))
    n_r = int(os.environ.get("BENCH_WARM_RESOURCES",
                             os.environ.get("BENCH_RESOURCES", "100000")))
    # churn while "down" defaults to 0.2% of the corpus, capped at the
    # driver's delta-sweep row bound so the restored basis serves the
    # first sweep (a pod reschedule is seconds; beyond the bound the
    # restore still works, the first sweep is just a full dispatch)
    churn = int(os.environ.get(
        "BENCH_WARM_CHURN", str(max(1, min(200, n_r // 500)))))
    snap_dir = os.environ.get(
        "GK_SNAPSHOT_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".snapshots-bench"),
    )
    shutil.rmtree(snap_dir, ignore_errors=True)
    code = (
        f"N_T, N_R, CHURN = {n_t}, {n_r}, {churn}\n"
        f"SNAP = {snap_dir!r}\n"
        + r"""
import json, os, sys, time
sys.path.insert(0, ".")
MODE = os.environ["BENCH_WARM_MODE"]  # populate | cold | warm
from gatekeeper_tpu.ops.xlacache import enable_caches
enable_caches()
from gatekeeper_tpu.util.synthetic import make_pods, make_templates
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.ops.driver import TpuDriver

# the cluster: deterministic corpus + creation order, so every phase's
# kube assigns identical resourceVersions (corpus build is harness cost)
templates, constraints = make_templates(N_T)
kube = InMemoryKube()
for p in make_pods(N_R, 1):
    kube.create(p)
if MODE == "warm":
    # churn while "down": CHURN pods move their RV past the snapshot's
    # (an image retag — content change without widening any padded dim)
    gvk = ("", "v1", "Pod")
    for obj in kube.list(gvk)[:CHURN]:
        ctrs = obj.get("spec", {}).get("containers") or [{}]
        ctrs[0]["image"] = str(ctrs[0].get("image", "")) + "-churned"
        kube.update(obj)

out = {"mode": MODE}
t0 = time.time()
client = Client(driver=TpuDriver())
# pin the sweep sharding OFF the mesh so multi-device hosts measure the
# same thing (the snapshot basis is width-stamped: a width-drifted
# restore would drop it and turn the warm measurement into a cold one)
client.driver.set_mesh(False)
if MODE in ("populate", "cold"):
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    t_tmpl = time.time()
    for gvk in kube.list_gvks():
        for obj in kube.list(gvk):
            client.add_data(obj)
    t_built = time.time()
    res, _totals = client.audit_capped(20)
    t_ready = time.time()
    out.update({
        "template_ingest_s": round(t_tmpl - t0, 3),
        "data_replay_s": round(t_built - t_tmpl, 3),
        "first_sweep_s": round(t_ready - t_built, 3),
        "ready_s": round(t_ready - t0, 3),
        "violations": len(res.results()),
    })
    if MODE == "populate":
        from gatekeeper_tpu.snapshot import Snapshotter
        path = Snapshotter(client, SNAP).write_once()
        if path is None:
            raise RuntimeError("snapshot write failed")
        out["snapshot_bytes"] = sum(
            os.path.getsize(os.path.join(path, f)) for f in os.listdir(path))
else:
    from gatekeeper_tpu.ops.auditpack import AuditPackCache
    from gatekeeper_tpu.snapshot import SnapshotLoader
    packs = {"n": 0}
    orig = AuditPackCache._pack_rows
    def counting(self, drv, rows, *a, **k):
        packs["n"] += len(rows)
        return orig(self, drv, rows, *a, **k)
    AuditPackCache._pack_rows = counting
    loader = SnapshotLoader(SNAP)
    outcome = loader.restore(client, kube)
    t_restored = time.time()
    res, _totals = client.audit_capped(20)
    t_ready = time.time()
    stats = dict(client.driver.last_sweep_stats)
    out.update({
        "restore_outcome": outcome,
        "delta_restored": loader.delta_restored,
        "resync": loader.stats,
        "restore_s": round(t_restored - t0, 3),
        "first_sweep_s": round(t_ready - t_restored, 3),
        "first_sweep_delta_rows": stats.get("delta_rows"),
        "ready_s": round(t_ready - t0, 3),
        "violations": len(res.results()),
        "repacked_rows": packs["n"],
    })
out["device"] = client.driver.device_info()
print(json.dumps(out))
"""
    )
    out = {}
    for mode in ("populate", "cold", "warm"):
        t0 = time.time()
        env = dict(os.environ, BENCH_WARM_MODE=mode)
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=1800, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            log(f"warm_resume[{mode}] failed: {proc.stderr[-500:]}")
            raise RuntimeError("warm_resume bench subprocess failed")
        out[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
        log(f"warm_resume[{mode}]: {out[mode]} (wall {time.time()-t0:.1f}s)")
    cold, warm = out["cold"], out["warm"]
    if warm["violations"] != cold["violations"]:
        log(
            f"warm_resume: violation mismatch cold={cold['violations']} "
            f"warm={warm['violations']}"
        )
    speedup = (
        round(cold["ready_s"] / warm["ready_s"], 2)
        if warm["ready_s"] > 0 else None
    )
    return {
        "metric": f"warm-resume speedup to first sweep ({n_t}x{n_r})",
        "value": speedup,
        "unit": "x",
        "vs_baseline": 0,
        "warm_resume_speedup": speedup,
        "warm_resume_ready_s": warm["ready_s"],
        "warm_resume_first_sweep_ms": round(warm["first_sweep_s"] * 1e3, 1),
        "warm_resume_restore_s": warm["restore_s"],
        "warm_resume_repacked_rows": warm["repacked_rows"],
        "warm_resume_resync": warm["resync"],
        "warm_resume_outcome": warm["restore_outcome"],
        "warm_resume_delta_restored": warm.get("delta_restored"),
        "warm_resume_delta_rows": warm.get("first_sweep_delta_rows"),
        "warm_resume_violations_match": warm["violations"] == cold["violations"],
        "cold_ready_s": cold["ready_s"],
        "cold_first_sweep_s": cold["first_sweep_s"],
        "snapshot_bytes": out["populate"].get("snapshot_bytes"),
        "churned_rows": churn,
        **device_stamp(warm["device"]),
    }


def bench_curve() -> dict:
    """The reference's constraint-count scaling sweep
    (policy_benchmark_test.go:269: N in {5,10,50,100,200,1000,2000}):
    admission-handler latency per N through the production hybrid driver.
    Exposes where recompile/padding buckets would bite."""
    import numpy as np

    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.kube.inmem import InMemoryKube
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates
    from gatekeeper_tpu.webhook import ValidationHandler

    counts = [int(x) for x in os.environ.get(
        "BENCH_CURVE", "5,10,50,100,200,1000,2000").split(",")]
    # two regimes per N: UNIQUE-content requests (true evaluation scaling —
    # the whole-request memo cannot hit) and REPEAT-content requests (what
    # replica/retry storms look like; served by the request memo)
    uniq_pods = make_pods(4096, seed=9, violation_rate=0.0)

    def req_for(pod):
        return {
            "uid": "u", "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": pod["metadata"]["name"],
            "namespace": pod["metadata"]["namespace"],
            "operation": "CREATE", "userInfo": {"username": "bench"},
            "object": pod,
        }

    req = req_for(uniq_pods[0])
    curve = {}
    curve_memo = {}
    curve_device = {}
    curve_interp = {}
    curve_np = {}
    routes = {}
    routez_wins = {}
    cal_logged = None
    for n in counts:
        templates, constraints = make_templates(n)
        c = Client(driver=TpuDriver())
        for t, k in zip(templates, constraints):
            c.add_template(t)
            c.add_constraint(k)
        kube = InMemoryKube()
        # every review namespace must exist: a missing namespace sends the
        # request down the error path (LookupError + traceback logging),
        # and the curve would measure THAT instead of policy evaluation
        # (the reference benchmark's fakeNsGetter always succeeds,
        # policy_benchmark_test.go:52-66)
        for ns_name in {p["metadata"]["namespace"] for p in uniq_pods}:
            kube.create({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": ns_name}})
        handler = ValidationHandler(c, kube=kube)
        iters = max(10, min(100, 20000 // max(n, 1)))
        for _ in range(3):
            handler.handle(req)
        # startup calibration: the measured cost model picks the route
        cal = c.driver.calibrate_routing()
        if cal and cal_logged is None:
            cal_logged = {k: round(v, 3) for k, v in cal.items()}
            log(f"routing calibration: {cal_logged}")
        routes[n] = c.driver._route_eval(n)
        # route explainability (ISSUE 13): the decision just recorded
        # lands in this driver's ledger — keep its per-shape win row so
        # the artifact carries the ledger's view of the frontier, not
        # just the return value
        routez_wins[n] = next(
            (
                row["wins"]
                for row in c.driver.route_ledger.tier_wins()
                if row["per_review_cells"] == n and row["n_reviews"] == 1
            ),
            {},
        )

        def series(offset, forced=None):
            # distinct pod offset per series: unique content must not hit
            # request-memo entries another series populated
            saved = c.driver.DEVICE_MIN_CELLS
            cal_saved = c.driver._route_cal
            np_saved = c.driver.np_serve_enabled
            if forced == "interp":
                c.driver.DEVICE_MIN_CELLS = 1 << 30
                c.driver._route_cal = None
                c.driver.np_serve_enabled = False
            elif forced == "np":
                c.driver.DEVICE_MIN_CELLS = 1 << 30
                c.driver._route_cal = None
                c.driver.NP_MIN_CELLS = 0
                c.driver.np_serve_enabled = True
            elif forced == "device":
                c.driver.DEVICE_MIN_CELLS = 0
            ts = []
            try:
                for j in range(iters):
                    r = req_for(uniq_pods[(offset + j) % len(uniq_pods)])
                    t0 = time.perf_counter()
                    handler.handle(r)
                    ts.append(time.perf_counter() - t0)
            finally:
                c.driver.DEVICE_MIN_CELLS = saved
                c.driver._route_cal = cal_saved
                c.driver.np_serve_enabled = np_saved
                c.driver.NP_MIN_CELLS = TpuDriver.NP_MIN_CELLS
            return float(np.percentile(np.array(ts) * 1000, 50))

        # adaptive (production default), then the three forced paths so
        # the crossovers are visible in the artifact
        p50 = series(7)
        curve[n] = round(p50, 3)
        curve_interp[n] = round(series(1100, "interp"), 3)
        curve_np[n] = round(series(3300, "np"), 3)
        curve_device[n] = round(series(2200, "device"), 3)
        # repeat-content: identical object, fresh uid (request-memo hits)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            handler.handle(req)
            ts.append(time.perf_counter() - t0)
        m50 = float(np.percentile(np.array(ts) * 1000, 50))
        curve_memo[n] = round(m50, 3)
        log(f"curve N={n}: adaptive p50 {p50:.2f}ms (route={routes[n]}), "
            f"interp {curve_interp[n]:.2f}ms, np {curve_np[n]:.2f}ms, "
            f"device {curve_device[n]:.2f}ms, "
            f"repeat(memo) {m50:.2f}ms ({iters} iters)")
    # route-accuracy audit: at every N the adaptive route should name the
    # measured-fastest forced series (the r4 verdict's mis-route demand)
    agree = sum(
        1 for n in counts
        if routes[n] == min(
            [(curve_interp[n], "interp"), (curve_np[n], "np"),
             (curve_device[n], "device")]
        )[1]
    )
    log(f"curve route accuracy: {agree}/{len(counts)} Ns picked the "
        f"measured-fastest path")
    # the exact shape frontier where the compiled tier starts winning
    # (ISSUE 13: consumed from the route ledger rather than inferred) —
    # None means the compiled tier lost at every measured shape
    sorted_ns = sorted(counts)
    device_ns = [n for n in sorted_ns if routes[n] == "device"]
    frontier = {
        "device_first_cells": device_ns[0] if device_ns else None,
        "host_last_cells": max(
            (n for n in sorted_ns if routes[n] != "device"), default=None
        ),
    }
    log(f"curve route frontier: {frontier} (ledger wins: {routez_wins})")
    return {
        "metric": "admission handler p50 vs constraint count (unique-content)",
        "value": curve[max(counts)],
        "unit": "ms",
        "vs_baseline": 0,
        "curve_p50_ms": curve,
        "curve_repeat_p50_ms": curve_memo,
        "curve_interp_p50_ms": curve_interp,
        "curve_np_p50_ms": curve_np,
        "curve_device_p50_ms": curve_device,
        "curve_route": routes,
        "curve_route_accuracy": f"{agree}/{len(counts)}",
        "curve_routez_wins": routez_wins,
        "curve_route_frontier": frontier,
        "routing_calibration": cal_logged,
    }


def bench_mesh() -> dict:
    """Multi-chip scaling of the device sweep, measured on a virtual
    8-device CPU mesh in a subprocess (the bench env exposes ONE real
    chip).  Virtual devices share one host's cores, so this validates the
    sharded path's overhead/correctness at scale rather than wall-clock
    speedup; the scaling factor is reported as measured."""
    import subprocess

    n_t = int(os.environ.get("BENCH_MESH_TEMPLATES", "48"))
    n_r = int(os.environ.get("BENCH_MESH_ROWS", "8192"))
    code = f"N_T, N_R = {n_t}, {n_r}\n" + r"""
import time, json, sys
import jax, numpy as np
import jax.numpy as jnp
sys.path.insert(0, ".")
from gatekeeper_tpu.util.synthetic import build_driver

client = build_driver(N_T, N_R)
driver = client.driver
out = {}
for mesh_on in (False, True):
    # set_mesh invalidates every topology-keyed cache (placements, sweep
    # cache, delta basis) in one call
    driver.set_mesh(mesh_on)
    client.audit_capped(20)  # compile + warm
    # honest steady state: invalidate the sweep cache, keep executables
    ts = []
    for i in range(3):
        driver._audit_cache = None
        driver._delta_state = None
        t0 = time.perf_counter()
        client.audit_capped(20)
        ts.append(time.perf_counter() - t0)
    out["mesh" if mesh_on else "single"] = min(ts)

# device-only scaling series: the fused packed-only kernel at 1/2/4/8
# shards, N chained executions per dispatch (optimization_barrier per
# iteration so XLA cannot CSE), median per-sweep time.  Virtual devices
# share one host's cores, so the honest signal is per-shard WORK (rows
# per device falls ~1/N) plus the measured wall series as context.
from gatekeeper_tpu.parallel.mesh import audit_mesh, shard_review_side

driver.set_mesh(False)
with driver._lock:
    K = driver._audit_topk(20)
    fn, _o, cp, gparams, _crow = driver._audit_inputs(K)
raw = fn.__wrapped__
ap = driver._audit_pack
N_REP = 8
series = {}
shard_rows = {}
for k in (1, 2, 4, 8):
    mesh = audit_mesh(k)
    rv_p, cols_p, target = shard_review_side(mesh, ap.capacity, ap.rp, ap.cols)
    with driver._lock:
        driver._cs_device_cache = None
        cs_p, gp_p = driver._constraint_device_side(cp.arrays, gparams, None, mesh)

    def rep_n(rv, cs, cols, gp):
        def body(carry, _):
            a, b, c, d = jax.lax.optimization_barrier((rv, cs, cols, gp))
            packed = raw(a, b, c, d)
            return carry + packed[0, 0], None
        c0, _ = jax.lax.scan(body, jnp.int32(0), None, length=N_REP)
        return c0

    with mesh:
        rj = jax.jit(rep_n)
        rj(rv_p, cs_p, cols_p, gp_p).block_until_ready()  # compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            rj(rv_p, cs_p, cols_p, gp_p).block_until_ready()
            ts.append(time.perf_counter() - t0)
    series[k] = float(np.median(ts)) / N_REP * 1e3
    shard_rows[k] = target // k
out["device_scaling_ms"] = series
out["rows_per_shard"] = shard_rows
print(json.dumps(out))
"""
    from gatekeeper_tpu.parallel.mesh import virtual_mesh_env

    env = virtual_mesh_env(8)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh subprocess failed: {proc.stderr[-2000:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    factor = data["single"] / data["mesh"] if data["mesh"] else 0.0
    log(f"mesh scaling (virtual 8-dev CPU, 48x8192): single {data['single']*1000:.0f}ms "
        f"mesh {data['mesh']*1000:.0f}ms -> x{factor:.2f} "
        f"(virtual devices share one host: overhead check, not speedup)")
    scaling = data.get("device_scaling_ms", {})
    if scaling:
        log("mesh device-only series (N-rep chained, virtual CPU devices): "
            + ", ".join(f"{k} shard(s) {v:.1f}ms"
                        f" ({data['rows_per_shard'][k]} rows/shard)"
                        for k, v in sorted(scaling.items(),
                                           key=lambda kv: int(kv[0]))))
    return {
        "metric": "virtual 8-device mesh sweep vs single device",
        "value": round(factor, 3),
        "unit": "x",
        "vs_baseline": 0,
        "single_s": round(data["single"], 4),
        "mesh_s": round(data["mesh"], 4),
        "device_scaling_ms": {
            str(k): round(v, 3) for k, v in scaling.items()
        },
        "rows_per_shard": data.get("rows_per_shard", {}),
    }


def bench_mesh_curve() -> dict:
    """The production sharded audit across mesh widths 1/2/4/8 on the
    virtual CPU mesh (subprocess; the bench env exposes ONE real chip),
    recorded as MULTICHIP_r06.  Per width: interpreter-oracle parity on
    a moderate corpus (byte-identical verdicts + rendered messages +
    totals), warm full-resweep wall time and rows-per-shard at the
    full-scale corpus (the ~linear per-shard work signal — virtual
    devices share one host's cores, so wall time is an overhead check,
    not a speedup claim), and the O(churn) delta check: 200 churned rows
    dispatch 200 rows, never the cluster."""
    import subprocess

    n_t = int(os.environ.get("BENCH_MESH_CURVE_TEMPLATES", "48"))
    n_r = int(os.environ.get("BENCH_MESH_CURVE_ROWS", "8192"))
    p_t = int(os.environ.get("BENCH_MESH_CURVE_PARITY_TEMPLATES", "12"))
    p_r = int(os.environ.get("BENCH_MESH_CURVE_PARITY_ROWS", "512"))
    churn = int(os.environ.get("BENCH_MESH_CURVE_CHURN", "200"))
    code = (
        f"N_T, N_R, P_T, P_R, CHURN = {n_t}, {n_r}, {p_t}, {p_r}, {churn}\n"
        + r"""
import json, sys, time
sys.path.insert(0, ".")
import numpy as np
from gatekeeper_tpu.util.synthetic import (
    audit_result_sig as sig, build_driver, build_oracle, make_pods,
)

WIDTHS = (1, 2, 4, 8)
PARITY_CAP = 4096  # above any per-constraint count: totals exact everywhere

# interpreter oracle on the parity corpus (build_oracle: own instance,
# same corpus and parity signature as the tool and the tests)
oracle = build_oracle(P_T, P_R)
oracle_r, oracle_t, _ = oracle.driver.audit_capped(PARITY_CAP)
oracle_sig = sig(oracle_r)

parity_client = build_driver(P_T, P_R)
curve_client = build_driver(N_T, N_R)
curve = {}
for w in WIDTHS:
    # parity against the interpreter oracle at this width
    pd = parity_client.driver
    pd.set_mesh(w > 1, width=w)
    got_r, got_t, _ = pd.audit_capped(PARITY_CAP)
    parity = sig(got_r) == oracle_sig and got_t == oracle_t

    # full-scale warm resweep + per-shard work at this width
    cd = curve_client.driver
    cd.set_mesh(w > 1, width=w)
    curve_client.audit_capped(20)  # compile + place + warm
    ts = []
    for _ in range(3):
        # honest steady state: drop the sweep cache and the delta basis,
        # keep placements and executables
        cd._audit_cache = None
        cd._delta_state = None
        t0 = time.perf_counter()
        curve_client.audit_capped(20)
        ts.append(time.perf_counter() - t0)
    stats = dict(cd.last_sweep_stats)
    # capacity-slab based at every width (driver emits it for width 1
    # too), so the parent's linearity check compares like with like
    rows_per_shard = int(stats["rows_per_shard"])

    # O(churn) delta under this width: in-place churn of CHURN objects
    curve_client.audit_capped(20)  # rebase the delta basis
    pods = make_pods(N_R, 1)[:CHURN]
    for p in pods:
        p["metadata"].setdefault("labels", {})["churn"] = f"w{w}"
        curve_client.add_data(p)
    t0 = time.perf_counter()
    curve_client.audit_capped(20)
    delta_s = time.perf_counter() - t0
    dstats = dict(cd.last_sweep_stats)

    curve[str(w)] = {
        "parity": bool(parity),
        "warm_full_resweep_s": round(min(ts), 4),
        "rows_per_shard": rows_per_shard,
        "shards": stats.get("shards"),
        "delta_rows_dispatched": dstats.get("delta_rows"),
        "delta_owning_shards": dstats.get("delta_shards"),
        "delta_sweep_s": round(delta_s, 4),
    }
print(json.dumps({"curve": curve}))
"""
    )
    from gatekeeper_tpu.parallel.mesh import virtual_mesh_env

    env = virtual_mesh_env(8)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh_curve subprocess failed: {proc.stderr[-2000:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    curve = data["curve"]
    all_parity = all(v["parity"] for v in curve.values())
    # rows_per_shard * width == slab-padded capacity: padding adds < width
    # rows total, so linear-within-padding is 0 <= excess < width
    linear = all(
        0 <= v["rows_per_shard"] * int(w) - curve["1"]["rows_per_shard"]
        < int(w)
        for w, v in curve.items()
    )
    for w, v in sorted(curve.items(), key=lambda kv: int(kv[0])):
        log(f"mesh_curve width {w}: parity={v['parity']} "
            f"resweep {v['warm_full_resweep_s']*1000:.0f}ms "
            f"{v['rows_per_shard']} rows/shard, delta "
            f"{v['delta_rows_dispatched']} rows "
            f"({v['delta_sweep_s']*1000:.0f}ms)")
    log(f"mesh_curve: parity_all={all_parity} rows_per_shard "
        f"linear={linear} (virtual devices share one host: per-shard "
        f"work is the scaling signal, wall time the overhead check)")
    out = {
        "metric": f"mesh width curve 1/2/4/8 (virtual CPU, {n_t}x{n_r})",
        "value": 1.0 if all_parity else 0.0,
        "unit": "parity",
        "vs_baseline": 0,
        "parity_all_widths": all_parity,
        "rows_per_shard_linear": linear,
        "templates": n_t,
        "rows": n_r,
        "churn_rows": churn,
        "curve": curve,
    }
    record = {
        "config": {
            "templates": n_t, "rows": n_r,
            "parity_templates": p_t, "parity_rows": p_r,
            "churn_rows": churn,
            "mesh": "virtual 8-device CPU (subprocess)",
        },
        "parity_all_widths": all_parity,
        "rows_per_shard_linear": linear,
        "curve": curve,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MULTICHIP_r06.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    log(f"mesh_curve recorded: {path}")
    return out


def bench_referential() -> dict:
    """Referential policies (ISSUE 14): the cross-resource join/aggregate
    kernel subsystem.  Subprocess on the virtual 8-device CPU mesh:

    - parity: a referential corpus (unique-key / required-reference /
      count-quota) audited at widths 1 and 4 must be BYTE-identical to
      the interpreter oracle (verdicts + rendered messages + totals),
      with GK_JOIN_ASSERT armed and every family served by a join plan
      (the `join_plan` route-ledger reason present, never interp
      fallback);
    - throughput: warm steady-state full join sweep wall time -> rows/s
      at the full-scale corpus;
    - delta locality: a CHURN-row batch rides the O(key-group) delta
      path — dispatch rows == dirty + affected readers — and the
      delta-vs-full speedup is recorded.

    Recorded as REF_r14.json."""
    import subprocess

    n_t = int(os.environ.get("BENCH_REF_TEMPLATES", "24"))
    n_r = int(os.environ.get("BENCH_REF_ROWS", "6000"))
    p_t = int(os.environ.get("BENCH_REF_PARITY_TEMPLATES", "6"))
    p_r = int(os.environ.get("BENCH_REF_PARITY_ROWS", "240"))
    churn = int(os.environ.get("BENCH_REF_CHURN", "20"))
    code = (
        f"N_T, N_R, P_T, P_R, CHURN = {n_t}, {n_r}, {p_t}, {p_r}, {churn}\n"
        + r"""
import json, sys, time
sys.path.insert(0, ".")
from gatekeeper_tpu.ops.driver import TpuDriver
TpuDriver.DELTA_MASK_WAIT_S = 300.0
from gatekeeper_tpu.util.synthetic import (
    audit_result_sig as sig, build_referential_driver,
    build_referential_oracle, make_referential_objects,
)
CAP = 4096

# --- parity at widths 1 and 4 vs the interpreter oracle ---
oracle = build_referential_oracle(P_T, P_R)
t0 = time.perf_counter()
oracle_r, oracle_t, _ = oracle.driver.audit_capped(CAP)
oracle_s = time.perf_counter() - t0
oracle_sig = sig(oracle_r)
parity = {}
for w in (1, 4):
    c = build_referential_driver(P_T, P_R)
    d = c.driver
    d.set_mesh(w > 1, width=w)
    res, tot, _ = d.audit_capped(CAP)
    st = dict(d.last_sweep_stats)
    counts = d.route_ledger.snapshot()["counts"]
    parity[str(w)] = {
        "parity": sig(res) == oracle_sig and tot == oracle_t,
        "join_plans": st.get("join_plans"),
        "join_plan_routed": any(
            k.endswith("|join_plan") for k in counts
        ),
    }

# --- full-scale join sweep throughput + delta locality ---
client = build_referential_driver(N_T, N_R)
d = client.driver
client.audit_capped(20)  # compile + place + index build
full_ts = []
for _ in range(3):
    d._audit_cache = None
    d._delta_state = None  # honest steady state; placements stay warm
    t0 = time.perf_counter()
    client.audit_capped(20)
    full_ts.append(time.perf_counter() - t0)
full_s = min(full_ts)
rows = d.last_sweep_stats["rows"]

client.audit_capped(20)  # rebase the delta basis + join index
objs = make_referential_objects(N_R, 1)
ingresses = [o for o in objs if o["kind"] == "Ingress"]
pods = [o for o in objs if o["kind"] == "Pod"
        and str(o["metadata"]["labels"]["team"]).startswith("team-")]

def churn_hosts(batch, tag):
    for o in batch:
        o = dict(o)
        o["spec"] = {"rules": [{"host": f"moved-{tag}-{o['metadata']['name']}.corp.io"}]}
        client.add_data(o)

def churn_neutral(batch, tag):
    # content churn that leaves every join key unchanged — the common
    # production case (status/annotation updates)
    for o in batch:
        o = dict(o)
        o["metadata"] = {**o["metadata"],
                         "annotations": {"touched": tag}}
        client.add_data(o)

# prime the delta executable's row-width bucket (one-time XLA compile,
# shared by every later churn batch of this magnitude)
churn_neutral(pods[:CHURN], "prime")
client.audit_capped(20)
assert d.last_sweep_stats.get("delta_rows") is not None, d.last_sweep_stats

# (a) NEUTRAL churn: keys unchanged -> zero affected readers, zero
# re-renders; the delta-vs-full dispatch win in its pure form
churn_neutral(pods[CHURN:2 * CHURN], "live")
t0 = time.perf_counter()
client.audit_capped(20)
neutral_s = time.perf_counter() - t0
nstats = dict(d.last_sweep_stats)

# (b) KEY churn: hosts move -> the old/new key groups' readers
# co-dispatch and re-render.  Compared against a FULL sweep doing the
# SAME work (same churn magnitude, basis dropped), since both arms pay
# the interpreter re-render of the legitimately-invalidated cells.
churn_hosts(ingresses[:CHURN], "key")
t0 = time.perf_counter()
client.audit_capped(20)
key_delta_s = time.perf_counter() - t0
kstats = dict(d.last_sweep_stats)

churn_hosts(ingresses[CHURN:2 * CHURN], "full")
d._audit_cache = None
d._delta_state = None
t0 = time.perf_counter()
client.audit_capped(20)
key_full_s = time.perf_counter() - t0

print(json.dumps({
    "parity": parity,
    "oracle_sweep_s": round(oracle_s, 4),
    "full_sweep_s": round(full_s, 4),
    "rows": rows,
    "join_rows_per_s": round(rows / full_s, 1),
    "delta_neutral_s": round(neutral_s, 4),
    "delta_neutral_rows": nstats.get("delta_rows"),
    "delta_neutral_affected": nstats.get("join_affected_rows"),
    "delta_vs_full_speedup": round(full_s / max(neutral_s, 1e-9), 2),
    "delta_keychurn_s": round(key_delta_s, 4),
    "delta_keychurn_rows": kstats.get("delta_rows"),
    "join_affected_rows": kstats.get("join_affected_rows"),
    "full_after_keychurn_s": round(key_full_s, 4),
    "keychurn_speedup": round(key_full_s / max(key_delta_s, 1e-9), 2),
}))
"""
    )
    from gatekeeper_tpu.parallel.mesh import virtual_mesh_env

    env = virtual_mesh_env(8)
    env["GK_JOIN_ASSERT"] = "1"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"referential subprocess failed: {proc.stderr[-2000:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    parity_all = all(
        v["parity"] and v["join_plan_routed"]
        for v in data["parity"].values()
    )
    log(f"referential: parity_all={parity_all} "
        f"join sweep {data['full_sweep_s']*1000:.0f}ms "
        f"({data['join_rows_per_s']:.0f} rows/s at {n_t}x{n_r}); "
        f"neutral churn delta {data['delta_neutral_rows']} rows in "
        f"{data['delta_neutral_s']*1000:.0f}ms "
        f"({data['delta_vs_full_speedup']}x vs full); key churn "
        f"{data['delta_keychurn_rows']} rows "
        f"({data['join_affected_rows']} group readers) in "
        f"{data['delta_keychurn_s']*1000:.0f}ms vs full "
        f"{data['full_after_keychurn_s']*1000:.0f}ms "
        f"({data['keychurn_speedup']}x)")
    out = {
        "metric": f"referential join sweep parity+throughput ({n_t}x{n_r})",
        "value": 1.0 if parity_all else 0.0,
        "unit": "parity",
        "vs_baseline": 0,
        "referential_parity": parity_all,
        "join_rows_per_s": data["join_rows_per_s"],
        "delta_vs_full_speedup": data["delta_vs_full_speedup"],
        **data,
    }
    record = {
        "config": {
            "templates": n_t, "rows": n_r,
            "parity_templates": p_t, "parity_rows": p_r,
            "churn_rows": churn,
            "families": ["unique-key", "required-reference",
                         "count-quota"],
            "mesh": "virtual 8-device CPU (subprocess), widths 1+4",
        },
        "parity": parity_all,
        **data,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "REF_r14.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    log(f"referential recorded: {path}")
    return out


def bench_multihost() -> dict:
    """Two REAL OS processes joined via jax.distributed (gRPC coordinator,
    the DCN control-plane analogue), 4 virtual CPU devices each, one
    8-device (host, data) mesh: the fused capped-audit reduction runs SPMD
    across both processes (tests/test_multihost.py recipe, SURVEY §5.8).
    Reports parity vs the single-process sweep, warm sweep wall time, and
    the bytes crossing the host boundary per sweep (the replicated
    [C, 1+K] reduction — nothing [C, R]-sized ever crosses DCN)."""
    import socket
    import subprocess

    n_t = int(os.environ.get("BENCH_MH_TEMPLATES",
                             os.environ.get("BENCH_TEMPLATES", "500")))
    n_r = int(os.environ.get("BENCH_MH_ROWS",
                             os.environ.get("BENCH_RESOURCES", "100000")))
    worker = f"N_T, N_R = {n_t}, {n_r}\n" + r"""
import os, sys, json, time
sys.path.insert(0, ".")
import numpy as np
import jax
from gatekeeper_tpu.parallel.multihost import (
    init_distributed, multihost_audit_mesh, multihost_capped_sweep,
)

pid = int(os.environ["GK_PROC"])
init_distributed(os.environ["GK_COORD"], 2, pid)
from gatekeeper_tpu.util.synthetic import build_driver

client = build_driver(N_T, N_R, seed=0)
driver = client.driver
driver.set_mesh(False)  # the local auto-mesh must not eat the global one
K = 64
ordered, counts, topk = multihost_capped_sweep(driver, K=K)  # compile+warm
ts = []
for _ in range(3):  # every call re-dispatches (no result cache here)
    t0 = time.perf_counter()
    ordered, counts, topk = multihost_capped_sweep(driver, K=K)
    ts.append(time.perf_counter() - t0)

parity = None
if pid == 0:  # one reference single-process sweep is enough for parity
    driver2 = build_driver(N_T, N_R, seed=0).driver
    driver2.set_mesh(False)
    sweep = driver2._audit_sweep(K)
    _r, _o, _m, ref_counts, ref_topk = sweep
    k = min(topk.shape[1], ref_topk.shape[1])
    parity = bool((counts == ref_counts).all()
                  and (topk[:, :k] == ref_topk[:, :k]).all())
# per-host DCN contribution: its own [C, 1+K] reduction (the all_gather
# payload it sends; it receives the other hosts' equal share)
packed_bytes = int((counts.shape[0]) * (1 + K) * 4)
print(json.dumps({"pid": pid, "parity": parity,
                  "sweep_s": min(ts), "packed_bytes": packed_bytes}),
      flush=True)
"""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    from gatekeeper_tpu.parallel.mesh import virtual_mesh_env

    procs = []
    for pid in range(2):
        env = virtual_mesh_env(4)
        env.update(GK_COORD=coord, GK_PROC=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=1800)
            if p.returncode != 0:
                raise RuntimeError(
                    f"multihost worker rc={p.returncode}:\n{err[-2000:]}")
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    parity = all(o["parity"] for o in outs if o["parity"] is not None)
    sweep_s = max(o["sweep_s"] for o in outs)
    dcn_bytes = outs[0]["packed_bytes"]
    log(f"multihost (2 procs x 4 virtual devices, {n_t}x{n_r}): "
        f"parity={parity} warm sweep {sweep_s*1000:.0f}ms, "
        f"~{dcn_bytes/1e3:.1f}KB ([C,1+K] reduction) crossing the host "
        f"boundary per sweep")
    return {
        "metric": f"2-process multihost capped sweep (DCN lane, {n_t}x{n_r})",
        "value": round(sweep_s, 4),
        "unit": "s",
        "vs_baseline": 0,
        "parity": parity,
        "sweep_s": round(sweep_s, 4),
        "templates": n_t,
        "rows": n_r,
        "dcn_bytes_per_sweep": dcn_bytes,
    }


def bench_synthetic() -> dict:
    n_templates = int(os.environ.get("BENCH_TEMPLATES", "500"))
    n_resources = int(os.environ.get("BENCH_RESOURCES", "100000"))
    baseline_slice = int(os.environ.get("BENCH_BASELINE_SLICE", "20"))
    cap = int(os.environ.get("BENCH_CAP", "20"))

    from gatekeeper_tpu.util.synthetic import build_driver, make_pods, make_templates

    # the roofline share is stated against THIS device's published HBM
    # bandwidth: an unknown device (the CPU included) fails here, before
    # any work, instead of being measured against a v5e's peak
    hbm_gbps = device_peaks(device_stamp()["device_kind"])["hbm_gbps"]

    t0 = time.time()
    client = build_driver(n_templates, n_resources)
    driver = client.driver
    log(f"workload built: {n_templates} templates x {n_resources} resources "
        f"in {time.time()-t0:.1f}s")

    # long-lived-state GC hygiene, as a production audit pod would do
    # (webhook/server.py does the same at startup): without it, gen-2
    # collections scanning the 100k-object inventory inject 100ms+ pauses
    # into steady-state sweeps.  Unfrozen at the end of this config so the
    # other configs in a combined run keep normal GC behavior.
    import gc

    gc.collect()
    gc.freeze()

    # ---- cold sweep: review build + pack + XLA compile + device + render
    t0 = time.time()
    res, totals = client.audit_capped(cap)
    cold_s = time.time() - t0
    settle_warmups()  # base-mask + delta executable compile off-path
    n_results = len(res.results())
    n_capped = sum(1 for v in totals.values() if v[1] == "resources")
    log(f"cold end-to-end capped audit: {cold_s:.1f}s "
        f"({n_results} violations kept, {n_capped}/{len(totals)} constraints at cap)")

    # ---- steady state: one object mutated since the last sweep.  The
    # production path is the INCREMENTAL delta sweep: only the changed
    # rows are re-evaluated on device and folded into the resident
    # per-constraint reduction (ops/deltasweep.py)
    times = []
    best_stats = {}
    for i in range(5):
        p = make_pods(1, seed=1000 + i, violation_rate=1.0)[0]
        p["metadata"]["name"] = f"bench-delta-{i}"
        client.add_data(p)
        t0 = time.time()
        res, totals = client.audit_capped(cap)
        times.append(time.time() - t0)
        s = driver.last_sweep_stats
        log(f"  sweep {i}: {times[-1]*1000:.1f}ms | pack {s.get('pack_ms', 0):.1f} "
            f"device {s.get('device_ms', 0):.1f} fetch {s.get('fetch_ms', 0):.1f} "
            f"render {s.get('render_ms', 0):.1f} ms | fetch {s.get('fetch_bytes', 0)/1e3:.1f}KB "
            f"delta_rows {s.get('delta_rows', 0):.0f} "
            f"fallback_rows {s.get('fallback_rows', 0):.0f} "
            f"rendered_cells {s.get('rendered_cells', 0):.0f}")
        if times[-1] == min(times):
            best_stats = dict(s)
    sweep_s = min(times)
    n_results = len(res.results())
    cells = len(driver._ordered_constraints()) * driver._audit_pack.n_rows
    delta_rows = int(best_stats.get("delta_rows", 0))
    log(f"steady-state end-to-end sweep (1 mutation): {sweep_s*1000:.1f}ms "
        f"({n_results} violations kept); covers {cells} constraint x resource "
        f"cells incrementally ({delta_rows} changed rows re-evaluated on device)")

    # ---- warm FULL resweep (no incremental state): the non-delta number,
    # and the honest basis for the device-utilization estimate
    p = make_pods(1, seed=2000, violation_rate=1.0)[0]
    p["metadata"]["name"] = "bench-full-resweep"
    client.add_data(p)
    driver._delta_state = None
    driver._audit_cache = None
    t0 = time.time()
    client.audit_capped(cap)
    full_s = time.time() - t0
    full_stats = dict(driver.last_sweep_stats)
    log(f"warm full resweep (incremental state dropped): {full_s*1000:.1f}ms "
        f"| device {full_stats.get('device_ms', 0):.1f}ms "
        f"({cells/full_s/1e6:.1f}M cell-evals/s end-to-end)")

    # ---- CLEAN on-device sweep time + bandwidth utilization.  N
    # back-to-back executions of the fused packed-only sweep kernel run
    # inside ONE dispatch (lax.scan with an optimization_barrier per
    # iteration, carry data-dependent on each result, so XLA can neither
    # CSE nor reorder them); the dispatch round trip amortizes across N
    # and is subtracted via a separately-timed trivial dispatch.  The
    # published device_util is measured against the device's HBM roofline
    # (DEVICE_PEAKS).
    import jax
    import jax.numpy as jnp
    import numpy as np

    try:
        N_REP_LO = int(os.environ.get("BENCH_DEVICE_REPS_LO", "200"))
        N_REP = int(os.environ.get("BENCH_DEVICE_REPS", "2000"))
        with driver._lock:
            K = driver._audit_topk(cap)
            fn, _ord2, cp2, gp2, _crow2 = driver._audit_inputs(K)
            rv_d, cols_d = driver._audit_device_inputs()
            cs_d, gp_d = driver._constraint_device_side(
                cp2.arrays, gp2, None, None
            )
        raw = fn.__wrapped__
        fused_raw = driver._fused.__wrapped__  # plain (mask, autoreject)
        from gatekeeper_tpu.ops.matchkernel import match_kernel as _mk

        def _rep_jit(body_fn, reps):
            # every integer/bool review-side leaf is xor-folded with an
            # OPAQUE carry-derived zero: the kernel's data roots become
            # loop-variant, so XLA cannot hoist the (otherwise genuinely
            # loop-invariant) body out of the scan — observed always on
            # XLA:CPU and intermittently per-body on TPU, which made
            # variant timings mutually inconsistent.  The xor fuses into
            # each consumer's first read (no extra HBM pass; measured
            # zero inflation vs the unperturbed body on CPU).
            def _perturb(tree, zero):
                def fold(x):
                    if x.dtype == jnp.bool_:
                        return x ^ (zero != 0)
                    if jnp.issubdtype(x.dtype, jnp.integer):
                        return x ^ zero.astype(x.dtype)
                    return x

                return jax.tree_util.tree_map(fold, tree)

            def rep_n(rv, cs, cols, gp):
                def body(carry, _):
                    rv2, cs2, cols2, gp2_ = jax.lax.optimization_barrier(
                        (rv, cs, cols, gp))
                    zero = jax.lax.optimization_barrier(carry & 0)
                    rv2 = _perturb(rv2, zero)
                    cols2 = _perturb(cols2, zero)
                    return body_fn(carry, rv2, cs2, cols2, gp2_), None

                c, _ = jax.lax.scan(body, jnp.int32(0), None, length=reps)
                return c

            return jax.jit(rep_n)

        def _timed(jitted):
            # MIN over several runs: host noise is one-sided (additive
            # spikes on top of a stable floor), so the minimum converges
            # to the true total and min-based slopes stay consistent
            # where median-based ones flapped between runs
            ts = []
            for _ in range(7):
                t0 = time.perf_counter()
                jitted(rv_d, cs_d, cols_d, gp_d).block_until_ready()
                ts.append(time.perf_counter() - t0)
            return float(min(ts))

        def _chained(body_fn, reps=None):
            """Per-iteration time of a barrier-chained scan, estimated by
            a CASCADE: slope between two scan lengths (cancels the
            dispatch RTT exactly), at two length pairs, then plain RTT
            subtraction.
            XLA may legitimately hoist the loop-invariant body out of the
            scan (observed always on XLA:CPU, intermittently on TPU, and
            it varies with trip count) — a collapsed estimator reports
            None rather than a fake zero, and the caller publishes null.
            body_fn(carry, rv, cs, cols, gp) -> new carry; it must depend
            on EVERY output element (a [0,0] probe would let XLA's slice
            pushdown dead-code the rest of the grid)."""
            hi = max(2, reps or N_REP)
            lo = max(1, min(N_REP_LO, hi // 10))
            floor_ms = 0.002  # below this, the estimator didn't resolve

            def compiled(n):
                j = _rep_jit(body_fn, n)
                j(rv_d, cs_d, cols_d, gp_d).block_until_ready()
                return j

            jit_lo, jit_hi = compiled(lo), compiled(hi)
            t_lo, t_hi = _timed(jit_lo), _timed(jit_hi)
            if hi > lo:
                per = (t_hi - t_lo) / (hi - lo) * 1e3
                if per > floor_ms:
                    return per
            if lo > 1:
                # built lazily: the common path never needs the 1-rep jit
                t_1 = _timed(compiled(1))
                per = (t_lo - t_1) / (lo - 1) * 1e3
                if per > floor_ms:
                    return per
            per = (t_hi - rtt) / hi * 1e3
            return per if per > floor_ms else None

        tiny = jax.jit(lambda x: x + 1)
        xd = jax.device_put(np.int32(1))
        tiny(xd).block_until_ready()
        rtts = []
        for _ in range(5):
            t0 = time.perf_counter()
            tiny(xd).block_until_ready()
            rtts.append(time.perf_counter() - t0)
        rtt = float(np.median(rtts))

        # the breakdown the 2.25x roofline gap demands (r4 verdict #4):
        # full kernel, mask-only (difference = reduction cost), match-only
        # (difference = violation-program cost), and a pure input-bytes
        # traversal (the ACHIEVABLE bandwidth for these arrays on this
        # chip, a tighter bound than the spec-sheet roofline)
        device_sweep_ms = _chained(
            lambda k, rv, cs, c, gp:
                k + raw(rv, cs, c, gp).sum(dtype=jnp.int32))
        mask_only_ms = _chained(
            lambda k, rv, cs, c, gp:
                k + fused_raw(rv, cs, c, gp)[0].sum(dtype=jnp.int32))
        match_only_ms = _chained(
            lambda k, rv, cs, c, gp:
                k + _mk(rv, cs)[0].sum(dtype=jnp.int32))

        in_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves(
                (driver._audit_pack.rp, driver._audit_pack.cols)))
        cs_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves((cs_d, gp_d)))
        # the [C, R] mask is an XLA-internal intermediate: the
        # hierarchical reduction fuses into the mask producer, so no
        # mask-sized array is ever written to (or re-read from) HBM —
        # the bandwidth bound is the one pass over the packed inputs +
        # the replicated constraint side
        roofline_ms = (in_bytes + cs_bytes) / (hbm_gbps * 1e9) * 1e3

        def _touch(k, rv, cs, c, gp):
            # sum ONLY the perturbed (loop-variant) trees: cs/gp and
            # float-leaf sums would stay loop-invariant and hoistable,
            # silently undercounting the traversal.  rv+cols are ~all of
            # in_bytes (the constraint side is KB-scale next to the row
            # pack), so the measured bound keeps its meaning.
            tot = k
            for leaf in jax.tree_util.tree_leaves((rv, c)):
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    continue
                tot = tot + leaf.astype(jnp.int32).sum(dtype=jnp.int32)
            return tot

        # the traversal kernel is ~10x cheaper than the sweep; give it
        # 10x the reps so it resolves above dispatch RTT jitter
        bytes_touch_ms = _chained(_touch, reps=N_REP * 10)

        # structural sanity: full >= mask-only >= match-only (supersets).
        # A variant that resolved BELOW its subset was noise-corrupted —
        # null it rather than publish an impossible figure.
        if (device_sweep_ms is not None and mask_only_ms is not None
                and device_sweep_ms < mask_only_ms * 0.9):
            device_sweep_ms = None
        if (mask_only_ms is not None and match_only_ms is not None
                and mask_only_ms < match_only_ms * 0.9):
            mask_only_ms = None
        # plausibility gate: a sweep "faster than reading its inputs from
        # HBM once" means the scan kept the working set chip-resident
        # (VMEM) across iterations — a flattering artifact of the repeat
        # harness, not the cost a production sweep streaming from HBM
        # pays.  The conservative claim nulls rather than publishes it.
        if (device_sweep_ms is not None
                and jax.default_backend() != "cpu"
                and device_sweep_ms < roofline_ms / 1.2):
            device_sweep_ms = None

        C = len(driver._ordered_constraints())
        ap = driver._audit_pack

        def _r(x):
            return round(x, 4) if x is not None else None

        def _delta(a, b):
            if a is None or b is None:
                return None
            return round(max(0.0, a - b), 4)

        # every derived figure is null when its estimator didn't resolve
        # (XLA hoisted the scan body; see _chained) — never a fake zero
        util = (
            round(roofline_ms / device_sweep_ms, 4)
            if device_sweep_ms else None
        )
        util_measured = (
            round(bytes_touch_ms / device_sweep_ms, 4)
            if device_sweep_ms and bytes_touch_ms else None
        )
        device_cells_per_s = (
            cells / (device_sweep_ms / 1e3) if device_sweep_ms else None
        )
        achieved_gbps = (
            (in_bytes + cs_bytes) / 1e9 / (device_sweep_ms / 1e3)
            if device_sweep_ms else None
        )
        c_padded = len(driver._constraint_side()[1].arrays["valid"])
        device_breakdown = {
            "full_ms": _r(device_sweep_ms),
            "mask_only_ms": _r(mask_only_ms),
            "reduction_ms": _delta(device_sweep_ms, mask_only_ms),
            "match_only_ms": _r(match_only_ms),
            "programs_ms": _delta(mask_only_ms, match_only_ms),
            "bytes_touch_ms": _r(bytes_touch_ms),
            "pad_row_frac": round(1.0 - ap.n_rows / max(ap.capacity, 1), 4),
            "pad_constraint_frac": round(1.0 - C / max(c_padded, 1), 4),
        }
        log("on-device sweep: "
            + (f"{device_sweep_ms:.3f}ms/sweep" if device_sweep_ms
               else "UNRESOLVED (estimator cascade collapsed)")
            + f" (chained-scan slope {N_REP_LO}/{N_REP} reps; dispatch "
            f"RTT ~{rtt*1e3:.1f}ms cancels in the difference) = "
            + (f"{device_cells_per_s/1e9:.2f}B cell-evals/s, "
               if device_cells_per_s else "")
            + (f"{achieved_gbps:.0f}GB/s" if achieved_gbps is not None
               else "n/a GB/s")
            + f" touched vs {hbm_gbps:.0f}GB/s HBM -> "
            + (f"{util*100:.1f}%" if util is not None else "n/a")
            + " of the spec-sheet input roofline, "
            + (f"{util_measured*100:.1f}%" if util_measured is not None
               else "unresolved fraction")
            + " of the measured-traversal bound "
            f"(roofline {roofline_ms:.2f}ms: inputs {in_bytes/1e6:.0f}MB + "
            f"constraint side {cs_bytes/1e6:.0f}MB; the [C,R] mask fuses "
            f"away and never touches HBM); breakdown "
            f"{device_breakdown}")
    except Exception as e:  # pragma: no cover
        log(f"on-device measurement failed: {e!r}")
        roofline_ms, device_sweep_ms, device_cells_per_s = 0.0, None, None
        util, util_measured, device_breakdown = None, None, {}

    # ---- baseline: interpreter oracle on a slice, derated (BASELINE.md) --
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.client.drivers import InterpDriver

    templates, constraints = make_templates(n_templates)
    ci = Client(driver=InterpDriver())
    for t in templates:
        ci.add_template(t)
    for c in constraints:
        ci.add_constraint(c)
    for p in make_pods(baseline_slice, seed=1):
        ci.add_data(p)
    t0 = time.time()
    ci.audit()
    interp_s = time.time() - t0
    interp_cells = n_templates * baseline_slice
    interp_rate = interp_cells / interp_s
    est_ref_rate = interp_rate * GO_TOPDOWN_DERATE
    est_ref_sweep_s = cells / est_ref_rate
    log(f"interp oracle: {interp_rate:.0f} evals/s; estimated Go-topdown "
        f"reference ({GO_TOPDOWN_DERATE:.0f}x derate): {est_ref_rate:.0f} "
        f"evals/s -> {est_ref_sweep_s:.0f}s for this sweep")

    gc.unfreeze()  # the other configs in a combined run want normal GC

    return {
        "metric": (
            f"end-to-end audit sweep seconds ({n_templates} templates"
            f" x {n_resources} resources, cap {cap}, steady-state)"
        ),
        "value": round(sweep_s, 3),
        "unit": "s",
        "vs_baseline": round(est_ref_sweep_s / sweep_s, 1),
        "cold_sweep_s": round(cold_s, 3),
        "full_resweep_s": round(full_s, 3),
        # cells covered per second: the incremental sweep verifies the full
        # C x R grid per interval while re-evaluating only changed rows
        "coverage_cells_per_s": round(cells / sweep_s, 1),
        "delta_rows_per_sweep": delta_rows,
        "sweep_breakdown_ms": {
            k: round(best_stats.get(k, 0.0), 2)
            for k in ("pack_ms", "device_ms", "fetch_ms", "render_ms")
        },
        "sweep_fetch_bytes": best_stats.get("fetch_bytes", 0.0),
        "full_sweep_device_ms": round(full_stats.get("device_ms", 0.0), 2),
        # clean ON-DEVICE numbers (min-based two-length chained-scan
        # slope — the dispatch RTT cancels in the difference; null when
        # the estimator cascade could not resolve consistently);
        # full_sweep_device_ms above is the host-clock dispatch time
        "device_sweep_ms": (
            round(device_sweep_ms, 4) if device_sweep_ms is not None
            else None),
        "device_cell_evals_per_s": (
            round(device_cells_per_s, 1) if device_cells_per_s is not None
            else None),
        "hbm_roofline_ms": round(roofline_ms, 2),
        "device_util": util,
        "device_util_measured": util_measured,
        "device_breakdown": device_breakdown,
    }


def _pipelined_drive(port: int, req_b: bytes, n_total: int,
                     n_clients: int = 2, window: int = 256,
                     timeout: float = 300.0):
    """Closed-loop persistent PIPELINED clients (EDGE_r19 satellite 1,
    shared with the edge-observability config): each keeps ``window``
    requests in flight on one connection and counts fixed-length
    responses by byte arithmetic, so the client side stays cheap enough
    not to mask the door.  Requires every response to be
    byte-length-identical (one fixed request body; trace ids and
    replica ids are fixed-width)."""
    import socket
    import threading

    done: dict = {}

    def _c(tid: int, n: int) -> None:
        s = socket.create_connection(("127.0.0.1", port),
                                     timeout=timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(timeout)
        batch = req_b * 16
        sent = got_b = recv = 0
        rlen = None
        buf = b""
        try:
            while recv < n:
                while sent - recv < window and sent < n:
                    s.sendall(batch)
                    sent += 16
                data = s.recv(1 << 20)
                if not data:
                    break
                if rlen is None:
                    buf += data
                    i = buf.find(b"\r\n\r\n")
                    if i < 0:
                        continue
                    m = re.search(
                        r"content-length:\s*(\d+)",
                        buf[:i].decode("latin-1").lower())
                    rlen = i + 4 + int(m.group(1))
                    got_b = len(buf)
                    buf = b""
                else:
                    got_b += len(data)
                recv = got_b // rlen
        finally:
            done[tid] = min(recv, n)
            try:
                s.close()
            except OSError:
                pass

    per = n_total // n_clients
    ts = [threading.Thread(target=_c, args=(i, per))
          for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout + 60.0)
        if t.is_alive():
            raise RuntimeError("edge pipelined client wedged "
                               "(no completion in time)")
    return sum(done.values()), time.perf_counter() - t0


def _stub_wire_responder(canned: bytes):
    """In-process GKW1 stub: answers every request record of every
    chunk with ``canned`` (a real AdmissionReview body), parsing only
    the frame skeleton — the EDGE_r19 door-capacity recipe, isolating
    the door's data plane from engine throughput.  Returns the bound
    listening socket (close it to stop the accept thread)."""
    import socket
    import struct
    import threading

    from gatekeeper_tpu.fleet import wireproto as _wp

    _hdrS = _wp._HDR
    _reqS = _wp._REQ
    resp_mid = struct.pack("!HI", 200, len(canned)) + canned
    resp_rec = 10 + len(canned)
    rid_pack = struct.Struct("!I").pack

    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(8)

    def _conn(sk):
        sk.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        rbuf = bytearray()
        try:
            while True:
                d = sk.recv(1 << 20)
                if not d:
                    return
                rbuf += d
                out: list = []
                while len(rbuf) >= _hdrS.size:
                    _m, _k, count, plen = _hdrS.unpack_from(rbuf, 0)
                    if len(rbuf) < _hdrS.size + plen:
                        break
                    off = _hdrS.size
                    for _ in range(count):
                        rid, _dl, pl, tl, bl = _reqS.unpack_from(
                            rbuf, off)
                        off += _reqS.size + pl + tl + bl
                        out.append(rid_pack(rid))
                        out.append(resp_mid)
                    del rbuf[:_hdrS.size + plen]
                if out:
                    n_recs = len(out) // 2
                    sk.sendall(_hdrS.pack(
                        _wp.MAGIC, _wp.KIND_RESPONSE, n_recs,
                        n_recs * resp_rec) + b"".join(out))
        except OSError:
            return

    def _accept():
        while True:
            try:
                sk, _addr = lsock.accept()
            except OSError:
                return
            threading.Thread(target=_conn, args=(sk,),
                             daemon=True).start()

    threading.Thread(target=_accept, daemon=True).start()
    return lsock


def bench_fleet() -> dict:
    """Fleet serving (docs/fleet.md, ISSUE 7): N webhook-only replica
    processes restore ONE shared sealed snapshot + AOT cache, sit behind
    the stdlib front door, and are measured on

      - warm time-to-device-ready per replica (spawn -> first admission
        answered end to end; the <5s shared-warmth claim),
      - client-observed admission latency through the front door under
        low sequential load and under concurrent load, attributed per
        replica via the X-GK-Replica header,
      - verdict parity: byte-identical AdmissionReview bodies across
        replicas for identical requests, and allow/deny + message
        parity against a fresh interpreter oracle,
      - combined saturated throughput: every replica streams its
        restored corpus through review_batch concurrently (the batch1m
        chunk shape, in-process per replica so the HTTP framing cost —
        measured separately above — does not mask engine throughput).
    """
    import http.client as _httpc
    import shutil
    import tempfile
    import threading

    from gatekeeper_tpu.fleet import EventFrontDoor, spawn_fleet
    from gatekeeper_tpu.snapshot import Snapshotter
    from gatekeeper_tpu.util.synthetic import (
        build_driver,
        build_oracle,
        make_pods,
    )

    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "3"))
    n_templates = int(os.environ.get("BENCH_FLEET_TEMPLATES", "2"))
    n_resources = int(os.environ.get("BENCH_FLEET_RESOURCES", "2048"))
    n_stream = int(os.environ.get("BENCH_FLEET_REVIEWS", "400000"))
    chunk = int(os.environ.get("BENCH_FLEET_CHUNK", "16384"))
    n_latency = int(os.environ.get("BENCH_FLEET_LATENCY_N", "400"))
    n_parity = int(os.environ.get("BENCH_FLEET_PARITY_N", "64"))

    root = tempfile.mkdtemp(prefix="gk-fleet-bench-")
    snap_dir = os.path.join(root, "snap")
    # no cache dir is handed to the replicas: each resolves the fixed one
    # itself (ops/xlacache.py) — a directory that moves never hits
    os.makedirs(snap_dir)

    # ---- shared warmth: populate once, snapshot once ----------------------
    client = build_driver(n_templates, n_resources)
    client.audit_capped(50)  # pack + sweep basis for the snapshot
    name = Snapshotter(client, snap_dir, interval_s=0.0).write_once()
    log(f"fleet: snapshot {name}")

    # admission sample: reuse the corpus generator at a different seed so
    # requests are fresh content (no audit-pack identity), same families
    sample_pods = make_pods(max(n_latency, n_parity), seed=99,
                            violation_rate=0.3)

    def admit_body(i: int) -> bytes:
        p = sample_pods[i % len(sample_pods)]
        return json.dumps({"request": {
            "uid": f"fleet-bench-{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "fleet-bench"},
            "object": p,
        }}).encode()

    def post(port: int, body: bytes, conn=None):
        c = conn or _httpc.HTTPConnection("127.0.0.1", port, timeout=60)
        c.request("POST", "/v1/admit", body=body,
                  headers={"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, dict(r.getheaders()), r.read(), c

    # ---- oracle verdicts (fresh interpreter, same corpus) -----------------
    oracle = build_oracle(n_templates, n_resources)
    oracle_verdicts = []
    for i in range(n_parity):
        p = sample_pods[i % len(sample_pods)]
        resp = oracle.review({
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "object": p,
        })
        results = resp.results()
        oracle_verdicts.append(
            (not results, tuple(sorted(r.msg for r in results)))
        )

    # one throwaway replica seeds the shared XLA/AOT cache (the running
    # fleet's steady state); every MEASURED replica then models the
    # scale-up case the <5s claim is about — joining a warm fleet
    seed = spawn_fleet(
        1, snapshot_dir=snap_dir,
        env={"JAX_PLATFORMS": "cpu"},
    )[0]
    seed_ready_s = seed.ready_s
    seed_outcome = seed.ready.get("restore_outcome")
    seed.stop()
    log(f"fleet: cache-seed replica ready={seed_ready_s}s "
        f"({seed_outcome})")

    handles = spawn_fleet(
        n_replicas, snapshot_dir=snap_dir,
        env={"JAX_PLATFORMS": "cpu"},
    )
    door = None
    try:
        for h in handles:
            if h.ready.get("restore_outcome") != "restored":
                raise RuntimeError(
                    f"replica {h.replica_id} came up COLD "
                    f"({h.ready.get('restore_outcome')}): the shared-"
                    f"warmth bench would measure the wrong thing"
                )
        log("fleet: " + ", ".join(
            f"{h.replica_id} ready={h.ready_s}s spawn={h.spawn_s}s"
            for h in handles
        ))

        no_wire = [h.replica_id for h in handles if not h.wire_port]
        if no_wire:
            raise RuntimeError(
                f"replicas {no_wire} announced no wire port — the "
                "door cannot serve")
        door = EventFrontDoor(
            [h.wire_backend() for h in handles]).start()

        # ---- parity: byte-identical across replicas, verdicts vs oracle --
        parity = True
        parity_vs_oracle = True
        for i in range(n_parity):
            body = admit_body(i)
            raws = []
            for h in handles:
                _st, _hd, data, _c = post(h.port, body)
                raws.append(data)
            if len(set(raws)) != 1:
                parity = False
                log(f"fleet: replica divergence on request {i}")
            out = json.loads(raws[0])["response"]
            allowed = out["allowed"]
            # message CONTENT parity, not just count: strip the
            # webhook's "[denied by <constraint>] " prefix (reference
            # log_denies format) so the rendered violation text is
            # compared byte-for-byte against the oracle's
            msgs = tuple(sorted(
                re.sub(r"^\[denied by [^\]]+\] ", "", m)
                for m in (out.get("status") or {}).get(
                    "message", "").split("\n") if m
            )) if not allowed else ()
            o_allowed, o_msgs = oracle_verdicts[i]
            if allowed != o_allowed or (not allowed and msgs != o_msgs):
                parity_vs_oracle = False
                log(f"fleet: oracle divergence on request {i}: "
                    f"fleet={allowed}/{msgs} "
                    f"oracle={o_allowed}/{o_msgs}")

        # ---- latency through the front door ------------------------------
        # low load: one sequential client (the inline fast path / p99
        # floor); saturating: 4x clients hammering concurrently
        def drive(n: int, conn_state: dict) -> list:
            out = []
            conn = conn_state.get("conn")
            for i in range(n):
                body = admit_body(i)
                t0 = time.perf_counter()
                try:
                    _st, hd, _data, conn = post(
                        door.port, body, conn)
                except Exception:
                    conn = None
                    continue
                out.append((
                    (time.perf_counter() - t0) * 1e3,
                    hd.get("X-GK-Replica", ""),
                ))
            conn_state["conn"] = conn
            return out

        def pct(xs, q):
            if not xs:
                return None
            return round(xs[min(int(q * len(xs)), len(xs) - 1)], 3)

        seq = drive(n_latency, {})
        seq_ms = sorted(ms for ms, _r in seq)

        # ---- wire-path observability (ISSUE 11, recorded OBS_r11) --------
        # The front door traced every request above: per-stage p50/p99
        # from the parent tracer's wire traces, the no-dark-time share
        # (stage p50s vs the wire p50), the federated /metrics view, and
        # one seeded slow request assembled across processes.
        from gatekeeper_tpu.fleet.wireproto import WIRE_STAGES
        from gatekeeper_tpu.obs import fleetobs
        from gatekeeper_tpu.obs import trace as obstrace

        fed = fleetobs.MetricsFederator(lambda: [
            {"replica_id": h.replica_id, "host": h.host,
             "port": h.metrics_port} for h in handles
        ])
        col = fleetobs.TraceCollector(lambda: [
            {"replica_id": h.replica_id, "host": h.host, "port": h.port}
            for h in handles
        ])
        door.attach_observability(federator=fed, collector=col)

        wire = [t for t in obstrace.get_tracer().traces()
                if t.get("root") == "wire"]
        from gatekeeper_tpu.obs.trace import stage_breakdown as _sb

        per_stage: dict = {s: [] for s in WIRE_STAGES}
        durations = []
        coverage = []
        for t in wire:
            bd = _sb(t)
            durations.append(t["duration_ms"])
            if t["duration_ms"] > 0:
                coverage.append(
                    sum(bd.get(s, 0.0) for s in WIRE_STAGES)
                    / t["duration_ms"]
                )
            for s in WIRE_STAGES:
                per_stage[s].append(bd.get(s, 0.0))
        durations.sort()
        stage_p50 = {s: pct(sorted(xs), 0.50) for s, xs in
                     per_stage.items()}
        stage_p99 = {s: pct(sorted(xs), 0.99) for s, xs in
                     per_stage.items()}
        wire_p50 = pct(durations, 0.50) or 0.0
        wire_p99 = pct(durations, 0.99)
        stage_share = (
            round(sum(v for v in stage_p50.values() if v) / wire_p50, 4)
            if wire_p50 else None
        )
        coverage.sort()
        log(f"fleet: wire p50={wire_p50}ms, stage-sum share="
            f"{stage_share}, median per-trace coverage="
            f"{pct(coverage, 0.5)}")

        # federated /metrics through the door: replica series must be
        # replica_id-labelled and the wire stage families present
        conn_m = _httpc.HTTPConnection("127.0.0.1", door.port, timeout=30)
        conn_m.request("GET", "/metrics")
        fed_text = conn_m.getresponse().read().decode()
        conn_m.close()
        fed_ok = (
            "gatekeeper_frontdoor_stage_seconds" in fed_text
            and 'replica_id="r0"' in fed_text
            and "gatekeeper_fleet_scrape_ok" in fed_text
            and "# EOF" not in fed_text
        )
        log(f"fleet: federated /metrics ok={fed_ok} "
            f"({len(fed_text.splitlines())} lines)")

        # seeded slow request: one latency fault on r0's batcher entry,
        # installed over the WARM replica's command pipe — the next
        # admission the door routes to r0 carries ~+80ms, and its trace
        # must assemble across processes under ONE trace_id
        slow_ms = 80.0
        chaos_reply = handles[0].command({"cmd": "chaos", "spec": {
            "seed": 11,
            "rules": [{"point": "webhook.enqueue", "mode": "latency",
                       "latency_s": slow_ms / 1e3, "count": 1}],
        }})
        if chaos_reply.get("error") or not chaos_reply.get("enabled"):
            # the seeded slow request is ACCEPTANCE evidence: a failed
            # fault install must fail the bench loudly, not silently
            # record slow_trace_joined=null
            raise RuntimeError(
                f"slow-request chaos seed failed: {chaos_reply}")
        state: dict = {}
        for _ in range(4 * len(handles)):
            drive(1, state)
        handles[0].command({"cmd": "chaos", "spec": None})

        def _find_joined():
            assembled = col.assemble(min_ms=slow_ms * 0.8)
            for entry in assembled["traces"]:
                if len(entry["processes"]) > 1 \
                        and entry["root"] == "wire":
                    has_wire = any(
                        sp.get("process") == "frontdoor"
                        and (sp.get("attrs") or {}).get("stage")
                        for sp in entry["spans"]
                    )
                    has_replica = any(
                        sp.get("process") not in (None, "frontdoor")
                        for sp in entry["spans"]
                    )
                    if has_wire and has_replica:
                        return {
                            "trace_id": entry["trace_id"],
                            "duration_ms": entry["duration_ms"],
                            "processes": entry["processes"],
                            "stage_breakdown": entry["stage_breakdown"],
                        }
            return None

        # the replica half completes asynchronously relative to the
        # door's response: poll briefly before declaring the join absent
        slow_joined = None
        for _ in range(20):
            slow_joined = _find_joined()
            if slow_joined is not None:
                break
            time.sleep(0.25)
        log(f"fleet: seeded slow trace joined: {slow_joined}")

        threads_out: list = []
        lock = threading.Lock()

        def _client():
            got = drive(n_latency, {})
            with lock:
                threads_out.extend(got)

        tt0 = time.perf_counter()
        clients = [threading.Thread(target=_client) for _ in range(4)]
        for t in clients:
            t.start()
        for t in clients:
            # bounded: a wedged driver must fail the bench, not hang it
            t.join(timeout=600.0)
            if t.is_alive():
                raise RuntimeError("bench latency client wedged (no "
                                   "result within 600s)")
        http_wall = time.perf_counter() - tt0
        http_rps = len(threads_out) / http_wall if threads_out else 0.0

        per_replica: dict = {}
        for ms, rid in threads_out:
            per_replica.setdefault(rid, []).append(ms)
        replica_lat = {
            rid: {
                "n": len(xs),
                "p50_ms": pct(sorted(xs), 0.50),
                "p99_ms": pct(sorted(xs), 0.99),
            }
            for rid, xs in sorted(per_replica.items())
        }

        # ---- combined saturated throughput (in-replica streams) ----------
        stream_out: dict = {}

        def _stream(h):
            stream_out[h.replica_id] = h.command(
                {"cmd": "stream", "n": n_stream, "chunk": chunk}
            )

        # best of 3 rounds: this box's co-tenancy swings host-path rates
        # ±30% run to run (the render bench takes min-of-3 for the same
        # reason); later rounds also stream with every replica's jit warm
        best = None
        for rnd in range(3):
            stream_out.clear()
            streams = [
                threading.Thread(target=_stream, args=(h,))
                for h in handles
            ]
            for t in streams:
                t.start()
            for t in streams:
                # bounded: a wedged replica stream fails the round loudly
                t.join(timeout=600.0)
                if t.is_alive():
                    raise RuntimeError("fleet stream thread wedged (no "
                                       "completion within 600s)")
            # the combined rate is measured over the union of the
            # replicas' TIMED windows (child-reported wall stamps,
            # warmup excluded) — the parent's own wall would bill each
            # child's jit warmup and command framing against engine
            # throughput
            wall = (
                max(s["t1_wall"] for s in stream_out.values())
                - min(s["t0_wall"] for s in stream_out.values())
            )
            rate = n_stream * len(handles) / wall
            log(f"fleet: round {rnd}: {rate:.0f} reviews/s over "
                f"{len(handles)} replicas ({wall:.1f}s wall)")
            if best is None or rate > best[0]:
                best = (rate, wall, dict(stream_out))
        combined, stream_wall, stream_out = best

        # ---- profiler overhead (ISSUE 11 acceptance: within 5%) ----------
        # The SAME warm replicas stream with the sampler off then on
        # (runtime re-rate over the command pipe — no respawn, no cold
        # jit).  This box's co-tenancy swings short windows ±30%, so the
        # estimate is PAIRED: off/on back-to-back, the ratio taken
        # within each pair (drift hits both arms of a pair almost
        # equally), the ARM ORDER alternated per pair (monotonic drift
        # would otherwise systematically tax whichever arm runs
        # second), median over pairs.
        n_overhead = int(os.environ.get("BENCH_FLEET_OVERHEAD_REVIEWS",
                                        str(n_stream)))
        n_pairs = int(os.environ.get("BENCH_FLEET_OVERHEAD_PAIRS", "5"))
        from gatekeeper_tpu.obs.profiler import DEFAULT_HZ as prof_hz

        def _profiler_round(hz: float) -> float:
            for h in handles:
                h.command({"cmd": "profiler", "hz": hz})
            outp: dict = {}
            errs: list = []

            def _s(h):
                try:
                    outp[h.replica_id] = h.command(
                        {"cmd": "stream", "n": n_overhead,
                         "chunk": chunk}
                    )
                except Exception as e:  # surfaced after the joins
                    errs.append((h.replica_id, e))

            ts = [threading.Thread(target=_s, args=(h,)) for h in handles]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=600.0)
                if t.is_alive():
                    raise RuntimeError(
                        "profiler-overhead stream wedged (no completion "
                        "within 600s)")
            if errs or len(outp) != len(handles):
                # a partial round would silently inflate the recorded
                # overhead number (numerator counts every replica)
                raise RuntimeError(
                    f"profiler-overhead round incomplete: errors={errs},"
                    f" replied={sorted(outp)}")
            wall = (max(s["t1_wall"] for s in outp.values())
                    - min(s["t0_wall"] for s in outp.values()))
            return round(n_overhead * len(handles) / wall, 1)

        rates_off, rates_on, pair_ratios = [], [], []
        for i in range(n_pairs):
            if i % 2 == 0:
                off = _profiler_round(0.0)
                on = _profiler_round(prof_hz)
            else:
                on = _profiler_round(prof_hz)
                off = _profiler_round(0.0)
            rates_off.append(off)
            rates_on.append(on)
            pair_ratios.append(on / off)
        # estimator: median(on)/median(off) over the position-balanced
        # arms — a pairwise-ratio median is hostage to whichever pair a
        # co-tenant burst lands in; arm medians reject those outliers
        med_off = sorted(rates_off)[len(rates_off) // 2]
        med_on = sorted(rates_on)[len(rates_on) // 2]
        profiler_overhead_pct = round((1.0 - med_on / med_off) * 100.0,
                                      2)
        log(f"fleet: profiler overhead {profiler_overhead_pct}% "
            f"(median off={med_off} on={med_on}, paired ratios="
            f"{[round(r, 3) for r in pair_ratios]}, off={rates_off}, "
            f"on={rates_on})")
        # the sampler's own output, from a replica that just streamed
        conn_p = _httpc.HTTPConnection(
            "127.0.0.1", handles[0].port, timeout=30)
        conn_p.request("GET", "/debug/profilez")
        profilez = conn_p.getresponse().read().decode()
        conn_p.close()
        profilez_lines = len(profilez.splitlines())

        obs_wire = {
            "wire_p50_ms": wire_p50,
            "wire_p99_ms": wire_p99,
            "wire_traces": len(wire),
            "stage_p50_ms": stage_p50,
            "stage_p99_ms": stage_p99,
            "stage_share_of_p50": stage_share,
            "trace_coverage_p50": pct(coverage, 0.50),
            "client_seq_p50_ms": pct(seq_ms, 0.50),
            "federated_metrics_ok": fed_ok,
            "federated_metrics_lines": len(fed_text.splitlines()),
            "slow_trace_joined": slow_joined,
            "profiler_overhead_pct": profiler_overhead_pct,
            "profiler_rates_off": rates_off,
            "profiler_rates_on": rates_on,
            "profilez_lines": profilez_lines,
            "fleet_reviews_per_s": round(combined, 1),
        }
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "OBS_r11.json"), "w") as f:
            json.dump(obs_wire, f, indent=2, sort_keys=True)

        # ---- event-loop edge (ISSUE 19, recorded EDGE_r19) ---------------
        # The selectors-based serving edge over the SAME warm replicas:
        #   (a) persistent-connection latency with per-stage p50s from
        #       the ring traces (sample 1.0), against the front
        #       section's stage numbers above;
        #   (b) the door-capacity headline against an in-process stub
        #       wire responder — the front door's own data plane
        #       (accept/parse/route/splice/write), isolated from engine
        #       throughput, with 2% head sampling as a high-rate
        #       deployment would run it;
        #   (c) the honest end-to-end pipelined rate through the real
        #       replicas (engine-bound, reported as such);
        #   (d) a connect-per-request round — the old clients' shape —
        #       reported separately;
        #   (e) the ISSUE 12 overload contract re-proven on this edge:
        #       tight-bounded door, 10x closed-loop saturation, shed
        #       p99 and zero verdict divergence vs the oracle.
        import gc

        from gatekeeper_tpu.util.overloadcheck import (
            ACCEPTED,
            PROBLEM,
            SHED,
            classify_response,
            verdict_matches,
        )

        n_edge_lat = int(os.environ.get("BENCH_EDGE_LATENCY_N", "400"))
        n_edge_cap = int(os.environ.get("BENCH_EDGE_CAP_REVIEWS", "40000"))
        # best-of like the stream rounds, but deeper: the capacity
        # rounds are ~1s each and this box's co-tenant bursts can sink
        # half of them (observed swing 27k..63k for identical code)
        cap_rounds = int(os.environ.get("BENCH_EDGE_CAP_ROUNDS", "5"))
        n_edge_e2e = int(os.environ.get("BENCH_EDGE_E2E_REVIEWS", "4000"))
        n_edge_conn = int(os.environ.get("BENCH_EDGE_CONNECT_N", "300"))
        overload_s = float(os.environ.get("BENCH_EDGE_OVERLOAD_S", "3.0"))

        missing_wire = [h.replica_id for h in handles if not h.wire_port]
        if missing_wire:
            raise RuntimeError(
                f"replicas {missing_wire} announced no wire port — the "
                "event-edge rounds would measure nothing")

        # Quiesce the co-tenants before measuring the edge — everything
        # here shares ONE core with the reactor, and each periodic
        # wakeup lands as a preemption inside some stage window:
        #   - the paired profiler rounds above END with the replicas'
        #     sampling profiler armed (the last pair's second arm is
        #     "on"), so every replica would keep waking at DEFAULT_HZ;
        #   - the front-section door is done serving: its prober
        #     re-probes the fleet every 250ms.  stats() below reads
        #     counters, which survive stop().
        for h in handles:
            h.command({"cmd": "profiler", "hz": 0.0})
        door.stop()

        edoor = EventFrontDoor([h.wire_backend() for h in handles]).start()
        odoor = None
        cap_lsock = None
        try:
            # The bench process carries several hundred MB of heap by
            # this point (parity oracles, per-round samples); a gen-2
            # collection walking it mid-round is a multi-ms stall billed
            # to whatever stage it lands in.  Freeze the existing heap
            # out of the collector and disable cycle collection for the
            # measured rounds — refcounting still frees the per-request
            # garbage, which is cycle-free on the hot path.
            gc.collect()
            gc.freeze()
            gc.disable()

            # -- (a) persistent-connection latency, everything traced --
            obstrace.get_tracer().configure(sample_rate=1.0)
            e_conn = None
            e_tids: list = []
            e_ms: list = []
            last_body = b""
            for i in range(n_edge_lat):
                body = admit_body(i)
                t0 = time.perf_counter()
                _st, hd, last_body, e_conn = post(edoor.port, body, e_conn)
                e_ms.append((time.perf_counter() - t0) * 1e3)
                e_tids.append(hd.get("X-GK-Trace-Id", ""))
            if e_conn is not None:
                e_conn.close()
            e_ms_sorted = sorted(e_ms)
            tidset = set(t for t in e_tids if t)
            e_wire = [t for t in obstrace.get_tracer().traces()
                      if t["trace_id"] in tidset]
            e_per_stage: dict = {s: [] for s in WIRE_STAGES}
            e_durs = []
            for t in e_wire:
                bd = _sb(t)
                e_durs.append(t["duration_ms"])
                for s in WIRE_STAGES:
                    e_per_stage[s].append(bd.get(s, 0.0))
            e_durs.sort()
            e_stage_p50 = {s: pct(sorted(xs), 0.50)
                           for s, xs in e_per_stage.items()}
            e_stage_p99 = {s: pct(sorted(xs), 0.99)
                           for s, xs in e_per_stage.items()}
            stage_p50_vs_front = {
                s: {"evloop_ms": e_stage_p50.get(s)}
                for s in WIRE_STAGES
            }
            log(f"fleet: event edge wire p50={pct(e_durs, 0.50)}ms over "
                f"{len(e_wire)} traces; stage p50 vs front: "
                + ", ".join(
                    f"{s} {e_stage_p50.get(s)}/{stage_p50.get(s)}"
                    for s in ("accept", "proxy_connect", "write_back")))

            # -- (b) door-capacity headline: stub wire responder -------
            # One fixed request body; the stub answers every request
            # record with the latency round's REAL AdmissionReview
            # bytes, parsing only the frame skeleton (req ids) so the
            # responder does not tax the core the door is measured on.
            canned = last_body or b"{}"
            cap_lsock = _stub_wire_responder(canned)
            cap_door = EventFrontDoor(
                [{"host": "127.0.0.1",
                  "port": cap_lsock.getsockname()[1],
                  "probe_port": 0, "replica_id": "stub"}],
                probe_interval_s=3600.0,
            ).start()
            cap_body = admit_body(0)
            cap_req = (
                b"POST /v1/admit HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n" % len(cap_body)
            ) + cap_body
            obstrace.get_tracer().configure(sample_rate=0.02)
            cap_best = None
            cap_runs = []
            try:
                for rnd in range(cap_rounds):
                    got, wall = _pipelined_drive(
                        cap_door.port, cap_req, n_edge_cap)
                    rate = got / wall if wall else 0.0
                    cap_runs.append(round(rate, 1))
                    log(f"fleet: edge capacity round {rnd}: {got} reqs "
                        f"in {wall:.2f}s = {rate:.0f}/s")
                    if cap_best is None or rate > cap_best:
                        cap_best = rate
            finally:
                obstrace.get_tracer().configure(sample_rate=1.0)
                cap_door.stop()

            # -- (c) honest end-to-end pipelined rate ------------------
            e2e_got, e2e_wall = _pipelined_drive(
                edoor.port, cap_req, n_edge_e2e)
            e2e_rate = e2e_got / e2e_wall if e2e_wall else 0.0
            log(f"fleet: edge e2e {e2e_got} reviews in {e2e_wall:.2f}s "
                f"= {e2e_rate:.0f}/s through {n_replicas} replicas")

            # -- (d) connect-per-request, reported separately ----------
            t0 = time.perf_counter()
            conn_ok = 0
            for i in range(n_edge_conn):
                _st, _hd, _data, c = post(edoor.port, cap_body)
                conn_ok += 1 if _st == 200 else 0
                c.close()
            conn_wall = time.perf_counter() - t0
            conn_rps = conn_ok / conn_wall if conn_wall else 0.0
            log(f"fleet: edge connect-per-request {conn_rps:.0f}/s "
                f"({conn_ok}/{n_edge_conn} ok)")

            # -- (e) overload contract re-proof on this edge -----------
            # GC back on: bench_overload (OVERLOAD_r12) ran its storm
            # with the collector enabled, and this round re-proves that
            # contract on the new edge under the same conditions.
            gc.unfreeze()
            gc.enable()
            # Shed latency is read DOOR-SIDE from the wire traces, the
            # same way bench_overload records shed_answer_p99_ms: ten
            # closed-loop storm clients share this process's GIL with
            # the reactor, so their client-clock timings measure thread
            # scheduling, not the door.  A deep ring holds the storm.
            obstrace.configure(buffer_size=4096, sample_rate=1.0)
            obstrace.get_tracer().clear()
            odoor = EventFrontDoor(
                [h.wire_backend() for h in handles],
                max_inflight=1, admission_budget_s=2.0,
            ).start()
            o_lock = threading.Lock()
            o_counts: dict = {}
            o_shed_ms: list = []
            o_retry_after = 0
            o_mismatches: list = []
            o_problems: list = []
            n_storm = 10

            def _storm(tid: int) -> None:
                nonlocal o_retry_after
                conn = None
                end = time.monotonic() + overload_s
                i = tid
                while time.monotonic() < end:
                    body = admit_body(i % n_parity)
                    t0 = time.perf_counter()
                    try:
                        st, hd, data, conn = post(
                            odoor.port, body, conn)
                    except Exception as e:
                        conn = None
                        with o_lock:
                            o_problems.append(f"conn_error:{e!r}")
                        continue
                    dt_ms = (time.perf_counter() - t0) * 1e3
                    kind, out_resp = classify_response(st, data)
                    with o_lock:
                        o_counts[kind] = o_counts.get(kind, 0) + 1
                        if kind == SHED and st == 429:
                            o_shed_ms.append(dt_ms)
                            if hd.get("Retry-After"):
                                o_retry_after += 1
                        if kind == ACCEPTED:
                            want = oracle_verdicts[i % n_parity]
                            if not verdict_matches(
                                    out_resp, (want[0], list(want[1]))):
                                o_mismatches.append(i % n_parity)
                        if kind == PROBLEM:
                            o_problems.append(f"status={st}")
                    i += n_storm

            storm_ts = [threading.Thread(target=_storm, args=(i,))
                        for i in range(n_storm)]
            for t in storm_ts:
                t.start()
            for t in storm_ts:
                t.join(timeout=overload_s + 120.0)
                if t.is_alive():
                    raise RuntimeError("edge overload storm client "
                                       "wedged")
            shed_door_ms: list = []
            for t in obstrace.get_tracer().traces():
                if t.get("root") != "wire":
                    continue
                rs = next((s for s in t.get("spans", ())
                           if s.get("name") == "wire"), None)
                if rs is None:
                    continue
                if (rs.get("attrs") or {}).get("outcome") == "shed":
                    shed_door_ms.append(t["duration_ms"])
            shed_door_ms.sort()
            shed_p99 = pct(shed_door_ms, 0.99)
            o_shed_ms.sort()
            log(f"fleet: edge overload: {o_counts}, shed p99="
                f"{shed_p99}ms door-side over {len(shed_door_ms)} "
                f"traces (client-clock p99={pct(o_shed_ms, 0.99)}ms), "
                f"divergences={len(o_mismatches)}, "
                f"problems={len(o_problems)}")

            edge = {
                "edge": "evloop (selectors reactor, batched wire "
                        "protocol)",
                "door_capacity_rps": round(cap_best or 0.0, 1),
                "door_capacity_runs_rps": cap_runs,
                "door_capacity_reviews": n_edge_cap,
                "door_capacity_sample_rate": 0.02,
                "door_capacity_note": (
                    "front-door data plane vs an in-process stub wire "
                    "responder answering real AdmissionReview bytes — "
                    "isolates the rebuilt component from engine "
                    "throughput; best of rounds (single shared core, "
                    "co-tenant noise)"),
                "e2e_pipelined_rps": round(e2e_rate, 1),
                "e2e_pipelined_reviews": e2e_got,
                "connect_per_request_rps": round(conn_rps, 1),
                "seq_p50_ms": pct(e_ms_sorted, 0.50),
                "seq_p99_ms": pct(e_ms_sorted, 0.99),
                "wire_p50_ms": pct(e_durs, 0.50),
                "wire_p99_ms": pct(e_durs, 0.99),
                "wire_traces": len(e_wire),
                "stage_p50_ms": e_stage_p50,
                "stage_p99_ms": e_stage_p99,
                "front_door_edge": "evloop",
                "stage_p50_vs_front_door": stage_p50_vs_front,
                "overload": {
                    "counts": o_counts,
                    "shed_p99_ms": shed_p99,
                    "shed_p99_note": (
                        "door answer time from the wire traces "
                        "(accept..write_back), the OVERLOAD_r12 "
                        "shed_answer_p99_ms methodology — the storm "
                        "clients share the door's GIL, so their "
                        "client-clock timings measure scheduling"),
                    "shed_answer_n": len(shed_door_ms),
                    "shed_client_p99_ms": pct(o_shed_ms, 0.99),
                    "sheds_with_retry_after": o_retry_after,
                    "verdict_divergences": len(o_mismatches),
                    "problems": o_problems[:20],
                    "burst_s": overload_s,
                    "clients": n_storm,
                    "max_inflight": 1,
                },
            }
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "EDGE_r19.json"), "w") as f:
                json.dump(edge, f, indent=2, sort_keys=True)
        finally:
            # idempotent under an exception mid-rounds (gc.enable on an
            # enabled collector and unfreeze with nothing frozen are
            # both no-ops); ring size back to the boot default
            gc.unfreeze()
            gc.enable()
            obstrace.configure(
                buffer_size=int(os.environ.get("GK_TRACE_BUFFER",
                                               "256")))
            if odoor is not None:
                odoor.stop()
            if cap_lsock is not None:
                try:
                    cap_lsock.close()
                except OSError:
                    pass
            edoor.stop()

        return {
            "metric": (
                f"combined streamed reviews/s, {n_replicas} replicas x "
                f"{n_templates} constraints (shared warm snapshot)"
            ),
            "value": round(combined, 1),
            "unit": "reviews/s",
            "vs_baseline": 0,
            "fleet_reviews_per_s": round(combined, 1),
            "fleet_replicas": n_replicas,
            "fleet_templates": n_templates,
            "fleet_stream_chunk": chunk,
            "fleet_stream_wall_s": round(stream_wall, 2),
            "fleet_replica_stream": {
                rid: {
                    "reviews_per_s": s.get("reviews_per_s"),
                    "s": s.get("s"),
                }
                for rid, s in sorted(stream_out.items())
            },
            "fleet_ready_s": {
                h.replica_id: h.ready_s for h in handles
            },
            "fleet_spawn_s": {
                h.replica_id: h.spawn_s for h in handles
            },
            "fleet_ready_max_s": max(h.ready_s for h in handles),
            "fleet_cold_seed_ready_s": seed_ready_s,
            "fleet_restore_outcomes": {
                h.replica_id: h.ready.get("restore_outcome")
                for h in handles
            },
            "fleet_parity_across_replicas": parity,
            "fleet_parity_vs_oracle": parity_vs_oracle,
            "fleet_seq_p50_ms": pct(seq_ms, 0.50),
            "fleet_seq_p99_ms": pct(seq_ms, 0.99),
            "fleet_http_reviews_per_s": round(http_rps, 1),
            "fleet_replica_latency": replica_lat,
            "fleet_frontdoor": door.stats(),
            "obs_wire": obs_wire,
            "edge": edge,
            "edge_door_capacity_rps": edge["door_capacity_rps"],
            "edge_e2e_pipelined_rps": edge["e2e_pipelined_rps"],
            "edge_connect_per_request_rps": edge[
                "connect_per_request_rps"],
        }
    finally:
        if door is not None:
            door.stop()
        for h in handles:
            h.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_edge_obs() -> dict:
    """Reactor flight deck (ISSUE 20, recorded EDGEOBS_r20): the event
    edge's observability plane measured on the door's own data plane.

      (a) steady-state telemetry overhead: the EDGE_r19 door-capacity
          recipe (event door vs an in-process stub wire responder
          answering real AdmissionReview bytes) run as PAIRED rounds —
          reactor telemetry detached (the loop's pre-ISSUE-20 dispatch:
          ``_telem is None``, one untaken branch per site) vs attached
          (the shipped default), arm order alternated per pair,
          median-of-arms estimator (the profiler-overhead methodology:
          co-tenant drift hits both arms of a pair almost equally);
      (b) the door-capacity headline with telemetry ON — the number a
          deployment actually gets — against EDGE_r19's recorded
          capacity (acceptance: within 5%);
      (c) a seeded 250ms ``evloop.slow_callback`` stall (latency rule
          on the heartbeat's registered fault point) caught END TO END:
          the culprit table and the flight-recorder ``evloop_stall``
          event name the heartbeat callback, the cross-thread watchdog
          captures the reactor stack MID-stall within one scan period
          of the budget and dumps an incident, the next heartbeat's
          skew surfaces in ``evloop_lag_seconds``, and the
          force-sampled tick lands in the tick histogram.
    """
    import gc
    import tempfile

    from gatekeeper_tpu import faults
    from gatekeeper_tpu.fleet.evdoor import EventFrontDoor
    from gatekeeper_tpu.fleet.wirelistener import _envelope
    from gatekeeper_tpu.metrics.exporter import render_prometheus
    from gatekeeper_tpu.obs import flightrec, reactorobs
    from gatekeeper_tpu.obs import trace as obstrace
    from gatekeeper_tpu.util.synthetic import make_pods
    from gatekeeper_tpu.webhook.policy import AdmissionResponse

    n_cap = int(os.environ.get("BENCH_EDGEOBS_CAP_REVIEWS", "40000"))
    n_pairs = int(os.environ.get("BENCH_EDGEOBS_PAIRS", "8"))
    stall_s = float(os.environ.get("BENCH_EDGEOBS_STALL_S", "0.25"))
    # the watchdog samples the breadcrumb every WATCHDOG_TICK_S, so the
    # drill budget must undercut the stall by at least one scan period
    # or only an exact-boundary scan could catch it mid-flight; the
    # production default (STALL_BUDGET_S) is unchanged
    budget_s = float(os.environ.get("BENCH_EDGEOBS_BUDGET_S", "0.15"))

    # one fixed request; the stub answers every record with one fixed
    # realistic AdmissionReview allow body, so the pipelined clients
    # count responses by byte arithmetic (the EDGE_r19 recipe)
    pod = make_pods(1, seed=99, violation_rate=0.3)[0]
    req_json = json.dumps({"request": {
        "uid": "edge-obs-0",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": pod["metadata"]["name"],
        "namespace": pod["metadata"]["namespace"],
        "operation": "CREATE",
        "userInfo": {"username": "edge-obs"},
        "object": pod,
    }}).encode()
    cap_req = (
        b"POST /v1/admit HTTP/1.1\r\nHost: bench\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: %d\r\n\r\n" % len(req_json)
    ) + req_json
    canned = _envelope(AdmissionResponse(True).to_dict(uid="edge-obs-0"))

    lsock = _stub_wire_responder(canned)
    door = EventFrontDoor(
        [{"host": "127.0.0.1", "port": lsock.getsockname()[1],
          "probe_port": 0, "replica_id": "stub"}],
        probe_interval_s=3600.0,
    ).start()
    loop = door._loop
    out: dict = {"edge": "evloop (selectors reactor, batched wire "
                         "protocol) vs in-process stub wire responder"}
    try:
        # ---- (a)+(b) paired capacity rounds ---------------------------
        obstrace.get_tracer().configure(sample_rate=0.02)
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            _pipelined_drive(door.port, cap_req, max(2000, n_cap // 8))

            def _cap_round(telemetry_on: bool) -> float:
                if telemetry_on:
                    reactorobs.attach(loop, "evdoor")
                else:
                    reactorobs.detach(loop)
                got, wall = _pipelined_drive(door.port, cap_req, n_cap)
                return round(got / wall, 1) if wall else 0.0

            rates_off, rates_on = [], []
            for i in range(n_pairs):
                if i % 2 == 0:
                    off = _cap_round(False)
                    on = _cap_round(True)
                else:
                    on = _cap_round(True)
                    off = _cap_round(False)
                rates_off.append(off)
                rates_on.append(on)
                log(f"edge_obs: pair {i}: off={off}/s on={on}/s")
        finally:
            gc.unfreeze()
            gc.enable()
            obstrace.get_tracer().configure(sample_rate=1.0)
            reactorobs.attach(loop, "evdoor")  # shipped default state
        med_off = sorted(rates_off)[len(rates_off) // 2]
        med_on = sorted(rates_on)[len(rates_on) // 2]
        overhead_pct = round((1.0 - med_on / med_off) * 100.0, 2)
        cap_best = max(rates_on)

        prior = None
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "EDGE_r19.json")) as f:
                prior = json.load(f).get("door_capacity_rps")
        except OSError:
            pass
        vs_prior = (round(cap_best / prior, 4)
                    if prior else None)
        out.update({
            "telemetry_overhead_pct": overhead_pct,
            "rates_off_rps": rates_off,
            "rates_on_rps": rates_on,
            "overhead_note": (
                "paired off/on rounds, arm order alternated per pair, "
                "median-of-arms; off = reactor telemetry detached "
                "(the pre-ISSUE-20 loop)"),
            "door_capacity_rps": cap_best,
            "door_capacity_off_rps": max(rates_off),
            "capacity_on_vs_off": round(cap_best / max(rates_off), 4),
            "capacity_control_note": (
                "the off-arm best is a SAME-RUN control: this box is "
                "one shared core and run-to-run host steal swings "
                "rates ±30% (EDGE_r19 documents 27k..63k for identical "
                "code), so on-vs-off within one run isolates telemetry "
                "cost from host drift"),
            "door_capacity_reviews": n_cap,
            "door_capacity_sample_rate": 0.02,
            "edge_r19_capacity_rps": prior,
            "capacity_vs_edge_r19": vs_prior,
            "capacity_within_5pct": (vs_prior is not None
                                     and vs_prior >= 0.95),
        })
        log(f"edge_obs: overhead {overhead_pct}% (median off={med_off} "
            f"on={med_on}); capacity {cap_best}/s vs EDGE_r19 {prior}/s")

        # ---- (c) the seeded stall, end to end -------------------------
        ddir = tempfile.mkdtemp(prefix="gk-edgeobs-flightrec-")
        flightrec.get_recorder().configure(dump_dir=ddir)
        flightrec.get_recorder().clear()
        reactorobs.detach(loop)
        telem = reactorobs.attach(loop, "evdoor", stall_budget_s=budget_s)

        def _tick_sum() -> float:
            m = re.search(
                r'gatekeeper_evloop_tick_seconds_sum\{[^}]*'
                r'loop="evdoor"[^}]*\}\s+([0-9.eE+-]+)',
                render_prometheus())
            return float(m.group(1)) if m else 0.0

        tick_sum0 = _tick_sum()
        plane = faults.install(seed=20)
        plane.add(faults.EVLOOP_SLOW_CALLBACK,
                  faults.FaultRule(mode=faults.LATENCY,
                                   latency_s=stall_s, count=1))
        lag_max = 0.0
        slow_ev = wd_ev = None
        deadline = time.monotonic() + 5.0
        try:
            while time.monotonic() < deadline:
                if telem.lag > lag_max:
                    lag_max = telem.lag
                for ev in flightrec.get_recorder().events():
                    if ev.get("type") != flightrec.EVLOOP_STALL:
                        continue
                    if ev.get("via") == "slow_callback":
                        slow_ev = ev
                    elif ev.get("via") == "watchdog":
                        wd_ev = ev
                if slow_ev and wd_ev and lag_max > 0.05:
                    break
                time.sleep(0.005)
        finally:
            faults.uninstall()
        culprits = telem.culprits()
        culprit = culprits[0]["callback"] if culprits else None

        # the force-sampled stalled tick must surface in the histogram
        # once the 0.5s flush cadence passes
        tick_delta = 0.0
        hist_deadline = time.monotonic() + 3.0
        while time.monotonic() < hist_deadline:
            tick_delta = _tick_sum() - tick_sum0
            if tick_delta >= stall_s * 0.8:
                break
            time.sleep(0.05)

        held_ms = (wd_ev or {}).get("held_ms")
        excess_ms = (round(held_ms - budget_s * 1e3, 1)
                     if held_ms is not None else None)
        stack = (wd_ev or {}).get("stack") or []
        out["stall"] = {
            "seeded_latency_ms": round(stall_s * 1e3, 1),
            "watchdog_budget_ms": round(budget_s * 1e3, 1),
            "watchdog_tick_ms": round(
                reactorobs.WATCHDOG_TICK_S * 1e3, 1),
            "culprit": culprit,
            "culprit_named_ok": bool(culprit and "_beat" in culprit),
            "slow_callback_event": (
                {k: slow_ev[k] for k in
                 ("callback", "kind", "duration_ms") if k in slow_ev}
                if slow_ev else None),
            "watchdog_held_ms": held_ms,
            "watchdog_excess_ms": excess_ms,
            "within_one_watchdog_period": (
                excess_ms is not None and excess_ms
                <= reactorobs.WATCHDOG_TICK_S * 1e3 + 25.0),
            "stack_names_culprit": any("_beat" in fr for fr in stack),
            "stack_depth": len(stack),
            "lag_seconds_max": round(lag_max, 4),
            "lag_visible": lag_max >= 0.1,
            "tick_hist_sum_delta_s": round(tick_delta, 4),
            "tick_hist_saw_stall": tick_delta >= stall_s * 0.8,
            "incident_dumps": sorted(os.listdir(ddir)),
        }
        log(f"edge_obs: stall drill: culprit={culprit} "
            f"lag_max={lag_max * 1e3:.1f}ms held={held_ms}ms "
            f"dumps={out['stall']['incident_dumps']}")
    finally:
        door.stop()
        try:
            lsock.close()
        except OSError:
            pass

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "EDGEOBS_r20.json"), "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    return {
        "metric": ("reactor telemetry overhead on the event-edge door "
                   "capacity (paired off/on rounds)"),
        "value": out.get("telemetry_overhead_pct"),
        "unit": "%",
        "vs_baseline": 0,
        **out,
    }


def bench_chaos_fleet() -> dict:
    """Self-healing fleet under chaos (ISSUE 8, recorded as CHAOS_r08):
    two supervised replicas restore one sealed snapshot behind the front
    door; seeded fault points crash one replica (`fleet.replica_crash`,
    an error-mode rule pulsed in-child -> hard exit rc 23) and wedge the
    other (`fleet.replica_wedge`, a hang-mode rule parking its command
    pipe) MID-LOAD, while a sequential client streams parity-checked
    admissions through the door.  Recorded:

      - failed admissions (non-200 through the door) — the acceptance
        criterion is ZERO: the door's immediate ejection + bounded
        retry covers every kill window;
      - verdict parity vs a fresh interpreter oracle before/during/
        after each failure (allow/deny + rendered message bytes);
      - per-failure recovery: eject->readmit wall seconds and the
        supervisor's warm spawn-to-ready (< 5s criterion);
      - a zero-failure rolling restart (drain stats included);
      - mesh degradation (subprocess, virtual 4-device mesh): a stalled
        collective trips the watchdog -> breaker -> width 4 -> 2, with
        byte-parity preserved at the narrower width.
    """
    import re as _re
    import shutil
    import tempfile

    from gatekeeper_tpu.fleet import EventFrontDoor, ReplicaSupervisor
    from gatekeeper_tpu.fleet.replica import spawn_replica
    from gatekeeper_tpu.snapshot import Snapshotter
    from gatekeeper_tpu.util.synthetic import (
        build_driver,
        build_oracle,
        make_pods,
    )

    n_templates = int(os.environ.get("BENCH_CHAOS_TEMPLATES", "2"))
    n_resources = int(os.environ.get("BENCH_CHAOS_RESOURCES", "64"))
    duration_s = float(os.environ.get("BENCH_CHAOS_DURATION_S", "25"))
    crash_after = int(os.environ.get("BENCH_CHAOS_CRASH_AFTER", "80"))
    wedge_after = int(os.environ.get("BENCH_CHAOS_WEDGE_AFTER", "40"))

    root = tempfile.mkdtemp(prefix="gk-chaos-fleet-")
    snap_dir = os.path.join(root, "snap")
    # no cache dir is handed to the replicas: each resolves the fixed one
    # itself (ops/xlacache.py) — a directory that moves never hits
    os.makedirs(snap_dir)

    client = build_driver(n_templates, n_resources)
    client.audit_capped(50)
    assert Snapshotter(client, snap_dir, interval_s=0.0).write_once()

    n_corpus = min(n_resources, 48)
    pods = make_pods(n_corpus, seed=31, violation_rate=0.4)
    reqs = []
    for i, p in enumerate(pods):
        reqs.append({
            "uid": f"chaos-{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "chaos-bench"},
            "object": p,
        })
    oracle = build_oracle(n_templates, n_resources)
    oracle_verdicts = []
    for req in reqs:
        results = oracle.review(
            {k: req[k] for k in
             ("kind", "name", "namespace", "operation", "object")}
        ).results()
        oracle_verdicts.append((not results, sorted(r.msg for r in results)))

    base_env = {"JAX_PLATFORMS": "cpu"}
    # the seeded fault specs ride into each child via GK_CHAOS
    # (faults.install_from_spec); restarts come back CLEAN — the
    # supervisor respawns with its own env
    crash_env = dict(base_env, GK_CHAOS=json.dumps({
        "seed": 8, "rules": [{
            "point": "fleet.replica_crash", "mode": "error",
            "after": crash_after, "count": 1,
        }],
    }))
    wedge_env = dict(base_env, GK_CHAOS=json.dumps({
        "seed": 8, "rules": [{
            "point": "fleet.replica_wedge", "mode": "hang",
            "hang_s": 120.0, "after": wedge_after, "count": 1,
        }],
    }))

    events = []  # (t, replica_id, "eject"|"readmit")
    door_box = {}

    def on_change(rid, backend):
        d = door_box.get("door")
        events.append((time.monotonic(), rid,
                       "eject" if backend is None else "readmit"))
        if d is None:
            return
        if backend is None:
            d.suspend(rid)
        else:
            d.set_backend(rid, backend["host"], backend["port"],
                          backend.get("probe_port", 0))

    sup = ReplicaSupervisor(
        snapshot_dir=snap_dir, env=base_env,
        heartbeat_s=0.25, miss_threshold=2, backoff_base_s=0.1,
        on_backend_change=on_change,
    )
    door = None
    try:
        # chaos-armed initial spawns, adopted under supervision (the
        # supervisor's own restarts use the clean env)
        h_wedge = spawn_replica("r0", snap_dir, env=wedge_env)
        h_crash = spawn_replica("r1", snap_dir, env=crash_env)
        for h in (h_wedge, h_crash):
            assert h.ready.get("restore_outcome") == "restored", h.ready
            sup.adopt(h)
        sup.start_monitor()
        door = EventFrontDoor(
            [h_wedge.wire_backend(), h_crash.wire_backend()],
            probe_interval_s=0.1,
        ).start()
        door_box["door"] = door
        log(f"chaos_fleet: r0(wedge@~{wedge_after} pings) "
            f"r1(crash@~{crash_after} pulses) streaming {duration_s}s")

        import http.client as _httpc

        def post(body):
            c = _httpc.HTTPConnection("127.0.0.1", door.port, timeout=30)
            try:
                c.request("POST", "/v1/admit", body=body,
                          headers={"Content-Type": "application/json"})
                r = c.getresponse()
                return r.status, r.read()
            finally:
                c.close()

        total = failed = divergences = 0
        t_start = time.monotonic()
        i = 0
        while time.monotonic() - t_start < duration_s:
            req = reqs[i % len(reqs)]
            body = json.dumps({"request": req}).encode()
            try:
                st, data = post(body)
            except Exception:
                st, data = 0, b""
            total += 1
            if st != 200:
                failed += 1
            else:
                out = json.loads(data)["response"]
                allowed = out["allowed"]
                msgs = sorted(
                    _re.sub(r"^\[denied by [^\]]+\] ", "", m)
                    for m in (out.get("status") or {}).get(
                        "message", "").split("\n") if m
                ) if not allowed else []
                o_allowed, o_msgs = oracle_verdicts[i % len(reqs)]
                if allowed != o_allowed or (
                    not allowed and msgs != o_msgs
                ):
                    divergences += 1
            i += 1
            time.sleep(0.002)  # pace: the stream must span both faults

        # both chaos victims must have been restarted warm by now
        recovery = {}
        for rid in ("r0", "r1"):
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                st = sup.status()[rid]
                if st["state"] == "running" and st["restarts"] >= 1:
                    break
                time.sleep(0.1)
            st = sup.status()[rid]
            ejects = [t for t, r, k in events if r == rid and k == "eject"]
            readmits = [t for t, r, k in events
                        if r == rid and k == "readmit" and t > (
                            ejects[0] if ejects else 0)]
            recovery[rid] = {
                "state": st["state"],
                "restarts": st["restarts"],
                "last_exit_rc": st["last_exit_rc"],
                "spawn_to_ready_s": st["last_restart_s"],
                "eject_to_readmit_s": round(
                    readmits[0] - ejects[0], 3
                ) if ejects and readmits else None,
            }
        new_handles = {h.replica_id: h for h in sup.handles()}
        restore_outcomes = {
            rid: h.ready.get("restore_outcome")
            for rid, h in new_handles.items()
        }

        # zero-failure rolling restart with drain stats (the upgrade path)
        rolled = sup.rolling_restart(drain_deadline_ms=500.0)
        roll_ok = all(r.get("ok") for r in rolled.values())

        stats = door.stats()
        log(f"chaos_fleet: {total} reqs, {failed} failed, "
            f"{divergences} divergences, recovery={recovery}, "
            f"door retries={stats['retries']}")

        mesh = _chaos_mesh_stall()
        log(f"chaos_fleet: mesh stall {mesh}")

        ok = (
            failed == 0 and divergences == 0
            and all(r["state"] == "running" and r["restarts"] >= 1
                    for r in recovery.values())
            and all((r["spawn_to_ready_s"] or 99) < 5.0
                    for r in recovery.values())
            and all(v == "restored" for v in restore_outcomes.values())
            and mesh.get("parity_during") and mesh.get("parity_after")
            and mesh.get("width_after") == 2
        )
        out = {
            "metric": (
                "chaos fleet: failed admissions with one replica crashed "
                "+ one wedged mid-load (2 supervised replicas)"
            ),
            "value": float(failed),
            "unit": "failed_admissions",
            "vs_baseline": 0,
            "chaos_ok": ok,
            "chaos_requests": total,
            "chaos_failed_admissions": failed,
            "chaos_verdict_divergences": divergences,
            "chaos_recovery": recovery,
            "chaos_restore_outcomes": restore_outcomes,
            "chaos_rolling_restart": {
                rid: {"ok": r.get("ok"),
                      "drain_ms": (r.get("drain") or {}).get("drain_ms"),
                      "drained": (r.get("drain") or {}).get("drained"),
                      "restart_s": r.get("restart_s")}
                for rid, r in rolled.items()
            },
            "chaos_rolling_ok": roll_ok,
            "chaos_frontdoor": stats,
            "chaos_mesh_stall": mesh,
            "chaos_config": {
                "templates": n_templates, "resources": n_resources,
                "duration_s": duration_s, "crash_after": crash_after,
                "wedge_after": wedge_after,
            },
        }
        record = {k: v for k, v in out.items()
                  if k not in ("metric", "value", "unit", "vs_baseline")}
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "CHAOS_r08.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        log(f"chaos_fleet recorded: {path}")
        return out
    finally:
        if door is not None:
            door.stop()
        sup.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_overload() -> dict:
    """Overload robustness (ISSUE 12, recorded as OVERLOAD_r12): 2
    replicas restore one sealed snapshot behind the overload-armed front
    door (per-backend inflight bound, 1s admission budget, retry
    budget); closed-loop client fleets drive 1x/2x/5x/10x the
    saturation concurrency through the door.  The fleets HONOR the shed
    contract — a 429's Retry-After paces them, capped at
    BENCH_OVERLOAD_BACKOFF_S so they stay far more aggressive than the
    door asks — because that is what the header is for; an extra
    no-backoff phase records the abusive floor (a tight shed/retry loop
    that on this one-core box steals the door's own CPU), where sheds
    must STILL answer fast with exact verdicts.  Recorded per level:
    offered and GOODPUT rates (no congestive collapse: goodput at 10x
    must hold >= 70% of the 1x peak), accepted-request p50/p99 (p99
    within the admission budget), shed counts by layer, and shed-answer
    latency (door-side, from the wire traces: the single-digit-ms
    criterion).  Verdict parity vs a fresh interpreter oracle is
    checked on EVERY accepted response at every level — shedding drops
    requests, never accuracy.  A seeded `fleet.overload_storm` chaos
    phase then proves zero divergence while shedding under injected
    slow-replica latency, and the brownout ladder is observed stepping
    UP under the storm and RECOVERING to level 0 with hysteresis."""
    import http.client as _httpc
    import shutil
    import tempfile
    import threading

    from gatekeeper_tpu import faults as _faults
    from gatekeeper_tpu.faults import FaultRule
    from gatekeeper_tpu.fleet import EventFrontDoor, spawn_fleet
    from gatekeeper_tpu.obs import brownout as obsbrownout
    from gatekeeper_tpu.obs import trace as obstrace
    from gatekeeper_tpu.snapshot import Snapshotter
    from gatekeeper_tpu.util.overloadcheck import (
        classify_response,
        verdict_matches,
    )
    from gatekeeper_tpu.util.synthetic import (
        build_driver,
        build_oracle,
        make_pods,
    )

    n_templates = int(os.environ.get("BENCH_OVERLOAD_TEMPLATES", "2"))
    n_resources = int(os.environ.get("BENCH_OVERLOAD_RESOURCES", "256"))
    n_corpus = int(os.environ.get("BENCH_OVERLOAD_CORPUS", "64"))
    phase_s = float(os.environ.get("BENCH_OVERLOAD_PHASE_S", "6"))
    levels = [int(x) for x in os.environ.get(
        "BENCH_OVERLOAD_LEVELS", "1,2,5,10").split(",")]
    base_clients = int(os.environ.get("BENCH_OVERLOAD_BASE_CLIENTS", "2"))
    max_inflight = int(os.environ.get("BENCH_OVERLOAD_INFLIGHT", "1"))
    budget_s = float(os.environ.get("BENCH_OVERLOAD_BUDGET_S", "1.0"))
    max_pending = int(os.environ.get("BENCH_OVERLOAD_MAX_PENDING", "64"))

    root = tempfile.mkdtemp(prefix="gk-overload-bench-")
    snap_dir = os.path.join(root, "snap")
    # no cache dir is handed to the replicas: each resolves the fixed one
    # itself (ops/xlacache.py) — a directory that moves never hits
    os.makedirs(snap_dir)

    client = build_driver(n_templates, n_resources)
    client.audit_capped(50)
    assert Snapshotter(client, snap_dir, interval_s=0.0).write_once()

    pods = make_pods(n_corpus, seed=61, violation_rate=0.4)
    reqs = []
    for i, p in enumerate(pods):
        reqs.append({
            "uid": f"ov-{i}",
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "name": p["metadata"]["name"],
            "namespace": p["metadata"]["namespace"],
            "operation": "CREATE",
            "userInfo": {"username": "overload-bench"},
            "object": p,
        })
    bodies = [json.dumps({"request": r}).encode() for r in reqs]
    oracle = build_oracle(n_templates, n_resources)
    oracle_verdicts = []
    for req in reqs:
        results = oracle.review(
            {k: req[k] for k in
             ("kind", "name", "namespace", "operation", "object")}
        ).results()
        oracle_verdicts.append(
            (not results, sorted(r.msg for r in results)))

    def verdict_ok(out: dict, idx: int) -> bool:
        # shared normalization with tools/check_overload.py: the tier-1
        # gate and this artifact must judge the same bytes the same way
        return verdict_matches(out, oracle_verdicts[idx])

    handles = spawn_fleet(
        2, snapshot_dir=snap_dir,
        env={"JAX_PLATFORMS": "cpu"},
        extra_flags=["--webhook-max-pending", str(max_pending)],
    )
    door = None
    ctl = obsbrownout.get_controller()
    try:
        for h in handles:
            assert h.ready.get("restore_outcome") == "restored", h.ready
        door = EventFrontDoor(
            [h.wire_backend() for h in handles], probe_interval_s=0.1,
            max_inflight=max_inflight, admission_budget_s=budget_s,
        ).start()
        # a deep trace ring: door-side shed latency is read from the
        # wire traces (outcome attr), and the storm produces thousands
        obstrace.configure(buffer_size=4096, sample_rate=1.0)
        # the bench parent IS the door process: its global brownout
        # controller sees every door shed via record_shed, so the
        # ladder is driven by REAL signals (no actions wired — the
        # parent has no audit/profiler to degrade; the ladder itself
        # is the observable)
        ctl.reset()
        ctl.start()
        level_series: list = []  # (wall_s, level) across the whole run
        series_stop = threading.Event()
        t_bench0 = time.monotonic()

        def poll_levels():
            while not series_stop.wait(0.1):
                level_series.append(
                    (round(time.monotonic() - t_bench0, 1), ctl.level))

        poller = threading.Thread(target=poll_levels, daemon=True)
        poller.start()

        # warm both replicas through the door (jit, memos, connections)
        for i in range(16):
            st, _hd, _b = _door_post(door.port, bodies[i % len(bodies)])
            assert st in (200, 429), st

        # shed-backoff the client fleet applies on a 429: the shed
        # contract's Retry-After is 1s — these clients are IMPATIENT
        # (they cap the advertised wait at this fraction) but not
        # abusive; a separate no-backoff phase records the abusive
        # floor.  On this one-core box the load generators share the
        # GIL with the door, so a no-backoff fleet's shed loop consumes
        # the very CPU goodput needs — precisely the storm Retry-After
        # exists to prevent
        backoff_s = float(os.environ.get("BENCH_OVERLOAD_BACKOFF_S",
                                         "0.25"))

        def run_phase(n_clients: int, duration: float,
                      backoff=None):
            # per-phase trace isolation: door-side latency (sheds AND
            # accepted) is read from the wire ring afterwards, so it
            # must hold only THIS phase's requests
            obstrace.get_tracer().clear()
            backoff = backoff_s if backoff is None else backoff
            results: list = []
            lock = threading.Lock()
            stop_at = time.monotonic() + duration

            def slam(tid: int):
                # one persistent keep-alive connection per client: a
                # real apiserver reuses connections, and a fresh
                # connection per request would bill a handler-thread
                # spawn to every shed
                conn = None
                i = tid
                while time.monotonic() < stop_at:
                    idx = i % len(reqs)
                    i += n_clients
                    t0 = time.perf_counter()
                    try:
                        if conn is None:
                            conn = _httpc.HTTPConnection(
                                "127.0.0.1", door.port, timeout=30)
                        conn.request(
                            "POST", "/v1/admit", body=bodies[idx],
                            headers={
                                "Content-Type": "application/json"})
                        r = conn.getresponse()
                        data = r.read()
                        st = r.status
                        retry_after = r.getheader("Retry-After")
                    except Exception:
                        st, data, retry_after = 0, b"", None
                        try:
                            if conn is not None:
                                conn.close()
                        except OSError:
                            pass
                        conn = None
                    dur = time.perf_counter() - t0
                    with lock:
                        results.append((st, dur, data, idx))
                    if st == 429 and backoff > 0:
                        try:
                            wait = min(float(retry_after or 1.0),
                                       backoff)
                        except ValueError:
                            wait = backoff
                        time.sleep(wait)
                if conn is not None:
                    try:
                        conn.close()
                    except OSError:
                        pass

            ts = [threading.Thread(target=slam, args=(t,))
                  for t in range(n_clients)]
            t0 = time.monotonic()
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=duration + 120)
                if t.is_alive():
                    raise RuntimeError("overload client wedged — a "
                                       "refusal path is hanging")
            wall = time.monotonic() - t0
            return results, wall

        # shared taxonomy with tools/check_overload.py (one copy: the
        # tier-1 gate and this artifact cannot drift apart)
        classify = classify_response

        def pct(xs, q):
            if not xs:
                return None
            xs = sorted(xs)
            return round(xs[min(int(q * len(xs)), len(xs) - 1)], 3)

        def wire_latencies():
            """{outcome: [duration_ms]} over this phase's wire traces —
            the DOOR's answer time (accept..write_back), free of the
            co-located load generators' client-thread scheduling noise
            (a real apiserver does not share the door's GIL)."""
            out: dict = {}
            for t in obstrace.get_tracer().traces():
                if t.get("root") != "wire":
                    continue
                rootspan = next(
                    (s for s in t.get("spans", ())
                     if s.get("name") == "wire"), None)
                if rootspan is None:
                    continue
                oc = (rootspan.get("attrs") or {}).get("outcome")
                if oc:
                    out.setdefault(oc, []).append(t["duration_ms"])
            return out

        phase_out = {}
        divergence_box = [0]

        def measure(label: str, n_clients: int, backoff=None,
                    duration=None):
            results, wall = run_phase(
                n_clients, phase_s if duration is None else duration,
                backoff=backoff,
            )
            counts: dict = {}
            accepted_client_ms, divergences = [], 0
            door_shed = replica_shed = expired = errors = 0
            for st, dur, data, idx in results:
                kind, out = classify(st, data)
                counts[kind] = counts.get(kind, 0) + 1
                if kind == "accepted":
                    accepted_client_ms.append(dur * 1e3)
                    if not verdict_ok(out, idx):
                        divergences += 1
                elif kind == "shed":
                    if st == 429:
                        door_shed += 1
                    else:
                        replica_shed += 1
                elif kind == "expired":
                    expired += 1
                else:
                    errors += 1
            wire = wire_latencies()
            shed_wire_ms = wire.get("shed", [])
            ok_wire_ms = wire.get("ok", [])
            divergence_box[0] += divergences
            accepted = counts.get("accepted", 0)
            phase_out[label] = {
                "clients": n_clients,
                "offered_rps": round(len(results) / wall, 1),
                "goodput_rps": round(accepted / wall, 1),
                "accepted": accepted,
                "accepted_p50_ms": pct(ok_wire_ms, 0.50),
                "accepted_p99_ms": pct(ok_wire_ms, 0.99),
                "accepted_client_p50_ms": pct(accepted_client_ms, 0.50),
                "accepted_client_p99_ms": pct(accepted_client_ms, 0.99),
                "door_sheds": door_shed,
                "replica_sheds": replica_shed,
                "expired": expired,
                "errors": errors,
                "verdict_divergences": divergences,
                "shed_answer_p50_ms": pct(shed_wire_ms, 0.50),
                "shed_answer_p99_ms": pct(shed_wire_ms, 0.99),
                "shed_answer_n": len(shed_wire_ms),
                "brownout_level_end": ctl.level,
            }
            log(f"overload {label} ({n_clients} clients): "
                f"{phase_out[label]}")

        for mult in levels:
            measure(f"{mult}x", base_clients * mult)
        # the abusive floor: the same 10x fleet IGNORING Retry-After —
        # a tight shed/retry loop that (on this one-core box) steals
        # the door's own CPU.  Recorded for honesty: sheds must stay
        # fast and verdicts exact even under the storm the contract
        # exists to prevent; the goodput criterion applies to the
        # protocol-conformant fleet above
        measure(f"{levels[-1]}x_nobackoff",
                base_clients * levels[-1], backoff=0.0, duration=4.0)
        divergences_total = divergence_box[0]

        # ---- seeded chaos storm: shedding must never corrupt verdicts ----
        plane = _faults.install(seed=12)
        plane.add("fleet.overload_storm",
                  FaultRule(mode="latency", latency_s=0.25))
        storm_results, storm_wall = run_phase(base_clients * 6, 4.0)
        _faults.uninstall()
        storm_counts: dict = {}
        storm_divergences = 0
        for st, dur, data, idx in storm_results:
            kind, out = classify(st, data)
            storm_counts[kind] = storm_counts.get(kind, 0) + 1
            if kind == "accepted" and not verdict_ok(out, idx):
                storm_divergences += 1
        storm_level_peak = max(
            (lv for _t, lv in level_series), default=0)
        log(f"overload chaos storm: {storm_counts}, divergences="
            f"{storm_divergences}, ladder peak={storm_level_peak}")

        # ---- recovery: the ladder must step back DOWN with hysteresis ----
        recovered = False
        recovery_deadline = time.monotonic() + 60.0
        while time.monotonic() < recovery_deadline:
            if ctl.level == 0:
                recovered = True
                break
            time.sleep(0.25)
        recovery_s = round(time.monotonic() - t_bench0, 1)
        series_stop.set()
        poller.join(timeout=5)

        goodput_1x = phase_out[f"{levels[0]}x"]["goodput_rps"]
        goodput_peak = max(p["goodput_rps"] for p in phase_out.values())
        top = f"{levels[-1]}x"
        goodput_top = phase_out[top]["goodput_rps"]
        ratio = round(goodput_top / max(goodput_1x, 1e-9), 3)
        shed_p99 = phase_out[top]["shed_answer_p99_ms"]
        accepted_p99 = phase_out[top]["accepted_p99_ms"]
        ok = (
            ratio >= 0.7
            and divergences_total == 0
            and storm_divergences == 0
            and storm_counts.get("shed", 0) > 0
            and (shed_p99 is not None and shed_p99 < 10.0)
            and (accepted_p99 is not None
                 and accepted_p99 <= budget_s * 1e3)
            and storm_level_peak >= 1
            and recovered
        )
        out = {
            "metric": (
                f"goodput at {top} offered load as a fraction of the "
                f"1x saturation goodput (2 replicas, overload-armed "
                f"door)"
            ),
            "value": ratio,
            "unit": "goodput_ratio",
            "vs_baseline": 0,
            "overload_ok": ok,
            "overload_goodput_ratio_10x": ratio,
            "overload_goodput_1x_rps": goodput_1x,
            "overload_goodput_peak_rps": goodput_peak,
            "overload_phases": phase_out,
            "overload_shed_answer_p99_ms": shed_p99,
            "overload_accepted_p99_ms": accepted_p99,
            "overload_budget_ms": budget_s * 1e3,
            "overload_verdict_divergences": divergences_total,
            "overload_chaos": {
                "storm_counts": storm_counts,
                "storm_divergences": storm_divergences,
                "ladder_peak_level": storm_level_peak,
                "ladder_recovered": recovered,
                "recovered_by_s": recovery_s,
            },
            "overload_brownout_series": level_series[-400:],
            "overload_frontdoor": door.stats(),
            "overload_config": {
                "templates": n_templates, "resources": n_resources,
                "phase_s": phase_s, "levels": levels,
                "base_clients": base_clients,
                "max_inflight": max_inflight,
                "budget_s": budget_s, "max_pending": max_pending,
            },
        }
        record = {k: v for k, v in out.items()
                  if k not in ("metric", "value", "unit", "vs_baseline")}
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "OVERLOAD_r12.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        log(f"overload recorded: {path}")
        return out
    finally:
        ctl.stop()
        ctl.reset()
        if door is not None:
            door.stop()
        for h in handles:
            h.stop()
        shutil.rmtree(root, ignore_errors=True)


def _door_post(port: int, body: bytes, timeout: float = 60):
    import http.client as _httpc

    conn = _httpc.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/admit", body=body,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _chaos_mesh_stall() -> dict:
    """Mesh-degradation leg of the chaos bench (subprocess on a virtual
    4-device CPU mesh, like mesh_curve): a seeded `mesh.dispatch_stall`
    hang wedges the sharded sweep's collective; the watchdog abandons
    it, the breaker serves interpreter-parity verdicts, the sweep
    re-shards 4 -> 2, and the rebased width-2 sweep stays byte-parity
    with the interpreter oracle."""
    import subprocess

    code = r"""
import json, sys, time
sys.path.insert(0, ".")
from gatekeeper_tpu import faults
from gatekeeper_tpu.faults import FaultRule
from gatekeeper_tpu.parallel.mesh import DISPATCH_LOCK
from gatekeeper_tpu.util.synthetic import (
    audit_result_sig as sig, build_driver, build_oracle,
)

N_T, N_R, CAP = 8, 512, 4096
oracle = build_oracle(N_T, N_R)
oracle_r, oracle_t, _ = oracle.driver.audit_capped(CAP)
want = (sig(oracle_r), oracle_t)

client = build_driver(N_T, N_R)
drv = client.driver
drv.mesh_watchdog_s = 0.5
drv.set_mesh(True, width=4)

plane = faults.install(seed=8)
plane.add("mesh.dispatch_stall",
          FaultRule(mode="hang", hang_s=30.0, count=1))
got_r, got_t, _ = drv.audit_capped(CAP)
parity_during = (sig(got_r), got_t) == want
breaker_state = drv.breaker.state
width_after = drv.mesh_layout()
stalls = DISPATCH_LOCK.revocations

plane.release_hangs()
time.sleep(0.5)          # the abandoned dispatch finishes alone
plane.clear("mesh.dispatch_stall")
drv.mesh_watchdog_s = 120.0   # the width-2 rebase compiles in-region
probe_ok = drv.breaker.probe_now()
got_r, got_t, _ = drv.audit_capped(CAP)
parity_after = (sig(got_r), got_t) == want
stats = dict(drv.last_sweep_stats)
faults.uninstall()
print(json.dumps({
    "parity_during": parity_during, "parity_after": parity_after,
    "breaker_during": breaker_state, "probe_recovered": probe_ok,
    "width_before": 4, "width_after": width_after,
    "gate_revocations": stalls,
    "rebase_shards": stats.get("shards"),
}))
"""
    from gatekeeper_tpu.parallel.mesh import virtual_mesh_env

    env = virtual_mesh_env(4)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"chaos mesh subprocess failed: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_obs_engine() -> dict:
    """ISSUE 13 proof config -> OBS_r13.json, three sections:

      1. engine-telemetry overhead: the route ledger + compile stats
         measured on the in-process fleet-shape review stream with
         PAIRED off/on arms (alternating order, arm medians — the
         OBS_r11 profiler estimator), acceptance <3%;
      2. route explainability: a calibrated shape sweep whose
         /debug/routez tier-win table must reproduce the live
         `_route_eval` choices (the BENCH_r05 curve_route frontier,
         re-measured on this box's calibration);
      3. a SEEDED breaker trip (fault plane on tpu.dispatch) proving the
         flight-recorder dump carries trip -> tier fallback -> recovery
         in causal order.
    """
    from gatekeeper_tpu import faults
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.obs import compilestats, flightrec
    from gatekeeper_tpu.obs.debug import get_router
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    n_templates = int(os.environ.get("BENCH_OBS_TEMPLATES", "10"))
    n_stream = int(os.environ.get("BENCH_OBS_REVIEWS", "300000"))
    n_pairs = int(os.environ.get("BENCH_OBS_PAIRS", "5"))
    chunk = int(os.environ.get("BENCH_OBS_CHUNK", "256"))

    templates, constraints = make_templates(n_templates)
    c = Client(driver=TpuDriver())
    for t in templates:
        c.add_template(t)
    for cons in constraints:
        c.add_constraint(cons)
    driver = c.driver
    pods = make_pods(4096, seed=13)
    reqs = [{
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": p["metadata"]["name"],
        "namespace": p["metadata"]["namespace"],
        "operation": "CREATE",
        "object": p,
    } for p in pods]

    def batch_of(start, n):
        return [reqs[(start + j) % len(reqs)] for j in range(n)]

    # warm every chunk shape, then calibrate so stream routing runs the
    # production (measured cost model) decision path
    driver.review_batch(batch_of(0, chunk))
    tail = n_stream % chunk
    if tail:
        driver.review_batch(batch_of(0, tail))
    cal = driver.calibrate_routing()
    cal_out = {k: round(v, 3) for k, v in cal.items()} if cal else None
    log(f"obs_engine: calibration {cal_out}")

    # ---- 1. paired telemetry overhead --------------------------------------
    ledger = driver.route_ledger
    stats = compilestats.get_stats()

    def stream_round() -> float:
        t0 = time.perf_counter()
        done = 0
        while done < n_stream:
            n = min(chunk, n_stream - done)
            driver.review_batch(batch_of(done, n))
            done += n
        return round(n_stream / (time.perf_counter() - t0), 1)

    def set_telemetry(on: bool):
        ledger.enabled = on
        stats.enabled = on

    rates_off, rates_on = [], []
    try:
        for i in range(n_pairs):
            # alternate arm order: monotonic co-tenant drift must not
            # systematically tax whichever arm runs second
            order = (False, True) if i % 2 == 0 else (True, False)
            for on in order:
                set_telemetry(on)
                (rates_on if on else rates_off).append(stream_round())
    finally:
        set_telemetry(True)
    # estimator: MEDIAN OF PAIR RATIOS — this box's co-tenancy swings
    # round rates ±7%, far above the plane's real cost (one ledger
    # record per 256-review chunk).  Within a back-to-back pair the
    # drift hits both arms almost equally (order alternated), and the
    # median over pairs rejects a burst landing inside any single pair;
    # the arm medians ride along in the artifact for cross-checking
    pair_ratios = sorted(on / off for on, off in zip(rates_on, rates_off))
    ratio = pair_ratios[len(pair_ratios) // 2]
    overhead_pct = round((1.0 - ratio) * 100.0, 2)
    med_off = sorted(rates_off)[len(rates_off) // 2]
    med_on = sorted(rates_on)[len(rates_on) // 2]
    log(f"obs_engine: telemetry overhead {overhead_pct}% "
        f"(pair ratios={[round(r, 4) for r in pair_ratios]}, "
        f"median off={med_off} on={med_on}, off={rates_off}, "
        f"on={rates_on})")

    # ---- 2. /debug/routez vs the live route frontier -----------------------
    ledger.clear()
    curve_ns = [int(x) for x in os.environ.get(
        "BENCH_CURVE", "5,10,50,100,200,1000,2000").split(",")]
    live_routes = {n: driver._route_eval(n, n_reviews=1) for n in curve_ns}
    batch_routes = {
        r: driver._route_eval(n_templates * r, n_reviews=r)
        for r in (1, 8, 64, 256, 1024, 4096)
    }
    code, _ctype, body = get_router().handle("/debug/routez", "limit=64")
    assert code == 200, f"/debug/routez answered {code}"
    routez = json.loads(body)
    wins_by_shape = {
        (row["per_review_cells"], row["n_reviews"]): row["wins"]
        for row in routez["tier_wins"]
    }
    matches = all(
        max(wins_by_shape.get((n, 1), {}).items(),
            key=lambda kv: kv[1], default=(None, 0))[0] == live_routes[n]
        for n in curve_ns
    )
    device_ns = [n for n in sorted(curve_ns) if live_routes[n] == "device"]
    frontier = {
        "device_first_cells": device_ns[0] if device_ns else None,
        "host_last_cells": max(
            (n for n in sorted(curve_ns) if live_routes[n] != "device"),
            default=None,
        ),
    }
    log(f"obs_engine: routez matches live routes: {matches}; "
        f"routes={live_routes}; batch_routes={batch_routes}")

    # ---- 3. seeded breaker trip -> flight-recorder dump --------------------
    import tempfile

    rec = flightrec.get_recorder()
    rec.clear()
    dump_dir = tempfile.mkdtemp(prefix="gk-flightrec-")
    rec.configure(dump_dir=dump_dir)
    c2 = Client(driver=TpuDriver(breaker_threshold=3,
                                 breaker_cooldown_s=0.5))
    for t, k in zip(templates[:5], constraints[:5]):
        c2.add_template(t)
        c2.add_constraint(k)
    d2 = c2.driver
    d2.DEVICE_MIN_CELLS = 0  # force the device tier (instance override)
    d2.review_batch(batch_of(0, 1))  # warm: device path healthy
    plane = faults.install(seed=13)
    from gatekeeper_tpu.faults import FaultRule

    plane.add(faults.TPU_DISPATCH,
              FaultRule(mode=faults.ERROR, probability=1.0, count=3))
    try:
        for i in range(3):  # three failed dispatches trip the breaker
            d2.review_batch(batch_of(100 + i, 1))
        assert d2.breaker.state == "open", d2.breaker.state
        # diverted while open: the ledger records breaker_open and the
        # tier flip lands in the flight recorder
        d2.review_batch(batch_of(200, 1))
        # recovery: the background probe's next dispatch succeeds (the
        # fault rule is spent) and closes the breaker
        t0 = time.perf_counter()
        while d2.breaker.state != "closed":
            if time.perf_counter() - t0 > 30.0:
                raise RuntimeError(
                    f"breaker did not recover (state={d2.breaker.state})")
            time.sleep(0.05)
    finally:
        faults.uninstall()
    d2.review_batch(batch_of(300, 1))  # back on the device tier
    code, _ctype, body = get_router().handle("/debug/flightrecz", "dump=1")
    assert code == 200, f"/debug/flightrecz answered {code}"
    fpayload = json.loads(body)
    events = fpayload["events"]

    def first_seq(pred):
        return next((e["seq"] for e in events if pred(e)), None)

    trip_seq = first_seq(
        lambda e: e["type"] == "breaker_transition"
        and e.get("new") == "open"
    )
    fallback_seq = first_seq(
        lambda e: e["type"] == "route_flip"
        and e.get("reason") in ("breaker_open", "device_failed")
        and (trip_seq is None or e["seq"] > trip_seq)
    )
    recovery_seq = first_seq(
        lambda e: e["type"] == "breaker_transition"
        and e.get("new") == "closed"
        and (fallback_seq is None or e["seq"] > fallback_seq)
    )
    causal = (
        trip_seq is not None and fallback_seq is not None
        and recovery_seq is not None
        and trip_seq < fallback_seq < recovery_seq
    )
    log(f"obs_engine: flight recording trip={trip_seq} "
        f"fallback={fallback_seq} recovery={recovery_seq} "
        f"causal={causal} ({len(events)} events, "
        f"dump={fpayload.get('dumped_to')})")

    # compile provenance for the corpus (populated by every aot_jit build
    # this config triggered; xlacache counters availability rides along)
    compilez = stats.snapshot(limit=0)
    out = {
        "metric": "engine-telemetry overhead on the in-process stream "
                  f"({n_templates} constraints, chunk {chunk})",
        "value": overhead_pct,
        "unit": "%",
        "vs_baseline": 0,
        "engine_telemetry_overhead_pct": overhead_pct,
        "telemetry_pair_ratios": [round(r, 4) for r in pair_ratios],
        "telemetry_arm_median_overhead_pct": round(
            (1.0 - med_on / med_off) * 100.0, 2),
        "telemetry_rates_off": rates_off,
        "telemetry_rates_on": rates_on,
        "routing_calibration": cal_out,
        "routez_live_routes": {str(k): v for k, v in live_routes.items()},
        "routez_batch_routes": {
            str(k): v for k, v in batch_routes.items()
        },
        "routez_tier_wins": routez["tier_wins"],
        "routez_matches_live": bool(matches),
        "route_frontier": frontier,
        "compile_provenance_mix": compilez["provenance_mix"],
        "compile_epoch_lag": compilez["compile_epoch_lag"],
        "xlacache_counters_available": compilez["xlacache"][
            "counters_available"],
        "flightrec": {
            "dump_path": fpayload.get("dumped_to"),
            "event_count": len(events),
            "trip_seq": trip_seq,
            "fallback_seq": fallback_seq,
            "recovery_seq": recovery_seq,
            "causal_order_ok": causal,
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "OBS_r13.json"), "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    assert overhead_pct < 3.0, (
        f"engine telemetry overhead {overhead_pct}% >= 3%")
    assert causal, "flight recording lost the trip->fallback->recovery order"
    return out


def bench_decisions() -> dict:
    """ISSUE 15 proof config -> DECLOG_r15.json, three sections:

      1. decision-log overhead: recording (ring + queue + writer, seal
         on, the production 1% head-sampling posture) measured on the
         in-process handler-level admission stream with PAIRED off/on
         arms — many short interleaved rounds in alternating order,
         overhead from the ratio of per-arm PER-REQUEST latency
         MEDIANS.  This box shows multi-second co-tenant slowdowns of
         10-40% that dwarf the effect size; round-level throughput
         ratios are at their mercy (a slow spell poisons a whole
         round), but a slow spell only poisons the minority of
         individual requests it covers, so the median over ~10k
         per-request samples per arm stays on the deterministic cost
         (direct percentile probes put it at +1.6-2.1% across
         p10-p50) — acceptance <3%.  The stream carries
         UNIQUE-content requests (distinct objects/uids, as production
         CREATE traffic does) so the baseline reflects real per-request
         evaluation, not the request-memo fast path;
      2. always-keep proof: under 1% head sampling, EVERY served
         denial, shed, deadline expiry and fail-closed error must be
         captured (allows sample down to ~1%);
      3. differential replay: tools/replay_decisions.py reports ZERO
         drift replaying the recorded corpus against the live engine,
         while a seeded GK_BUG_COMPAT divergence IS flagged.
    """
    import shutil
    import sys as _sys
    import tempfile

    from gatekeeper_tpu import deadline as gk_deadline
    from gatekeeper_tpu.obs import decisionlog as dlog

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import replay_decisions as rp

    import gc as _gc

    n_stream = int(os.environ.get("BENCH_DECLOG_REQS", "600"))
    n_pairs = int(os.environ.get("BENCH_DECLOG_PAIRS", "20"))
    n_keep = int(os.environ.get("BENCH_DECLOG_KEEP_REQS", "3000"))

    os.environ.pop("GK_BUG_COMPAT", None)
    handler = rp._selftest_handler()
    reqs = rp.selftest_requests(n=400, divergent=8)
    # the overhead stream uses a production-shaped violation rate (~5%,
    # the synthetic default) — the always-keep/replay sections keep the
    # deny-rich corpus above
    reqs_ov = rp.selftest_requests(n=400, divergent=0,
                                   violation_rate=0.05)

    # unique-content request stream: every request a distinct object +
    # uid (production CREATE traffic), so each handle pays real
    # evaluation instead of the content-keyed request-memo fast path
    def uniq(i):
        r = reqs_ov[i % len(reqs_ov)]
        obj = json.loads(json.dumps(r["object"]))
        obj["metadata"]["labels"]["req"] = f"r{i}"
        return {**r, "uid": f"u{i}", "object": obj}

    total = n_stream * n_pairs * 2 + 500
    uniq_reqs = [uniq(i) for i in range(total)]
    cursor = [0]

    def stream_round(n, sink=None):
        start = cursor[0]
        cursor[0] += n
        clock = time.perf_counter
        if sink is None:
            for i in range(start, start + n):
                handler.handle(uniq_reqs[i])
            return
        for i in range(start, start + n):
            t0 = clock()
            handler.handle(uniq_reqs[i])
            sink.append(clock() - t0)

    log_dir = tempfile.mkdtemp(prefix="gk-declog-bench-")
    dl = dlog.get_log()
    dl.clear()
    # the production posture: sealed segments, 1% head sampling
    dl.configure(dir=log_dir, seal=True, sample_rate=0.01)
    dl.start()

    # ---- 1. paired recording overhead --------------------------------------
    stream_round(500)  # warm compiles/caches off the clock
    lat_off, lat_on = [], []
    # production admission serving runs with the cyclic GC off the hot
    # path (WebhookServer.start freezes + disables it); measuring the
    # handler stream bare would attribute gen-2 collection spikes to
    # whichever arm they land in
    _gc.collect()
    _gc.freeze()
    _gc.disable()
    try:
        for i in range(n_pairs):
            # many SHORT interleaved rounds with alternating arm order
            # spread each arm's samples across the whole wall-clock
            # window; per-request latency MEDIANS then shrug off the
            # minority of samples a co-tenant slow spell poisons
            order = (False, True) if i % 2 == 0 else (True, False)
            for on in order:
                dl.record_enabled = on
                stream_round(n_stream, lat_on if on else lat_off)
    finally:
        _gc.enable()
        _gc.unfreeze()
    dl.record_enabled = True

    def pctl(samples, q):
        s = sorted(samples)
        return s[min(len(s) - 1, int(q * len(s)))]

    med_off = pctl(lat_off, 0.50)
    med_on = pctl(lat_on, 0.50)
    overhead_pct = round((med_on / med_off - 1.0) * 100.0, 2)
    lat_stats = {
        arm: {f"p{int(q * 100)}_us": round(pctl(samples, q) * 1e6, 2)
              for q in (0.10, 0.50, 0.90)}
        for arm, samples in (("off", lat_off), ("on", lat_on))
    }
    log(f"decisions: recording overhead {overhead_pct}% "
        f"(per-request latency medians, n={len(lat_off)}/arm, "
        f"stats={lat_stats})")

    # ---- 2. always-keep under 1% head sampling -----------------------------
    # stop (final drain + rotate) BEFORE clearing the dir, or leftover
    # phase-1 records flush into the recreated dir and pollute the count
    dl.stop()
    dl.clear()
    shutil.rmtree(log_dir, ignore_errors=True)
    dl.configure(dir=log_dir, seal=True, sample_rate=0.01)
    dl.start()
    served = {"allow": 0, "deny": 0, "shed": 0, "expired": 0, "error": 0}
    for i in range(n_keep):
        resp = handler.handle(reqs[i % len(reqs)])
        served["allow" if resp.allowed else "deny"] += 1

    class _Shed:
        def review(self, obj, tracing=False):
            raise gk_deadline.OverloadShed("bench shed")

    class _Boom:
        def review(self, obj, tracing=False):
            raise RuntimeError("bench fail-closed")

    class _Expired:
        # the batcher's refusal shape: expired budgets raise
        # DeadlineExceeded before any evaluation (webhook/server.py)
        def review(self, obj, tracing=False):
            raise gk_deadline.DeadlineExceeded("bench expired")

    from gatekeeper_tpu.webhook.policy import ValidationHandler

    for n, shim, key in ((40, _Shed(), "shed"), (40, _Boom(), "error"),
                         (40, _Expired(), "expired")):
        h = ValidationHandler(shim)
        for i in range(n):
            h.handle(reqs[i % len(reqs)])
            served[key] += 1
    dl.flush()
    records, seal_problems = rp.load_records(log_dir, require_seal=True)
    recorded = {}
    for r in records:
        if r.get("kind") == dlog.KIND_ADMISSION:
            recorded[r["class"]] = recorded.get(r["class"], 0) + 1
    always_kept = all(
        recorded.get(k, 0) == served[k]
        for k in ("deny", "shed", "expired", "error")
    )
    allow_frac = recorded.get("allow", 0) / max(served["allow"], 1)
    log(f"decisions: served={served} recorded={recorded} "
        f"always_kept={always_kept} allow_keep_frac={allow_frac:.4f} "
        f"seal_problems={len(seal_problems)} "
        f"segments={len(dlog.segment_paths(log_dir))}")

    # ---- 3. differential replay: zero drift + seeded divergence ------------
    baseline = rp.replay_records(handler, records)
    os.environ["GK_BUG_COMPAT"] = "1"
    try:
        compat = rp.replay_records(rp._selftest_handler(), records)
    finally:
        os.environ.pop("GK_BUG_COMPAT", None)
    log(f"decisions: replay baseline {baseline['replayed']} replayed / "
        f"{baseline['drift_count']} drift; GK_BUG_COMPAT "
        f"{compat['drift_count']} drift")
    dl.stop()
    dl.clear()
    # dir="" detaches the archive dir: later configs must not keep
    # archiving into this bench's temp dir
    dl.configure(dir="", sample_rate=1.0, seal=False)
    shutil.rmtree(log_dir, ignore_errors=True)

    out = {
        "metric": "decision-log recording overhead on the in-process "
                  "handler stream (sealed segments, ring + queue + "
                  "writer)",
        "value": overhead_pct,
        "unit": "%",
        "vs_baseline": 0,
        "decision_log_overhead_pct": overhead_pct,
        "decision_latency_stats": lat_stats,
        "decision_latency_samples_per_arm": len(lat_off),
        "sample_rate": 0.01,
        "served": served,
        "recorded_classes": recorded,
        "always_keep_complete": bool(always_kept),
        "allow_keep_fraction": round(allow_frac, 4),
        "seal_problems": len(seal_problems),
        "replay": {
            "replayed": baseline["replayed"],
            "drift": baseline["drift_count"],
            "skipped_transient": baseline["skipped_transient"],
            "bug_compat_drift": compat["drift_count"],
            "bug_compat_example": (compat["drift"][0]
                                   if compat["drift"] else None),
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "DECLOG_r15.json"), "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    assert overhead_pct < 3.0, (
        f"decision-log overhead {overhead_pct}% >= 3%")
    assert always_kept, (
        f"always-keep incomplete: served={served} recorded={recorded}")
    assert not seal_problems, seal_problems
    assert baseline["drift_count"] == 0, baseline["drift"]
    assert compat["drift_count"] > 0, (
        "seeded GK_BUG_COMPAT divergence was not flagged")
    return out


CONFIGS = {
    "synthetic": bench_synthetic,
    "latency": bench_latency,
    "psp": bench_psp,
    "agilebank": bench_agilebank,
    "batch1m": bench_batch1m,
    "ingest": bench_ingest,
    "render": bench_render,
    "slo": bench_slo,
    "curve": bench_curve,
    "restart": bench_restart,
    "warm_resume": bench_warm_resume,
    "mesh": bench_mesh,
    "mesh_curve": bench_mesh_curve,
    "multihost": bench_multihost,
    "referential": bench_referential,
    "fleet": bench_fleet,
    "edge_obs": bench_edge_obs,
    "chaos_fleet": bench_chaos_fleet,
    "overload": bench_overload,
    "obs_engine": bench_obs_engine,
    "decisions": bench_decisions,
}

# secondary configs folded into the default run, with the extra-key name
# their headline value lands under
_FOLDED = [
    ("latency", "admission_p99_ms"),
    ("psp", "psp_audit_s"),
    ("agilebank", "agilebank_audit_s"),
    # ingest runs BEFORE the 1M-review streaming config (minimal reorder):
    # the storm's unique-content p99 is numpy-allocation-sensitive and
    # measurably degrades on the bloated post-streaming heap
    ("ingest", "ingest_p50_ms"),
    ("render", "render_violating_unique_p50_ms"),
    ("batch1m", "streamed_reviews_per_s"),
    ("curve", "curve_p50_ms"),
    ("restart", "warm_restart_ready_s"),
    ("warm_resume", "warm_resume_speedup"),
    ("mesh", "mesh_scaling_x8"),
    ("mesh_curve", "mesh_curve_parity"),
    ("referential", "referential_parity"),
    ("multihost", "multihost_sweep_s"),
    ("fleet", "fleet_reviews_per_s"),
    ("chaos_fleet", "chaos_failed_admissions"),
    ("overload", "overload_goodput_ratio_10x"),
    ("obs_engine", "engine_telemetry_overhead_pct"),
    ("decisions", "decision_log_overhead_pct"),
]


# Configs whose measured work happens in child processes that need the
# chip (bench_restart, bench_warm_resume): this process must never
# initialise a JAX backend — a parent that has touched JAX holds the chip
# and the child then fails or hangs.  Their device stamp is the child's.
_CHILD_WORK = {"restart", "warm_resume"}

# Behaviour proofs that need several engine processes at once (replica
# fleets, virtual multi-device meshes).  One chip serves one process, so
# these run on the CPU end to end — parent and children — and say so in
# their output.
_CPU_PINNED = {
    "fleet", "edge_obs", "chaos_fleet", "overload",
    "mesh", "mesh_curve", "multihost", "referential",
}


def run_config(name: str) -> dict:
    """One config in THIS process, stamped with the device that did the
    work."""
    if name in _CPU_PINNED:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if name in _CHILD_WORK:
        return CONFIGS[name]()
    from gatekeeper_tpu.ops.xlacache import enable_caches

    # persistent XLA compile cache (restart-recovery path, SURVEY §5.4):
    # cold_sweep_s reflects a warm cache when prior runs populated it —
    # the entry count below makes that auditable in the artifact's stderr
    cache_dir = enable_caches()
    try:
        n = len(os.listdir(cache_dir))
    except OSError:
        n = 0
    log(f"xla cache: {cache_dir} ({n} entries pre-run)")
    out = CONFIGS[name]()
    out.update(device_stamp())
    log(f"device: {out['platform']} {out['device_kind']} "
        f"x{out['device_count']}")
    return out


def _run_config_child(name: str):
    """`BENCH_CONFIG=<name> python bench.py` as its own process: in `all`
    mode every config gets the chip to itself.  -> its JSON, or None when
    it failed (stderr passes through)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=dict(os.environ, BENCH_CONFIG=name),
        stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if proc.returncode != 0:
        log(f"[{name}] exited rc={proc.returncode}")
        return None
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        log(f"[{name}] printed no JSON result")
        return None


def main():
    config = os.environ.get("BENCH_CONFIG", "all")
    if config != "all":
        print(json.dumps(run_config(config)))
        return

    # all: this process stays off JAX and runs each config as its own
    # child in turn
    failed = []
    out = _run_config_child("synthetic")
    if out is None:
        failed.append("synthetic")
        out = {}
    for name, key in _FOLDED:
        t0 = time.time()
        sub = _run_config_child(name)
        if sub is None:
            log(f"[{name}] FAILED after {time.time()-t0:.0f}s")
            out[key] = None
            failed.append(name)
            continue
        log(f"[{name}] done in {time.time()-t0:.0f}s")
        if name == "curve":
            out[key] = sub["curve_p50_ms"]
            out["curve_device_p50_ms"] = sub.get("curve_device_p50_ms")
            out["curve_interp_p50_ms"] = sub.get("curve_interp_p50_ms")
            out["curve_np_p50_ms"] = sub.get("curve_np_p50_ms")
            out["curve_route"] = sub.get("curve_route")
            out["curve_route_accuracy"] = sub.get("curve_route_accuracy")
            out["routing_calibration"] = sub.get("routing_calibration")
        else:
            out[key] = sub["value"]
        if name == "latency":
            out["admission_stage_p50_ms"] = sub.get("stage_p50_ms")
            out["admission_p50_ms"] = sub.get("p50_ms")
            out["admission_p99_runs_ms"] = sub.get("p99_runs_ms")
            out["admission_p99_max_ms"] = sub.get("p99_max_ms")
            out["admission_server_p99_ms"] = sub.get("server_p99_ms")
            out["admission_server_p50_ms"] = sub.get("server_p50_ms")
            out["admission_server_p99_max_ms"] = sub.get("server_p99_max_ms")
        if name == "mesh":
            out["mesh_device_scaling"] = sub.get("device_scaling_ms")
        if name == "mesh_curve":
            out["mesh_curve"] = sub.get("curve")
            out["mesh_curve_rows_per_shard_linear"] = sub.get(
                "rows_per_shard_linear")
        if name == "restart":
            out["warm_restart_template_ingest_s"] = sub.get(
                "template_ingest_s")
            out["warm_restart_data_replay_s"] = sub.get("data_replay_s")
            out["warm_restart_first_sweep_s"] = sub.get("first_sweep_s")
            out["restart_populate_ready_s"] = sub.get("populate_ready_s")
        if name == "warm_resume":
            for k in (
                "warm_resume_first_sweep_ms", "warm_resume_ready_s",
                "warm_resume_restore_s", "warm_resume_repacked_rows",
                "warm_resume_resync", "warm_resume_outcome",
                "warm_resume_violations_match", "cold_ready_s",
                "snapshot_bytes",
            ):
                out[k] = sub.get(k)
        if name == "ingest":
            out["ingest_p99_ms"] = sub.get("p99_ms")
            out["ingest_unique_p50_ms"] = sub.get("unique_p50_ms")
            out["ingest_unique_p99_ms"] = sub.get("unique_p99_ms")
            out["ingest_violating_unique_p50_ms"] = sub.get(
                "violating_unique_p50_ms")
            out["ingest_violating_unique_p99_ms"] = sub.get(
                "violating_unique_p99_ms")
            out["ingest_queue_wait_p50_ms"] = sub.get("queue_wait_p50_ms")
        if name == "render":
            for k in (
                "render_cells_per_s", "render_plan_fraction",
                "render_cells_static", "render_cells_slots",
                "render_cells_interp",
            ):
                out[k] = sub.get(k)
        if name == "fleet":
            ow = sub.get("obs_wire") or {}
            out["obs_wire_stage_share"] = ow.get("stage_share_of_p50")
            out["obs_wire_p50_ms"] = ow.get("wire_p50_ms")
            out["obs_profiler_overhead_pct"] = ow.get(
                "profiler_overhead_pct")
            out["edge_door_capacity_rps"] = sub.get(
                "edge_door_capacity_rps")
            out["edge_e2e_pipelined_rps"] = sub.get(
                "edge_e2e_pipelined_rps")
            out["edge_connect_per_request_rps"] = sub.get(
                "edge_connect_per_request_rps")
        if name == "multihost":
            out["multihost"] = {
                k: sub.get(k) for k in
                ("parity", "sweep_s", "dcn_bytes_per_sweep")
            }
        if name == "obs_engine":
            out["route_frontier"] = sub.get("route_frontier")
            out["routez_matches_live"] = sub.get("routez_matches_live")
            out["flightrec_causal_order_ok"] = (
                sub.get("flightrec") or {}
            ).get("causal_order_ok")
        if name == "decisions":
            out["decision_always_keep_complete"] = sub.get(
                "always_keep_complete")
            out["decision_replay_drift"] = (
                sub.get("replay") or {}
            ).get("drift")
            out["decision_bug_compat_drift"] = (
                sub.get("replay") or {}
            ).get("bug_compat_drift")
        out.setdefault("devices", {})[name] = {
            k: sub.get(k) for k in ("platform", "device_kind", "device_count")
        }
    out["failed_configs"] = failed
    print(json.dumps(out))
    if failed:
        # a folded null is a failure of the run, not a result
        sys.exit(1)


if __name__ == "__main__":
    main()
