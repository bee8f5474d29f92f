"""Auxiliary subsystems: syncutil, upgrade manager, cert rotation."""

import ssl
import threading
import time
import urllib.request

import pytest

# the battery exercises cert rotation end to end; without `cryptography`
# (gated import, see main.py) the module cannot even import — skip
# cleanly instead of erroring at collection
pytest.importorskip("cryptography")

from gatekeeper_tpu.certs import CertRotator
from gatekeeper_tpu.certs.rotator import SECRET_GVK, VWC_GVK, cert_expiry
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.syncutil import SingleRunner, SyncBool, retry_with_backoff
from gatekeeper_tpu.upgrade import UpgradeManager


class TestSyncUtil:
    def test_syncbool(self):
        b = SyncBool()
        assert not b.get()
        b.set(True)
        assert b.get()

    def test_single_runner_keys_are_single_use(self):
        runner = SingleRunner()
        ran = []

        def work(stop):
            ran.append(1)
            stop.wait(timeout=5)

        assert runner.schedule("k", work)
        assert not runner.schedule("k", work)  # silently ignored
        runner.cancel("k")
        runner.wait(timeout=2)
        assert ran == [1]

    def test_single_runner_cancel_unblocks(self):
        runner = SingleRunner()
        finished = threading.Event()

        def work(stop):
            stop.wait(timeout=30)
            finished.set()

        runner.schedule("x", work)
        t0 = time.monotonic()
        runner.cancel("x")
        assert finished.wait(timeout=2)
        assert time.monotonic() - t0 < 2

    def test_retry_with_backoff(self):
        attempts = []

        def fn():
            attempts.append(1)
            return len(attempts) >= 3

        assert retry_with_backoff(fn, initial=0.001)
        assert len(attempts) == 3
        attempts.clear()
        assert not retry_with_backoff(lambda: False, initial=0.001, steps=3)


class TestUpgradeManager:
    def test_migrates_v1alpha1(self):
        kube = InMemoryKube()
        kube.create({
            "apiVersion": "constraints.gatekeeper.sh/v1alpha1",
            "kind": "K8sRequiredLabels",
            "metadata": {"name": "old-one"},
            "spec": {"parameters": {"labels": ["a"]}},
        })
        kube.create({
            "apiVersion": "templates.gatekeeper.sh/v1alpha1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": "old-template"},
            "spec": {},
        })
        n = UpgradeManager(kube).upgrade()
        assert n == 2
        old = kube.list(("constraints.gatekeeper.sh", "v1alpha1",
                         "K8sRequiredLabels"))
        assert old == []
        new = kube.get(("constraints.gatekeeper.sh", "v1beta1",
                        "K8sRequiredLabels"), "old-one")
        assert new["spec"]["parameters"] == {"labels": ["a"]}
        assert new["apiVersion"] == "constraints.gatekeeper.sh/v1beta1"

    def test_existing_new_version_wins(self):
        kube = InMemoryKube()
        kube.create({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K",
            "metadata": {"name": "x"},
            "spec": {"new": True},
        })
        kube.create({
            "apiVersion": "constraints.gatekeeper.sh/v1alpha1",
            "kind": "K",
            "metadata": {"name": "x"},
            "spec": {"old": True},
        })
        UpgradeManager(kube).upgrade()
        kept = kube.get(("constraints.gatekeeper.sh", "v1beta1", "K"), "x")
        assert kept["spec"] == {"new": True}


class TestCertRotator:
    def test_generates_secret_and_injects_bundle(self):
        kube = InMemoryKube()
        kube.create({
            "apiVersion": "admissionregistration.k8s.io/v1",
            "kind": "ValidatingWebhookConfiguration",
            "metadata":
                {"name": "gatekeeper-validating-webhook-configuration"},
            "webhooks": [
                {"name": "validation.gatekeeper.sh", "clientConfig": {}},
                {"name": "check-ignore-label.gatekeeper.sh",
                 "clientConfig": {}},
            ],
        })
        rot = CertRotator(kube)
        assert not rot.is_ready.is_set()
        rot.ensure_certs()
        assert rot.is_ready.is_set()
        secret = kube.get(SECRET_GVK, rot.secret_name, rot.namespace)
        data = secret["stringData"]
        assert data["tls.crt"].startswith("-----BEGIN CERTIFICATE")
        vwc = kube.get(VWC_GVK, "gatekeeper-validating-webhook-configuration")
        assert all(w["clientConfig"]["caBundle"] for w in vwc["webhooks"])

    def test_valid_secret_not_regenerated(self):
        kube = InMemoryKube()
        rot = CertRotator(kube)
        s1 = rot.ensure_certs()
        s2 = rot.ensure_certs()
        assert s1["stringData"]["tls.crt"] == s2["stringData"]["tls.crt"]

    def test_expiring_cert_refreshed(self):
        kube = InMemoryKube()
        rot = CertRotator(kube)
        secret = rot.ensure_certs()
        # corrupt the cert: forces regeneration
        secret["stringData"]["tls.crt"] = "garbage"
        kube.update(secret)
        s2 = rot.ensure_certs()
        assert s2["stringData"]["tls.crt"].startswith("-----BEGIN CERTIFICATE")
        assert cert_expiry(s2["stringData"]["tls.crt"].encode())

    def test_tls_webhook_server(self, tmp_path):
        """End-to-end: rotator-issued certs serve real TLS."""
        from gatekeeper_tpu.client.client import Client
        from gatekeeper_tpu.webhook import ValidationHandler, WebhookServer

        kube = InMemoryKube()
        rot = CertRotator(kube)
        certfile, keyfile = rot.write_cert_files(str(tmp_path))
        handler = ValidationHandler(Client(), kube=kube)
        srv = WebhookServer(handler, port=0, certfile=certfile, keyfile=keyfile)
        srv.start()
        try:
            ctx = ssl.create_default_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            with urllib.request.urlopen(
                f"https://127.0.0.1:{srv.port}/healthz", context=ctx, timeout=5
            ) as r:
                assert r.status == 200
        finally:
            srv.stop()

    def test_refresh_reuses_valid_ca(self):
        import datetime
        from gatekeeper_tpu.certs import rotator as rot_mod

        kube = InMemoryKube()
        rot = CertRotator(kube)
        s1 = rot.ensure_certs()
        ca1 = s1["stringData"]["ca.crt"]
        # hook installed after bootstrap, as App wires it
        refreshed = []
        rot.on_refresh = lambda s: refreshed.append(s)
        # expire only the serving cert by shrinking its validity window
        old_validity = rot_mod.CERT_VALIDITY
        try:
            # re-issue a serving cert that is inside the refresh margin
            rot_mod.CERT_VALIDITY = datetime.timedelta(days=1)
            tls_crt, tls_key = rot_mod.generate_server_cert(
                ca1.encode(), s1["stringData"]["ca.key"].encode(),
                rot.dns_names,
            )
            s1["stringData"]["tls.crt"] = tls_crt.decode()
            s1["stringData"]["tls.key"] = tls_key.decode()
            kube.update(s1)
        finally:
            rot_mod.CERT_VALIDITY = old_validity
        s2 = rot.ensure_certs()
        # serving cert re-signed, CA unchanged (caBundle stability)
        assert s2["stringData"]["ca.crt"] == ca1
        assert s2["stringData"]["tls.crt"] != s1["stringData"]["tls.crt"]
        assert len(refreshed) == 1

    def test_key_file_permissions(self, tmp_path):
        import os

        kube = InMemoryKube()
        rot = CertRotator(kube)
        certfile, keyfile = rot.write_cert_files(str(tmp_path / "certs"))
        assert oct(os.stat(keyfile).st_mode & 0o777) == "0o600"
        assert oct(os.stat(os.path.dirname(keyfile)).st_mode & 0o777) == "0o700"


class TestSmallPieces:
    def test_version(self):
        from gatekeeper_tpu import version

        assert version.VERSION
        assert "gatekeeper-tpu/" in version.user_agent()

    def test_retry_kube_retries_conflict(self):
        from gatekeeper_tpu.kube.clients import RetryKube

        kube = InMemoryKube()
        kube.create({"apiVersion": "v1", "kind": "ConfigMap",
                     "metadata": {"name": "x"}})
        rk = RetryKube(kube, backoff_s=0.001)
        stale = rk.get(("", "v1", "ConfigMap"), "x")
        kube.update({"apiVersion": "v1", "kind": "ConfigMap",
                     "metadata": {"name": "x"}, "data": {"a": "1"}})
        import pytest as _pytest

        stale["data"] = {"b": "2"}
        with _pytest.raises(Exception):
            rk.update(stale, check_version=True)  # stays conflicted
        # non-versioned update goes through
        rk.update(stale)
        assert kube.get(("", "v1", "ConfigMap"), "x")["data"] == {"b": "2"}

    def test_noop_kube(self):
        from gatekeeper_tpu.kube.clients import NoopKube
        from gatekeeper_tpu.kube.inmem import NotFound

        nk = NoopKube()
        assert nk.list(("", "v1", "Pod")) == []
        assert nk.create({"x": 1}) == {"x": 1}
        import pytest as _pytest

        with _pytest.raises(NotFound):
            nk.get(("", "v1", "Pod"), "a")

    def test_profile_server(self):
        from gatekeeper_tpu.main import ProfileServer

        ps = ProfileServer(port=0)
        ps.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ps.port}/debug/pprof", timeout=5
            ) as r:
                body = r.read().decode()
            assert "thread MainThread" in body
        finally:
            ps.stop()


class TestJaxProfileServer:
    def test_flag_starts_profiler_server(self):
        """--jax-profile-port starts the jax.profiler server (the TPU
        analogue of --enable-pprof; TensorBoard attaches on demand)."""
        import socket

        from gatekeeper_tpu.main import App, build_parser
        from gatekeeper_tpu.kube.inmem import InMemoryKube

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        app = App(
            build_parser().parse_args(
                ["--jax-profile-port", str(port), "--disable-cert-rotation"]
            ),
            kube=InMemoryKube(),
        )
        try:
            app.start()
            # the profiler server listens (gRPC); a TCP connect suffices
            probe = socket.create_connection(("127.0.0.1", port), timeout=5)
            probe.close()
        finally:
            app.stop()


class TestFlagParityAdditions:
    """main.go:84-87 + controller.go:40 flags added for full surface parity."""

    def test_log_level_encoders(self):
        from gatekeeper_tpu.logging import LEVEL_ENCODERS
        assert LEVEL_ENCODERS["lower"]("INFO") == "info"
        assert LEVEL_ENCODERS["capital"]("info") == "INFO"
        assert "\x1b[" in LEVEL_ENCODERS["color"]("ERROR")
        assert "ERROR" in LEVEL_ENCODERS["capitalcolor"]("error").upper()

    def test_parser_accepts_new_flags(self):
        from gatekeeper_tpu.main import build_parser
        args = build_parser().parse_args([
            "--log-level-key", "severity", "--log-level-encoder", "capital",
            "--metrics-addr", ":0", "--debug-use-fake-pod",
        ])
        assert args.log_level_key == "severity"
        assert args.debug_use_fake_pod is True

    def test_debug_use_fake_pod_disables_ownership(self, monkeypatch):
        import os
        from gatekeeper_tpu.apis import status as status_api
        from gatekeeper_tpu.main import App
        monkeypatch.setattr(status_api, "_POD_OWNERSHIP", True)
        # App writes POD_NAME directly; register restoration so later tests
        # don't inherit the fake pod identity
        monkeypatch.setitem(os.environ, "POD_NAME", os.environ.get("POD_NAME", ""))
        app = App(["--debug-use-fake-pod", "--api-server", "inmem",
                   "--driver", "interp"])
        assert os.environ.get("POD_NAME") == "no-pod"
        assert status_api.pod_ownership_enabled() is False

    def test_status_crs_owner_reference_the_pod(self, monkeypatch):
        from gatekeeper_tpu.apis import status as status_api
        monkeypatch.setattr(status_api, "_POD_OWNERSHIP", True)
        pod = {"metadata": {"name": "gk-pod-1", "uid": "u-123"}}
        st = status_api.new_constraint_status_for_pod(
            "gk-pod-1", "gatekeeper-system",
            {"kind": "K8sFoo", "metadata": {"name": "c1"}}, ["audit"],
            owner_pod=pod,
        )
        refs = st["metadata"]["ownerReferences"]
        assert refs == [{"apiVersion": "v1", "kind": "Pod",
                         "name": "gk-pod-1", "uid": "u-123"}]
        # ownership disabled -> no owner refs (DisablePodOwnership analogue)
        monkeypatch.setattr(status_api, "_POD_OWNERSHIP", False)
        st2 = status_api.new_template_status_for_pod(
            "gk-pod-1", "gatekeeper-system",
            {"metadata": {"name": "t1"}}, ["audit"], owner_pod=pod,
        )
        assert "ownerReferences" not in st2["metadata"]

    def test_metrics_addr_rejects_malformed(self):
        from gatekeeper_tpu.main import App
        import pytest as _pytest
        for bad in ("localhost", "127.0.0.1:", ":", "localhost:http"):
            with _pytest.raises(SystemExit):
                app = App(["--api-server", "inmem", "--driver", "interp",
                           "--metrics-addr", bad, "--prometheus-port", "0",
                           "--port", "0", "--health-addr", ":0",
                           "--disable-cert-rotation"])
                app.start()
                app.stop()

    def test_stop_safe_after_failed_start(self):
        # a start() that dies before metrics-addr binding must still allow
        # cleanup via stop() without AttributeError
        from gatekeeper_tpu.main import App
        app = App(["--api-server", "inmem", "--driver", "interp"])
        app.stop()  # never started: every component is None

    def test_logging_resetup_applies_new_format(self):
        import io, json, logging
        from gatekeeper_tpu import logging as gklog
        root = logging.getLogger("gatekeeper")
        saved = root.handlers[:]
        try:
            root.handlers = []
            buf = io.StringIO()
            gklog.setup("INFO", stream=buf)
            gklog.setup("INFO", level_key="severity", level_encoder="capital")
            gklog.get("t").info("x")
            line = json.loads(buf.getvalue())
            assert line["severity"] == "INFO"
        finally:
            root.handlers = saved


class TestXlaCache:
    def test_enable_idempotent_and_functional(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from gatekeeper_tpu.ops import xlacache

        d = str(tmp_path / "cache")
        prior = jax.config.jax_compilation_cache_dir
        try:
            assert xlacache.enable(d) is True
            assert xlacache.enable(d) is True  # idempotent
            f = jax.jit(lambda x: (x * 2).sum())
            assert float(f(jnp.ones(64))) == 128.0
            import os
            assert os.path.isdir(d) and len(os.listdir(d)) >= 1
        finally:
            # undo the global config so later compiles don't write into a
            # pruned pytest tmp dir
            jax.config.update("jax_compilation_cache_dir", prior)
            xlacache._enabled_dir = None

    def test_flag_wires_cache(self, tmp_path, monkeypatch):
        from gatekeeper_tpu.main import build_parser
        from gatekeeper_tpu.ops import xlacache

        monkeypatch.delenv(xlacache.ENV_VAR, raising=False)
        args = build_parser().parse_args(["--xla-cache-dir", str(tmp_path)])
        assert args.xla_cache_dir == str(tmp_path)
        assert xlacache.resolve_cache_dir(args.xla_cache_dir) == str(tmp_path)
        # an explicit empty flag still means "no cache"
        assert xlacache.resolve_cache_dir("") == ""

    def test_variable_wins_and_is_left_untouched(self, tmp_path,
                                                 monkeypatch):
        """$JAX_COMPILATION_CACHE_DIR set: that directory is the cache —
        over the flag too — and the code never points jax at one (jax
        read the variable itself)."""
        import jax
        from gatekeeper_tpu.main import build_parser
        from gatekeeper_tpu.ops import xlacache

        env_dir = str(tmp_path / "from-env")
        monkeypatch.setenv(xlacache.ENV_VAR, env_dir)
        assert xlacache.resolve_cache_dir() == env_dir
        assert xlacache.resolve_cache_dir(str(tmp_path / "flag")) == env_dir
        assert build_parser().parse_args([]).xla_cache_dir == env_dir
        prior = jax.config.jax_compilation_cache_dir
        knobs = (jax.config.jax_persistent_cache_min_entry_size_bytes,
                 jax.config.jax_persistent_cache_min_compile_time_secs)
        try:
            assert xlacache.enable(env_dir) is True
            assert jax.config.jax_compilation_cache_dir == prior
        finally:
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", knobs[0])
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", knobs[1])
            xlacache._enabled_dir = None

    def test_unset_resolves_to_the_checkout(self):
        """Unset: <checkout>/.xla-cache — asked of a fresh process (this
        session's conftest blanks the default for test isolation), from a
        cwd that is NOT the checkout."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = repo
        code = (
            "import sys\n"
            "from gatekeeper_tpu.ops.xlacache import resolve_cache_dir\n"
            "from gatekeeper_tpu.main import build_parser\n"
            "print(resolve_cache_dir())\n"
            "print(build_parser().parse_args([]).xla_cache_dir)\n"
            "assert 'jax' not in sys.modules\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd="/",
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        want = os.path.join(repo, ".xla-cache")
        assert out.stdout.split() == [want, want]
