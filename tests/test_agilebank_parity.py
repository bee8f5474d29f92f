"""The agilebank bundle (ISSUE 29) at a small size on the CPU: the system
(Client(driver=TpuDriver()): a first full sweep, then 12 svc-keychurn
steps through the join index's delta path) held to the benchmark's plain
reference (benchmark/lib/agilebank_reference.py) AND to the InterpDriver
oracle, on seeded data, totals and messages byte for byte; and the tie
between the benchmark's copy of the two templates it shares with
tests/render_corpus.py and that corpus."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank, agilebank_reference  # noqa: E402

from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.client.drivers import InterpDriver  # noqa: E402
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.util.synthetic import audit_result_sig  # noqa: E402

NO_CAP = 4096  # above every per-constraint count: totals exact everywhere
STEPS = 12
CONFIG = {"pods": 300, "services": 60, "namespaces": 10,
          "unlimited_share": 0.2, "production_share": 0.1,
          "prod_other_repo_share": 0.3, "unowned_share": 0.3,
          "paired_share": 0.2, "grouped_share": 0.1,
          "no_selector_share": 0.05}
TRAFFIC = {"services_per_step": 8, "pods_per_step": 12}


def _client(driver, templates, constraints, objects):
    c = Client(driver=driver)
    for t in templates:
        c.add_template(t)
    for k in constraints:
        c.add_constraint(k)
    for o in objects:
        c.add_data(o)
    return c


def _record(results, totals) -> dict:
    kept = []
    for r in results:
        meta = (r.review.get("object") or {}).get("metadata") or {}
        kept.append((r.constraint["kind"], r.constraint["metadata"]["name"],
                     meta.get("namespace", ""), meta.get("name", ""), r.msg))
    return {"totals": dict(totals), "kept": kept}


def _hold_to_oracle(got, totals, oracle, ototals, cap):
    """The system's capped answer against the oracle's whole audit."""
    want = audit_result_sig(oracle)
    if cap == NO_CAP:
        assert audit_result_sig(got) == want
        assert totals == ototals
        return
    sig = audit_result_sig(got)
    assert len(set(sig)) == len(sig), "a violation kept twice"
    assert set(sig) <= set(want), "a kept violation the oracle lacks"
    for ckey, (count, how) in totals.items():
        n_viol = ototals[ckey][0]
        kept = sum(1 for s in sig if (s[0], s[1]) == ckey)
        if how == "exact":
            assert count == n_viol, ckey
        else:
            assert how == "resources" and kept >= cap, ckey
            violating = {s[3] for s in want if (s[0], s[1]) == ckey}
            assert count >= len(violating), ckey
        assert kept >= min(cap, n_viol), ckey


@pytest.mark.parametrize("cap", [20, NO_CAP], ids=["cap20", "nocap"])
@pytest.mark.parametrize("seed", [1, 2_900_000_017, 123_456_789_012])
def test_system_matches_reference_and_oracle(seed, cap, monkeypatch):
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    templates, constraints, objects, steps = agilebank.deployment(
        CONFIG, TRAFFIC, seed, STEPS)
    system = _client(TpuDriver(), templates, constraints, objects)
    oracle = _client(InterpDriver(), templates, constraints, objects)
    ref = agilebank_reference.AuditReference(constraints, objects)

    def sweep():
        got, totals, _ = system.driver.audit_capped(cap)
        ogot, ototals, _ = oracle.driver.audit_capped(NO_CAP)
        assert ref.compare(_record(got, totals), cap) == []
        # the reference's own answer is the oracle's, message for message
        assert ref.compare(_record(ogot, ototals), 0) == []
        _hold_to_oracle(got, totals, ogot, ototals, cap)
        return dict(system.driver.last_sweep_stats)

    first = sweep()
    assert first["full"] == 1.0 and first["join_plans"] >= 1
    from gatekeeper_tpu.ops import deltasweep

    for t in list(deltasweep._BG_THREADS):
        if t.name != "gk-route-cal":
            t.join(timeout=120)
    moved = 0
    for step in steps:
        for _i, obj in step:
            system.add_data(obj)
            oracle.add_data(obj)
            ref.put(obj)
        stats = sweep()
        assert stats["join_plans"] >= 1
        if stats["full"] == 0.0:
            # the delta path: the churn and the readers of its key groups
            assert stats["delta_rows"] <= 20 + stats["join_affected_rows"]
            moved += 1
    # a vocabulary that outgrows its bucket rebases now and then; the
    # delta path carries the rest
    assert moved >= STEPS - 3


# ---- the benchmark's copy and tests/render_corpus.py must not drift ----------


def _messages(rego_template, constraint, objects):
    c = _client(InterpDriver(), [rego_template], [constraint], objects)
    got, _totals, _ = c.driver.audit_capped(NO_CAP)
    return sorted((r.review["object"]["metadata"]["name"], r.msg)
                  for r in got)


@pytest.mark.parametrize("kind, corpus_params, bench_params", [
    ("K8sAllowedRepos", {"repos": ["openpolicyagent"]},
     {"repos": ["openpolicyagent"]}),
    # the demo's template takes [{key, allowedRegex}] where the library's
    # older form (the corpus's) takes the keys alone: same default message
    ("K8sRequiredLabels", {"labels": ["owner", "team"]},
     {"labels": [{"key": "owner"}, {"key": "team"}]}),
])
def test_shared_templates_render_as_the_render_corpus_does(
        kind, corpus_params, bench_params):
    from tests import render_corpus

    source = {"K8sAllowedRepos": render_corpus._ALLOWED_REPOS,
              "K8sRequiredLabels": render_corpus._REQUIRED_LABELS}[kind]
    _t, _c, objects = agilebank.cluster(
        dict(CONFIG, unowned_share=0.5, prod_other_repo_share=0.5), 7)
    objects = [o for o in objects if o["kind"] in ("Pod", "Namespace")]
    match = {"kinds": [{"apiGroups": [""], "kinds": ["Pod", "Namespace"]}]}

    def constraint(params):
        return {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": kind, "metadata": {"name": "tie"},
                "spec": {"match": match, "parameters": params}}

    ours = _messages(agilebank.template(kind), constraint(bench_params),
                     objects)
    theirs = _messages(render_corpus._template(kind, source),
                       constraint(corpus_params), objects)
    assert ours == theirs and ours
