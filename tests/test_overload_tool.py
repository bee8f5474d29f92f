"""Tier-1 wiring for tools/check_overload.py (ISSUE 12): a 2-replica
fleet behind the overload-armed front door survives a saturation burst
with fast explicit sheds, preserved goodput, and zero verdict
divergence among accepted requests.  Skips cleanly where subprocess
spawn is unavailable (same contract as test_self_heal_tool); the
classification and verdict helpers are covered unconditionally."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import check_overload as chk  # noqa: E402

from .test_snapshot_concurrent import spawn_available


@spawn_available
def test_fleet_sheds_fast_and_keeps_verdicts_under_saturation():
    assert chk.run_checks() == []


def test_classify_taxonomy():
    ok = b'{"response": {"allowed": true}}'
    assert chk.classify(200, ok)[0] == "accepted"
    shed_door = (b'{"response": {"allowed": false, '
                 b'"status": {"message": "shed", "code": 429}}}')
    assert chk.classify(429, shed_door)[0] == "shed"
    shed_replica = (b'{"response": {"allowed": false, '
                    b'"status": {"message": "shed", "code": 429}}}')
    assert chk.classify(200, shed_replica)[0] == "shed"
    expired = (b'{"response": {"allowed": false, '
               b'"status": {"message": "late", "code": 504}}}')
    assert chk.classify(200, expired)[0] == "expired"
    assert chk.classify(502, b"no backend")[0] == "problem"
    assert chk.classify(200, b"not-json")[0] == "problem"
    # a refusal WITHOUT an explicit verdict is a contract violation
    assert chk.classify(429, b'{"response": {}}')[0] == "problem"


def test_verdict_matcher():
    deny = {"allowed": False,
            "status": {"message": "[denied by a] broken pod",
                       "code": 403}}
    assert chk._verdict_matches(deny, (False, ["broken pod"]))
    assert not chk._verdict_matches(deny, (False, ["other"]))
    assert not chk._verdict_matches(deny, (True, []))
    assert chk._verdict_matches({"allowed": True}, (True, []))
