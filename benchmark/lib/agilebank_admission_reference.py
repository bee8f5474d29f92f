"""The plain reference of the agilebank deployment at admission: the
four policies of demo/agilebank in plain Python on (review request,
inventory), and the comparison that decides `correct` for a webhook
answer.

It imports nothing of the program and takes nothing the program made.
The three per-object policies are lib/agilebank_reference.py's.  The
unique-selector policy is a straightforward scan: every Service of the
inventory has its flattened selector computed once, and a Service
review is held against each of them in turn; the reviewed object itself
is left out by kind, namespace and name, as upstream's `identical`
leaves it out (so an UPDATE does not collide with the copy of itself
the inventory holds), and every other Service with the same flattened
selector is one violation that names it.  Where the Rego and common
sense part ways the Rego decides: a review without a selector carries
the empty key and collides with every Service that has none.
"""

from __future__ import annotations

import json

from .agilebank_reference import (ROW_LOCAL, UNIQUE, flatten_selector,
                                  matches, selector_message)
from .reference import response_verdict


class AdmissionReference:
    def __init__(self, constraints: list, objects: list):
        self.constraints = constraints
        # (namespace, name, flattened selector) of every synced Service
        self.services = [
            (o["metadata"]["namespace"], o["metadata"]["name"],
             flatten_selector(o))
            for o in objects
            if o["kind"] == "Service" and o["metadata"].get("namespace")]

    def colliding(self, req: dict) -> list:
        """(namespace, name) of every other Service of the inventory
        that holds the reviewed Service's flattened selector."""
        kind = req.get("kind") or {}
        if (kind.get("kind"), kind.get("version"),
                kind.get("group")) != ("Service", "v1", ""):
            return []
        obj = req.get("object")
        if not isinstance(obj, dict):
            return []
        mine = flatten_selector(obj)
        me = (req.get("namespace"), req.get("name"))
        return [(ns, name) for ns, name, flat in self.services
                if flat == mine and (ns, name) != me]

    def selector_messages(self, req: dict) -> set:
        return {selector_message(other) for other in self.colliding(req)}

    def evaluate(self, req: dict) -> list:
        """[(constraint name, message)] the request raises."""
        obj = req.get("object")
        if not isinstance(obj, dict):
            return []
        out = []
        for c in self.constraints:
            if not matches(c, obj):
                continue
            if c["kind"] == UNIQUE:
                msgs = self.selector_messages(req)
            else:
                msgs = ROW_LOCAL[c["kind"]](
                    c["spec"].get("parameters") or {}, obj)
            out += [(c["metadata"]["name"], m) for m in msgs]
        return out

    def verdict(self, req: dict) -> tuple:
        """(allowed, sorted deny messages as the webhook words them)."""
        msgs = sorted(f"[denied by {name}] {m}"
                      for name, m in self.evaluate(req))
        return (not msgs, msgs)


def compare_verdict(ref: AdmissionReference, body: bytes, status: int,
                    data: bytes):
    """None when the answer to the request `body` is the reference's,
    else a short description of the difference (lib/reference.py
    compare_verdict, for requests of any kind)."""
    req = json.loads(body)["request"]
    want_allowed, want_msgs = ref.verdict(req)
    uid, allowed, code, msgs = response_verdict(status, data)
    if allowed is None:
        return f"{req['uid']}: no verdict (HTTP {status}, code {code}): {msgs}"
    if uid != req["uid"]:
        return f"{req['uid']}: answered with uid {uid!r}"
    if allowed != want_allowed or msgs != want_msgs:
        return (f"{req['uid']}: got {allowed} {msgs[:2]}, "
                f"reference {want_allowed} {want_msgs[:2]}")
    if not allowed and code != 403:
        return f"{req['uid']}: denied with code {code}"
    return None
