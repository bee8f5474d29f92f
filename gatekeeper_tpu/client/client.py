"""The constraint-framework client surface.

Re-provides the capability surface of the vendored framework client
(frameworks/constraint/pkg/client/client.go): template lifecycle with
semantic-equality short-circuit, constraint CRUD with CRD-schema validation,
data replication, Review and Audit with the response schema of
regolib/src.go:13-19, Reset and Dump — over the Driver seam.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..apis.templates import ConstraintTemplate, TemplateError
from ..engine.interp import TemplatePolicy
from ..obs import trace as obstrace
from ..rego.ast import RegoError
from ..target.target import K8sValidationTarget, WipeData
from ..util import heap
from . import crd as crdlib
from .drivers import CompiledTemplate, Driver, InterpDriver, Result


class ClientError(Exception):
    pass


@dataclass
class Response:
    """Per-target response (vendored types/validation.go)."""

    target: str
    results: List[Result] = field(default_factory=list)
    trace: Optional[str] = None
    input: Optional[Any] = None


@dataclass
class Responses:
    by_target: Dict[str, Response] = field(default_factory=dict)

    def results(self) -> List[Result]:
        out: List[Result] = []
        for t in sorted(self.by_target):
            out.extend(self.by_target[t].results)
        return out

    def trace_dump(self) -> str:
        lines = []
        for t in sorted(self.by_target):
            r = self.by_target[t]
            lines.append(f"Target: {t}")
            lines.append(r.trace or "(trace disabled)")
        return "\n".join(lines)


class Client:
    """The analogue of the opa-frameworks constraint Client, bound to the
    K8s validation target and a pluggable Driver."""

    def __init__(
        self,
        driver: Optional[Driver] = None,
        target: Optional[K8sValidationTarget] = None,
    ):
        self.target = target or K8sValidationTarget()
        self.driver: Driver = driver or InterpDriver(self.target)
        self.driver.init()
        self._templates: Dict[str, ConstraintTemplate] = {}
        self._crds: Dict[str, dict] = {}
        self._semantic: Dict[str, str] = {}

    # ---- templates --------------------------------------------------------

    def create_crd(self, template: dict) -> dict:
        """Validate a template and synthesize its constraint CRD without
        installing anything (client.go:350-356) — the webhook's dry-run."""
        tmpl, _policy = self._compile_template(template)
        crd = crdlib.synthesize_crd(
            tmpl.kind, tmpl.validation_schema, self.target.match_schema()
        )
        crdlib.validate_crd(crd)
        return crd

    def add_template(self, template: dict) -> dict:
        """Compile + install a template; returns the synthesized constraint
        CRD (client.go:361-447).  Unchanged templates (semantic equality)
        short-circuit before the expensive Rego compile, as the reference
        does (client.go:361-379)."""
        try:
            parsed = ConstraintTemplate.from_dict(template)
        except TemplateError as e:
            raise ClientError(str(e))
        key = parsed.semantic_key()
        if self._semantic.get(parsed.kind) == key:
            return self._crds[parsed.kind]
        tmpl, policy = self._compile_template(template)
        crd = crdlib.synthesize_crd(
            tmpl.kind, tmpl.validation_schema, self.target.match_schema()
        )
        crdlib.validate_crd(crd)
        artifact = CompiledTemplate(kind=tmpl.kind, policy=policy, semantic_key=key)
        self.driver.put_template(tmpl.kind, artifact)
        self._templates[tmpl.kind] = tmpl
        self._crds[tmpl.kind] = crd
        self._semantic[tmpl.kind] = key
        return crd

    def remove_template(self, template: dict) -> bool:
        tmpl = ConstraintTemplate.from_dict(template)
        return self.remove_template_by_kind(tmpl.kind)

    def remove_template_by_kind(self, kind: str) -> bool:
        """Removal path for controllers that only hold a tombstone (the
        reference deletes by looking up the cached unversioned template,
        constrainttemplate_controller.go:281-301)."""
        self._templates.pop(kind, None)
        self._crds.pop(kind, None)
        self._semantic.pop(kind, None)
        return self.driver.delete_template(kind)

    def _compile_template(self, template: dict):
        try:
            tmpl = ConstraintTemplate.from_dict(template)
        except TemplateError as e:
            raise ClientError(str(e))
        spec = tmpl.targets[0]
        if spec.target and spec.target != self.target.name:
            raise ClientError(f"target {spec.target!r} not recognized")
        try:
            policy = TemplatePolicy.compile(spec.rego, spec.libs)
        except RegoError as e:
            raise ClientError(f"failed to compile template {tmpl.name}: {e}")
        return tmpl, policy

    def get_template(self, kind: str) -> Optional[ConstraintTemplate]:
        return self._templates.get(kind)

    def templates(self) -> List[str]:
        return sorted(self._templates)

    # ---- constraints ------------------------------------------------------

    def validate_constraint(self, constraint: dict):
        """Schema-validate a constraint against its template's CRD
        (client.go:662-664 -> crd_helpers.go:157-177)."""
        kind = constraint.get("kind") if isinstance(constraint, dict) else None
        crd = self._crds.get(kind or "")
        if crd is None:
            raise ClientError(f"no constraint template found for kind {kind!r}")
        try:
            crdlib.validate_constraint(constraint, crd)
        except crdlib.CRDError as e:
            raise ClientError(str(e))

    def add_constraint(self, constraint: dict):
        self.validate_constraint(constraint)
        kind = constraint["kind"]
        name = constraint["metadata"]["name"]
        self.driver.put_constraint(kind, name, constraint)

    def remove_constraint(self, constraint: dict) -> bool:
        kind = constraint.get("kind")
        name = (constraint.get("metadata") or {}).get("name")
        if not kind or not name:
            raise ClientError("constraint requires kind and metadata.name")
        return self.driver.delete_constraint(kind, name)

    def get_constraint(self, kind: str, name: str) -> Optional[dict]:
        """The engine's stored constraint object (None if absent)."""
        return self.driver.get_constraint(kind, name)

    # ---- data -------------------------------------------------------------

    def add_data(self, obj: Any):
        # `ingest` on the calling thread's audit stage clock (obs/trace.py)
        clock = obstrace.stage_clock(obstrace.PATH_AUDIT)
        own = clock.stage is None
        if own:
            clock.mark("ingest")
        try:
            handled, segments, data = self.target.process_data(obj)
            if not handled:
                raise ClientError("data not handled by target")
            if data is None:
                raise ClientError("cannot add WipeData")
            self.driver.put_data(segments, data)
        finally:
            # a thread that only ingests (a watch) never sweeps: its
            # clock reaches the counters here
            if own:
                clock.flush_due(clock.stop())

    def remove_data(self, obj: Any) -> bool:
        handled, segments, _data = self.target.process_data(obj)
        if not handled:
            raise ClientError("data not handled by target")
        return self.driver.delete_data(segments)

    def wipe_data(self) -> bool:
        return self.driver.delete_data(())

    # ---- evaluation -------------------------------------------------------

    def review(self, obj: Any, tracing: bool = False) -> Responses:
        return self.review_batch([obj], tracing=tracing)[0]

    def review_batch(self, objs: List[Any], tracing: bool = False) -> List[Responses]:
        """Batched review: one driver dispatch for N review inputs (the
        webhook micro-batching path)."""
        # the batch stage clock of this thread (obs/trace.py): the
        # batcher loop's when it called, else started and stopped here
        # (direct callers, the inline fast path)
        clock = obstrace.stage_clock(obstrace.PATH_BATCH)
        own = clock.stage is None
        if own:
            clock.mark("collect")
        try:
            reviews = []
            for obj in objs:
                handled, review = self.target.handle_review(obj)
                if not handled:
                    raise ClientError("review input not handled by target")
                reviews.append(review)
            evaled = self.driver.review_batch(reviews, tracing=tracing)
            clock.mark("render")  # resources rebuilt, Responses made
            out = []
            for review, (results, trace) in zip(reviews, evaled):
                self._rebuild_resources(results)
                out.append(
                    Responses(
                        by_target={
                            self.target.name: Response(
                                target=self.target.name,
                                results=results,
                                trace=trace,
                                input=review if tracing else None,
                            )
                        }
                    )
                )
            return out
        finally:
            if own:
                clock.flush_due(clock.stop())

    def audit(self, tracing: bool = False) -> Responses:
        with self._sweep_clock() as clock:
            results, trace = self.driver.audit(tracing=tracing)
            clock.mark("cap")
            return self._audit_responses(results, trace)

    def audit_capped(self, cap: int, tracing: bool = False):
        """Audit keeping at most `cap` violations per constraint, with
        per-constraint totals reported by the driver:
        -> (Responses, {(kind, name): (count, "exact"|"resources")}).
        On the TPU driver the host render walks the device candidate mask
        and stops at cap per constraint (the --constraint-violations-limit
        write-back never needs more)."""
        with self._sweep_clock() as clock:
            results, totals, trace = self.driver.audit_capped(
                cap, tracing=tracing)
            clock.mark("cap")
            return self._audit_responses(results, trace), totals

    @contextlib.contextmanager
    def _sweep_clock(self):
        """A sweep under the sweeping thread's audit stage clock
        (obs/trace.py StageClock, path `audit`): it opens in `pack`, the
        driver marks the rest, the caller marks `cap`.  Nested in a
        running clock it is the no-op clock: the outer call owns it."""
        clock = obstrace.stage_clock(obstrace.PATH_AUDIT)
        if clock.stage is not None:
            yield obstrace.NOOP_CLOCK
            return
        clock.mark("pack")
        try:
            yield clock
        finally:
            self._sweep_done(clock)

    def _sweep_done(self, clock) -> None:
        """Run the heap discipline at the sweep's boundary (util/heap.py:
        stage `collect`, opened only once a full collection inside a
        sweep has engaged it), stop the sweep's clock, flush it to the
        counters, and add to the driver's last_sweep_stats what only the
        clock knows: this thread's `ingest` since its previous sweep,
        the `cap` and `collect` stages, and the collector's pauses
        inside the sweep's stages (generation 2, and the younger two)."""
        if heap.engaged():
            heap.after_sweep(clock.mark("collect"))
        elif clock.gc_full_unlapsed() >= heap.ENGAGE_MIN_PAUSE_S:
            heap.engage(clock.mark("collect"))
        clock.stop()
        rows, gc_full_s = clock.lapse()
        stats = getattr(self.driver, "last_sweep_stats", None)
        if isinstance(stats, dict) and stats:
            stats["ingest_ms"] = rows.get("ingest", (0.0,))[0] * 1e3
            stats["cap_ms"] = rows.get("cap", (0.0,))[0] * 1e3
            stats["collect_ms"] = rows.get("collect", (0.0,))[0] * 1e3
            stats["gc_full_ms"] = gc_full_s * 1e3
            stats["gc_young_ms"] = max(
                0.0, sum(r[2] for r in rows.values()) - gc_full_s) * 1e3
        clock.flush()

    def _rebuild_resources(self, results):
        """handle_violation deep-copies the object out of the review
        (target.go:193-244) — ~20us per result, which at 10k results per
        sweep (or hundreds of violations per admission) dominates.
        Results reused across sweeps (driver render cache) keep their
        resource; fresh results sharing one review share one rebuild —
        the same aliasing contract as r.review itself.  Consumers treat
        resources as read-only (the audit manager extracts status
        fields)."""
        per_review: dict = {}
        for r in results:
            if r.resource is not None:
                continue
            key = id(r.review)
            res = per_review.get(key)
            if res is None:
                try:
                    res = self.target.handle_violation(r.review)
                except Exception:
                    res = None
                per_review[key] = res
            r.resource = res

    def _audit_responses(self, results, trace) -> Responses:
        self._rebuild_resources(results)
        return Responses(
            by_target={
                self.target.name: Response(
                    target=self.target.name, results=results, trace=trace
                )
            }
        )

    # ---- admin ------------------------------------------------------------

    def reset(self):
        self.driver.reset()
        self._templates.clear()
        self._crds.clear()
        self._semantic.clear()

    def dump(self) -> str:
        return self.driver.dump()
