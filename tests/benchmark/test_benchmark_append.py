"""The append guard: the tests of tests/benchmark/, run whole against
the manifest as it stands and again with what a later PR adds, made in
memory the way such a PR makes it (benchmark/README.md, "Adding things:
new files only"):

- a configuration of the audit role and a four-chip cell of it on
  `churn200`, its name appended to every `workloads` list that holds
  `churn200.synth500x100k-audit`;
- a configuration of the webhook role and a one-chip cell of it on
  `paced-unique`, appended wherever `paced-unique.synth500x100k-webhook`
  is listed;
- one per-layer metric at the end of `per_layer` that lists both.

The bodies are every test of the other test_benchmark_*.py files that
takes no argument, or only the manifest, whether it reads BENCHMARK.json
or not.  The additions are seen through `procs.read_json` (and the
manifest test's `load`), the reader the harness itself uses.  A test
that finds what it checks by name passes both ways; one that indexes
from the end, counts a list or holds a whole list equal to what it was
fails the second."""

import copy
import importlib
import inspect
import os

import pytest

from test_benchmark_rehearsal import BENCH, REPO, procs

import test_benchmark_manifest as manifest_tests  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# (the configuration copied, the traffic of the new cell, its chips)
APPENDED = [("synth500x100k-audit", "churn200", 4),
            ("synth500x100k-webhook", "paced-unique", 1)]
NEW_METRIC = "zz_append.compiles_in_window"


def bodies() -> list:
    """Every test of tests/benchmark/ that a plain call can run: one that
    takes no argument, or only the manifest, and is not parametrised.
    A test file a later PR adds is found here by its name."""
    found = []
    for fn in sorted(os.listdir(HERE)):
        name = fn[:-len(".py")]
        if (not fn.startswith("test_benchmark_") or not fn.endswith(".py")
                or fn == os.path.basename(__file__)):
            continue
        mod = importlib.import_module(name)
        for attr, f in vars(mod).items():
            if (attr.startswith("test_") and inspect.isfunction(f)
                    and f.__module__ == name
                    and not hasattr(f, "pytestmark")
                    and set(inspect.signature(f).parameters)
                    <= {"manifest"}):
                found.append((mod, attr))
    return found


BODIES = bodies()


def with_additions(m: dict, read) -> tuple:
    """`m` with the additions, and the files they bring by absolute
    path; `read` reads a file that is there."""
    m = copy.deepcopy(m)
    files, cells = {}, []
    for base, traffic, chips in APPENDED:
        name = "zz-append-" + base.rsplit("-", 1)[1]
        entry = {c["name"]: c for c in m["configs"]}[base]
        path = f"benchmark/configs/{name}.json"
        files[os.path.join(REPO, path)] = dict(
            read(os.path.join(REPO, entry["file"])), name=name)
        m["configs"].append(dict(entry, name=name, file=path))
        cell, was = f"{traffic}.{name}", f"{traffic}.{base}"
        m["workloads"].append({
            "name": cell, "config": name, "traffic": traffic,
            "chips": chips, "why": f"as {was}, appended"})
        for x in m["end_to_end"] + m["per_layer"]:
            if was in x.get("workloads", ()):
                x["workloads"].append(cell)
        cells.append(cell)
    m["per_layer"].append({
        "name": NEW_METRIC, "unit": "executables", "better": "lower",
        "source": "program_counter", "layer": "compile",
        "moves": "setup_s", "workloads": cells})
    files[os.path.join(BENCH, "metrics", NEW_METRIC + ".json")] = {
        "reader": "compiles", "args": {}, "doc": "appended"}
    return m, files


@pytest.fixture()
def appended(monkeypatch):
    """Every read of BENCHMARK.json, of the new configurations and of
    the new metric's file goes through the additions; returns the
    manifest with them."""
    read = procs.read_json
    m, files = with_additions(read(os.path.join(REPO, "BENCHMARK.json")),
                              read)

    def read_json(path):
        path = os.path.abspath(path)
        if path == os.path.join(REPO, "BENCHMARK.json"):
            return copy.deepcopy(m)
        if path in files:
            return copy.deepcopy(files[path])
        return read(path)

    monkeypatch.setattr(procs, "read_json", read_json)
    monkeypatch.setattr(manifest_tests, "load", read_json)
    return m


def test_the_additions_are_made_as_a_pr_makes_them(appended):
    m = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    assert m == appended
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells["churn200.zz-append-audit"]["chips"] == 4
    assert cells["paced-unique.zz-append-webhook"]["chips"] == 1
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["audit_sweep_s"]["workloads"][-1] == "churn200.zz-append-audit"
    assert e2e["admit_p50_ms"]["workloads"][-1] == (
        "paced-unique.zz-append-webhook")
    assert m["per_layer"][-1]["name"] == NEW_METRIC
    cfg = procs.read_json(os.path.join(
        BENCH, "configs", "zz-append-audit.json"))
    assert cfg["name"] == "zz-append-audit" and cfg["role"] == "audit"


@pytest.mark.parametrize(
    "mod, name", BODIES,
    ids=[f"{mod.__name__.removeprefix('test_benchmark_')}.{name}"
         for mod, name in BODIES])
def test_a_body_holds_with_what_a_later_pr_appends(mod, name, request):
    body = getattr(mod, name)

    def run():
        if "manifest" in inspect.signature(body).parameters:
            body(procs.read_json(os.path.join(REPO, "BENCHMARK.json")))
        else:
            body()

    run()                                   # the manifest as it stands
    request.getfixturevalue("appended")
    run()                                   # and with the additions
