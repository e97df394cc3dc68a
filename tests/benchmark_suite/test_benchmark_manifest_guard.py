"""What a PR adds to ``BENCHMARK.json`` has to work with a program that
lacks what the PR adds to the program: the driver lays a PR's benchmark
files over the PARENT commit too and runs every accepted cell traced
(ISSUE 36: a PR was refused because the parent gave no result line
there).  The ways a manifest entry reaches a cell it should not, caught
here on the CPU:

(a) a ``per_layer`` entry WITHOUT a ``workloads`` list is read in every
    cell (``run.py::per_layer``: ``m.get("workloads", [name])``);
(b) an entry that names a cell whose family's ``shape`` lacks the
    ``each`` key of its ``per_events_of`` (a ``KeyError`` in
    ``readers.trace_time_of``), or whose ``KERNEL_COSTS`` lacks its
    ``cost`` (a ``ValueError`` in ``readers._kernel_cost``), or whose
    traced facts lack the ``per`` fact it divides by.

And the limits of form that refuse a manifest before any run (this PR's
first hand-in fell to a ``why`` of 203 characters).
"""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from benchmark import drivers, readers, run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}

#: the facts a driver's traced sub-window carries beside what the
#: family's ``serve_work`` counts (``drivers.run_train`` / ``run_serve``)
DRIVER_FACTS = {"train": {"window_s", "steps", "tokens", "flops"},
                "serve_closed": {"window_s", "tokens", "ttft_s", "tpot_s"}}


def _family_and_shape(cell: str):
    _, cell_file, config = run.cell_files(cell)
    family = drivers.family_of(config)
    return family, family.shape_of(config), cell_file


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_a_per_layer_metric_reaches_only_cells_that_can_read_it(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert isinstance(entry.get("workloads"), list) and entry["workloads"], \
        f"{metric}: no \"workloads\" list -- it would be read in EVERY " \
        f"cell, accepted ones on a parent program included"
    spec = run.load("benchmark", "layer_metrics", metric + ".json")
    kind, args = spec["reader"]["kind"], spec["reader"].get("args", {})
    assert kind in readers.READERS, (metric, kind)
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == entry["moves"])
    for cell in entry["workloads"]:
        assert cell in CELLS, (metric, cell)
        assert cell in moved.get("workloads", [cell]), \
            f"{metric} moves {entry['moves']}, which {cell} does not report"
        family, shape, cell_file = _family_and_shape(cell)
        if "per_events_of" in args:
            assert args["per_events_of"]["each"] in shape, \
                f"{metric} in {cell}: the family's shape has no " \
                f"{args['per_events_of']['each']!r} (KeyError in the parent)"
        if "cost" in args:
            assert args["cost"] in family.KERNEL_COSTS, \
                f"{metric} in {cell}: {family.__name__} has no cost " \
                f"{args['cost']!r} (ValueError in the parent)"
        if isinstance(args.get("per"), str):
            facts = set(DRIVER_FACTS[cell_file["driver"]])
            if cell_file["driver"] == "serve_closed":
                facts |= set(family.serve_work(shape, []))
            assert args["per"] in facts, (metric, cell, args["per"])
        if kind == "fact_percentile":
            assert args["of"] in DRIVER_FACTS[cell_file["driver"]]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_reports_setup_another_end_to_end_metric_and_a_layer(cell):
    reports = lambda group: [m["name"] for m in MANIFEST[group]
                             if cell in m.get("workloads", [cell])]
    e2e = reports("end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2, e2e
    assert reports("per_layer")
    assert CELLS[cell]["config"] in CONFIGS
    for name in reports("per_layer"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")), name


def test_no_family_is_imported_by_another_cell_s_modules():
    """(c): what every cell loads -- ``run``, ``drivers``, ``readers``,
    ``trace_reduce``, ``correct`` -- imports no family at import time, so
    a family a PR adds cannot break a cell that does not name it."""
    import importlib
    import re
    for name in ("run", "drivers", "readers", "trace_reduce", "correct",
                 "costs"):
        module = importlib.import_module("benchmark." + name)
        with open(module.__file__) as f:
            source = f.read()
        assert not re.search(r"^\s*(from|import)\s+benchmark\.families",
                             source, re.M), name


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def _one_line(text) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and text.isprintable()


def _entries():
    return [(group, e) for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in MANIFEST[group]]


@pytest.mark.parametrize("group,entry", _entries(),
                         ids=[f"{g}:{e['name']}" for g, e in _entries()])
def test_an_entry_keeps_the_limits_of_form(group, entry):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[group]
    assert set(entry) - {"workloads"} == keys, sorted(entry)
    assert _NAME.fullmatch(entry["name"]), entry["name"]
    for key in ("why", "layer") + (("source",) * (group == "configs")):
        if key in entry:
            assert _one_line(entry[key]), (key, len(entry[key]))
    for key in ("config", "traffic", *entry.get("reduced", [])):
        assert _NAME.fullmatch(entry.get(key, key)), key
    assert len(entry.get("reduced", [])) <= 16
    if "unit" in entry:
        assert _UNIT.fullmatch(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    if "file" in entry:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", entry["file"])
        assert entry["file"].startswith(tuple(MANIFEST["paths"]))
    assert entry.get("chips", 1) in (1, 4)


def test_the_manifest_as_a_whole_keeps_its_limits():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    for group in ("configs", "workloads"):
        assert 1 <= len(MANIFEST[group]) <= 24
    assert 1 <= len(MANIFEST["per_layer"]) <= 128
    names = [e["name"] for g in ("end_to_end", "per_layer")
             for e in MANIFEST[g]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    n = len(MANIFEST["workloads"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, n // 4)
    assert (2 + 14 * n) * (MANIFEST["run_seconds"] + 60) + 180 * n + 1200 \
        <= 43200
