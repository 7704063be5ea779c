"""BENCHMARK.json held entry by entry, not by the length of its lists:
what was accepted is still there, in the order it was accepted, as a
prefix of every list, and every entry has the files it names. A later
PR appends a configuration, a cell, a metric or a cell's name to a
``workloads`` list and turns nothing here red (tiny_tree.py does all
four, and the same rules run on its tree); an accepted entry gone, or
one put before the accepted ones, still fails.

A `benchmark` PR that takes an entry away edits ACCEPTED with it."""

import copy
import importlib
import json
import os
import re

import pytest

import tiny_tree
from benchmarks import harness

REPO = tiny_tree.REPO
ONE_CHIP = ["fm16-train-zipf", "ffm4-train-zipf"]
TRAIN = ONE_CHIP + ["fm16x4-train-zipf"]
BAGS = ["fm8-train-bags", "fm3-train-bags"]             # PR 35, PR 41
EVAL = ["fm16-train-eval"]                              # PR 44
FOUR = TRAIN + BAGS[:1]         # PR 37's host-loop metrics (PERF.md 7(e))
SIX = TRAIN + BAGS + EVAL
# name -> the cells its ``workloads`` list was accepted with (None: the
# entry has no such key and every cell reports it), in list order.
ACCEPTED = {
    "configs": dict.fromkeys([
        "fm-k16-criteo1tb", "ffm-k4-avazu", "fm-k16-criteo1tb-x4",
        "fm-k8-kdd12-bags", "fm3-k8-kdd12-bags", "fm-k16-criteo1tb-eval"]),
    "workloads": dict.fromkeys(SIX),
    "end_to_end": {"train_examples_per_s_per_chip": SIX, "setup_s": None},
    "per_layer": {
        "setup_start_s": None, "setup_compile_s": None,
        "input_wait_share": SIX, "h2d_bytes_per_example": SIX,
        "step_device_ms": SIX, "step_roofline": SIX,
        "steady_rate.train": SIX,
        # PR 25's nine, six scopes of the step and three counters
        "dedup_sort_ms": ONE_CHIP + EVAL, "table_gather_ms": SIX,
        "slot_expand_ms": SIX, "interaction_ms": SIX,
        "table_scatter_ms": SIX, "step_unscoped_ms": FOUR + EVAL,
        "loss_sync_share": SIX, "epoch_barrier_s": SIX,
        "compiles_per_epoch": SIX,
        "uniq_slot_fill": SIX, "host_build_s_per_batch": SIX,       # PR 26
        "collective_exposed_ms": TRAIN[2:],                         # PR 27
        "shard_slot_fill": TRAIN[2:], "cell_fill": SIX,         # PR 33, 34
        # PR 35's three of lines of unequal length
        "cells_per_example": BAGS, "program_switches_per_step": BAGS,
        "truncated_cells_per_example": BAGS,
        # PR 37's ten of the host loop
        "bookkeeping_s_per_step": FOUR, "loop_unnamed_share": FOUR,
        "barrier_flush_s": FOUR, "pipeline_open_s": FOUR,
        "first_batch_s": FOUR, "idle_unnamed": FOUR,
        "idle_in_bookkeeping": FOUR, "idle_in_barrier_flush": FOUR,
        "idle_in_pipeline_open": FOUR, "idle_in_first_batch": FOUR,
        "anova_scan_ms": BAGS[1:], "anova_scan_roofline": BAGS[1:],  # PR 41
        # PR 44's nine of the validation sweep, PR 45's tenth
        "validation_share": EVAL, "validation_s_per_sweep": EVAL,
        "validation_examples_per_sweep": EVAL,
        "validation_score_device_ms": EVAL, "validation_gather_ms": EVAL,
        "idle_in_validation_first_batch": EVAL,
        "idle_in_validation_drain": EVAL,
        "idle_in_validation_dispatch": EVAL,
        "validation_score_roofline": EVAL,
        "validation_uniq_slot_fill": EVAL,
        # PR 46's four of the feed
        "loop_h2d_s_per_step": SIX, "place_s_per_step": SIX,
        "placed_ahead_share": SIX, "emit_s_per_batch": SIX},
}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ENTRY_KEYS = ("name", "unit", "better", "source", "layer", "moves")


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


REPO_SPEC = _json(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_tree.make(str(tmp_path_factory.mktemp("tree")))


@pytest.fixture(params=["repo", "tree with entries appended"])
def spec_and_root(request, tiny_root):
    root = REPO if request.param == "repo" else tiny_root
    return _json(root, "BENCHMARK.json"), root


# ---- the rules, as functions of a spec ---------------------------------

def hold_prefix(spec, kind):
    """The accepted entries lead the list, in their order, and each
    one's ``workloads`` list is led by the cells it was accepted with."""
    accepted = ACCEPTED[kind]
    got = spec[kind][:len(accepted)]
    assert [e["name"] for e in got] == list(accepted)
    for e in got:
        cells = accepted[e["name"]]
        if cells is None:
            assert "workloads" not in e, e["name"]
        else:
            assert e["workloads"][:len(cells)] == cells, e["name"]


def hold_shape(spec):
    assert set(spec) == KEYS
    names = [[e["name"] for e in spec[k]] for k in ACCEPTED]
    assert all(len(n) == len(set(n)) and all(map(NAME.match, n))
               for n in names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    cells = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert configs == {w["config"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        listed = m.get("workloads", [])
        assert set(listed) <= cells and len(listed) == len(set(listed))
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    # at most a quarter of the cells, rounded down and at least one,
    # ask for four chips
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4), four


def hold_cell(root, name):
    """A cell finds its configuration and traffic by name, reports
    setup_s, another end-to-end metric and a per-layer one, and its
    configuration names a reference family that is there."""
    cell = harness.load_cell(name, root)
    assert os.path.exists(os.path.join(root, "benchmarks", "drivers",
                                       cell.kind + ".py"))
    assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
    assert cell.per_layer
    assert cell.kind in cell.config["check_limits"]
    assert os.path.exists(os.path.join(
        root, "benchmarks", "references",
        cell.config["reference_family"] + ".py"))


def hold_metric(spec, root, name):
    """A per-layer entry says what its layer_metrics/<name>.json says,
    its reader is there, and its cells report the metric it moves."""
    m = next(e for e in spec["per_layer"] if e["name"] == name)
    own = _json(root, "benchmarks", "layer_metrics", name + ".json")
    assert {k: m[k] for k in ENTRY_KEYS} == {k: own[k] for k in ENTRY_KEYS}
    assert set(m) <= set(ENTRY_KEYS) | {"workloads"}
    assert os.path.exists(os.path.join(root, "benchmarks", "readers",
                                       own["reader"] + ".py"))
    target = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
    cells = [w["name"] for w in spec["workloads"]]
    assert set(m.get("workloads", cells)) <= set(
        target.get("workloads", cells))


# ---- on the repo's file and on the tree that appends -------------------

@pytest.mark.parametrize("kind", list(ACCEPTED))
def test_accepted_entries_lead_their_list_in_order(spec_and_root, kind):
    hold_prefix(spec_and_root[0], kind)


def test_the_file_has_its_shape_and_the_four_chip_share(spec_and_root):
    hold_shape(spec_and_root[0])


def test_the_tree_appends_all_four_kinds_of_entry(tiny_root):
    """What the parametrised tests above run on: the tree's spec holds
    a configuration, a cell and a per-layer metric more than the
    repo's, and every accepted ``workloads`` list a name more."""
    spec = _json(tiny_root, "BENCHMARK.json")
    for kind in ACCEPTED:
        assert len(spec[kind]) > len(REPO_SPEC[kind]), kind
    for kind in ("end_to_end", "per_layer"):
        for mine, theirs in zip(REPO_SPEC[kind], spec[kind]):
            if "workloads" in mine:
                assert len(theirs["workloads"]) > len(mine["workloads"])


@pytest.mark.parametrize("name", [w["name"] for w in REPO_SPEC["workloads"]])
def test_every_cell_finds_its_files(name):
    hold_cell(REPO, name)


@pytest.mark.parametrize("name", [m["name"] for m in REPO_SPEC["per_layer"]])
def test_every_per_layer_entry_matches_its_file(name):
    hold_metric(REPO_SPEC, REPO, name)
    own = _json(REPO, "benchmarks", "layer_metrics", name + ".json")
    reader = importlib.import_module("benchmarks.readers." + own["reader"])
    assert callable(reader.read)


def test_every_appended_entry_finds_its_files_too(tiny_root):
    spec = _json(tiny_root, "BENCHMARK.json")
    for w in spec["workloads"][len(ACCEPTED["workloads"]):]:
        hold_cell(tiny_root, w["name"])
    for m in spec["per_layer"][len(ACCEPTED["per_layer"]):]:
        hold_metric(spec, tiny_root, m["name"])


# ---- what the rules still catch -----------------------------------------

def _cell_gone(s):
    gone = s["workloads"].pop(1)["name"]
    for m in s["end_to_end"] + s["per_layer"]:
        if gone in m.get("workloads", ()):
            m["workloads"].remove(gone)


def _new_cell(s, chips=1, name="later-cell"):
    s["workloads"].append(dict(s["workloads"][0], name=name, chips=chips))


MUTATIONS = {
    "an accepted cell gone": (_cell_gone, "workloads"),
    "a cell put before the accepted ones": (
        lambda s: s["workloads"].insert(0, s["workloads"].pop()),
        "workloads"),
    "an accepted configuration gone": (
        lambda s: s["configs"].pop(0), "configs"),
    "a metric put before the accepted ones": (
        lambda s: s["per_layer"].insert(3, s["per_layer"].pop()),
        "per_layer"),
    "an accepted metric gone": (
        lambda s: s["per_layer"].pop(10), "per_layer"),
    "an end-to-end metric put first": (
        lambda s: s["end_to_end"].insert(0, dict(
            s["end_to_end"][0], name="later_rate")), "end_to_end"),
    "a cell's name put first in a workloads list": (
        lambda s: s["per_layer"][4]["workloads"].insert(0, "later-cell"),
        "per_layer"),
    "an accepted cell's name gone from a workloads list": (
        lambda s: s["end_to_end"][0]["workloads"].pop(0), "end_to_end"),
    "a workloads key on a metric accepted without one": (
        lambda s: s["per_layer"][0].update(workloads=list(TRAIN)),
        "per_layer"),
    "a second and a third four-chip cell among eight": (
        lambda s: [_new_cell(s, 4, n) for n in ("later-a", "later-b")],
        None),
    "a cell a metric lists and BENCHMARK.json has not": (
        lambda s: s["per_layer"][4]["workloads"].append("no-such-cell"),
        None),
    "a configuration no cell uses": (
        lambda s: s["configs"].append(dict(s["configs"][0], name="idle")),
        None),
}


@pytest.mark.parametrize("what", list(MUTATIONS))
def test_the_rules_still_fail(what):
    mutate, kind = MUTATIONS[what]
    spec = copy.deepcopy(REPO_SPEC)
    mutate(spec)
    with pytest.raises(AssertionError):
        hold_prefix(spec, kind) if kind else hold_shape(spec)
    # and the same spec with a cell appended in the permitted way passes
    sound = copy.deepcopy(REPO_SPEC)
    _new_cell(sound)
    for m in sound["end_to_end"] + sound["per_layer"]:
        if m.get("workloads", [None])[0] == TRAIN[0]:
            m["workloads"].append("later-cell")
    for k in ACCEPTED:
        hold_prefix(sound, k)
    hold_shape(sound)


def test_an_entry_that_disagrees_with_its_file_fails():
    spec = copy.deepcopy(REPO_SPEC)
    spec["per_layer"][4]["unit"] = "us"
    with pytest.raises(AssertionError):
        hold_metric(spec, REPO, spec["per_layer"][4]["name"])
