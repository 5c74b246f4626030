"""BENCHMARK.json against the limits of its contract that need no run, and
against the files it names."""
import json
import os
import re

import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_keys_names_and_lengths():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(tiny.REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(m["command"]) <= 32 and all(_line(w) for w in m["command"])
    assert m["paths"] == ["benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmark/") and os.path.isfile(
            os.path.join(tiny.REPO, c["file"]))
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
    names = [x["name"] for x in m["configs"]] + [x["name"] for x in m["workloads"]]
    assert len(names) == len(set(names))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 2)


def test_every_config_file_states_its_deployment():
    m = _manifest()
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in m["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in m["configs"]:
        assert c["name"] in used
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            cfg = json.load(f)
        for key in ("source", "reduced", "assumed", "guarantees", "cluster",
                    "server", "jobs"):
            assert key in cfg, (c["name"], key)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["jobs"]["templates"] and cfg["jobs"]["warm"]
    for w in m["workloads"]:
        assert w["config"] in {c["name"] for c in m["configs"]}
        path = os.path.join(tiny.BENCH, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        assert "drain_s" in mix and "why" in mix
        assert ("rate_per_s" in mix) == (mix["loop"] == "open")


def test_metrics_and_their_cells():
    m = _manifest()
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(m["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.25
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    names = list(e2e) + [x["name"] for x in m["per_layer"]]
    assert len(names) == len(set(names))

    def reported_in(metric):
        return metric.get("workloads", cells)

    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
        assert set(reported_in(x)) <= set(cells)
        assert os.path.isfile(os.path.join(tiny.BENCH, "metrics", x["name"] + ".py"))
    layers = set()
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert _line(x["layer"]) and x["moves"] in e2e and x["moves"] != "setup_s"
        layers.add(x["layer"])
        # every cell that reports it reports the end-to-end metric it moves
        assert set(reported_in(x)) <= set(reported_in(e2e[x["moves"]]))
        if "_roofline" in x["name"] or "mfu" in x["name"]:
            assert x["unit"] == "%" and x["source"] == "device_trace"
    for cell in cells:
        assert [x for x in m["end_to_end"] if x["name"] != "setup_s"
                and cell in reported_in(x)], cell
        assert [x for x in m["per_layer"] if cell in reported_in(x)], cell
    # PERF.md's list of layers has each of them, letter for letter
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_a_full_check_fits():
    m = _manifest()
    rs = m["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
