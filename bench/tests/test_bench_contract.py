"""BENCHMARK.json names only what exists, in the allowed forms, and every
name it gives resolves to a file of its own."""

import json
import re

from bench_toy import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")


def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_and_names():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_configs_and_cells_resolve():
    b = bench()
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        data = json.loads((REPO / c["file"]).read_text())
        assert data["source"] == c["source"] and data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert all(k in data and NAME.match(k) for k in c["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))
        assert ONE_LINE.match(c["why"]) and ONE_LINE.match(c["source"])
        for f in (f"bench/adapters/{data['family']}.py",
                  f"bench/reference/{data['family']}.py"):
            assert (REPO / f).exists()
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").exists()
        assert ONE_LINE.match(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in b["workloads"]} == set(cfgs)


def test_metrics_resolve():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and ONE_LINE.match(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", sorted(cells))
        assert set(m["workloads"]) <= set(moved)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert (REPO / "bench/metrics" / f"{m['name']}.py").exists()
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
