"""BENCHMARK.json against the letter of the contract that can be checked
without a chip: keys, name and unit alphabets, lengths, files found by
name, every cell's metrics, the share of four-chip cells."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    cells = len(B["workloads"])
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(B["configs"]) <= 24
    assert all(_line(w) for w in B["command"]) and len(B["command"]) <= 32


def test_configs_and_their_files():
    files = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in B["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        for key in ("source", "assumed", "precision", "memory_reckoned",
                    "kind", "check"):
            assert key in cfg, (c["name"], key)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench/references", c["name"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "perfbench/harness", f"kind_{cfg['kind']}.py"))
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}


def test_cells():
    seen, four = set(), 0
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        assert os.path.exists(os.path.join(
            ROOT, "perfbench/traffic", w["traffic"] + ".json"))
    assert four <= max(1, len(B["workloads"]) // 4)


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = set()
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = {}
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench/metrics", m["name"] + ".py")), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:
        have = [m for m in B["end_to_end"] if c in m.get("workloads", cells)]
        assert len(have) >= 2, c
        assert any(c in m.get("workloads", cells) for m in B["per_layer"]), c
    # a whole-step mfu beside every kernel roofline, moving the same metric
    for m in B["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in B["per_layer"])
