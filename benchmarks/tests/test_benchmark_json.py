"""``BENCHMARK.json`` against the limits of the benchmark's contract that can be
checked without a chip, and against the files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_limits():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert len(b["command"]) <= 32 and all(_line(w) for w in b["command"])
    assert b["paths"] == ["benchmarks"]
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_and_cells():
    b = _bench()
    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                                 r"head_dim|per_tok)", key)
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(body.get("reduced", {})) == set(c["reduced"])
    cells = b["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(1 for w in cells if w["chips"] == 4) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and _line(w["why"])
        path = os.path.join(ROOT, "benchmarks", "workloads", w["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        assert (cell["config"], cell["traffic_name"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "drivers", cell["driver"] + ".py"))
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in cells}


def test_metrics():
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e, layer = b["end_to_end"], b["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    all_names = [m["name"] for m in e2e + layer]
    assert len(set(all_names)) == len(all_names)
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in e2e)
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in {x["name"] for x in e2e}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells

    def where(m):
        return set(m.get("workloads", cells))

    for cell in cells:
        mine = [m for m in e2e if cell in where(m)]
        assert len(mine) >= 2                       # setup_s and one other
        moved = {m["name"] for m in mine}
        assert any(cell in where(m) and m["moves"] in moved for m in layer)
    for m in layer:                 # reported only where the metric it moves is
        target = next(x for x in e2e if x["name"] == m["moves"])
        assert where(m) <= where(target)


def test_readers_constants_agree_with_the_list():
    """A reader's NAME, UNIT, LAYER, MOVES are for its reader; BENCHMARK.json
    decides. Where a reader states them they must not drift."""
    import importlib.util

    for m in _bench()["per_layer"]:
        path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                            m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader_" + m["name"], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
