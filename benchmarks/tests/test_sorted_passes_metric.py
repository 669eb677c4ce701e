"""``moe_rows_run_per_pair_here`` (PR 42): the reader on a made-up trace whose
``dl4j.serve.passes`` spans carry the program's counts, what it gives where
there is nothing to read, and its entry in ``BENCHMARK.json`` -- looked up by
name, wherever later PRs' entries put it. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sorted_passes_metric.py -q
"""

import importlib.util
import json
import os

import pytest

from benchmarks.lib import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "moe_rows_run_per_pair_here"


def _reader():
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _trace(prefills):
    def ev(name, start, dur, **stats):
        e = xplane.Event(name, float(start), float(dur))
        e.stats.update(stats)
        return e

    host = [ev("bench.trace_window", 0, 10000)]
    for i, attrs in enumerate(prefills):
        host.append(ev("dl4j.serve.prefill", 100 * i, 50, prompt_len=4096))
        if attrs is not None:
            host.append(ev("dl4j.serve.passes", 100 * i + 49, 0.001, **attrs))
    host.append(ev("dl4j.serve.decode", 9000, 10, live=3, moe_rows_run=999,
                   moe_pairs_run=1))            # another span's attrs
    return xplane.Trace({}, host)


def test_the_reader_divides_the_rows_run_by_the_pairs_they_served():
    """Two prefills through the sorted form (four layers of a 4,096-row block:
    one pass of 12,800 a layer over some 10,000 pairs; two passes where the
    router crowded 13,000 pairs here) and one on a rung short enough for the
    dense form, which opens no such span."""
    trace = _trace([
        dict(moe_rows_run=4 * 12800, moe_pairs_run=40100), None,
        dict(moe_rows_run=4 * 25600, moe_pairs_run=52000)])
    got = _reader().compute(trace, None, {}, {})
    assert got == pytest.approx((51200 + 102400) / (40100 + 52000))
    assert 1.0 < got < 2.0


def test_nothing_to_read_is_none_and_nothing_raises():
    """The parent's traced run (it opens no such span), a run whose prefills
    all took the dense form, a trace with no span, no trace."""
    mod = _reader()
    assert mod.compute(_trace([None] * 3), None, {}, {}) is None
    assert mod.compute(_trace([]), None, {}, {}) is None
    assert mod.compute(xplane.Trace(), None, {}, {"cell": {}}) is None
    # pairs of zero (every pass-form block empty): no ratio
    assert mod.compute(_trace([dict(moe_rows_run=0, moe_pairs_run=0)]), None,
                       {}, {}) is None


def test_the_entry_is_found_by_name_and_lists_the_share_cells():
    bench = _bench()
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    mod = _reader()
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    # the cells that hold a share of a router's experts: those that report
    # how many pairs land here
    share, = [m for m in bench["per_layer"]
              if m["name"] == "routed_pairs_here_per_token"]
    assert entry["workloads"] == share["workloads"]
    assert entry["layer"] == share["layer"] == "routed experts"
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert "olmoe-serve-chat" not in entry["workloads"]   # every expert held
    tpot, = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(tpot["workloads"])


def test_every_listed_cell_holds_a_share_past_the_dense_form():
    """Each listed cell's configuration holds fewer experts than its router
    has, and its ladder has a rung whose programs hand the experts more rows
    than the dense form takes -- where the passes run."""
    from deeplearning4j_tpu.models import routed_experts
    from deeplearning4j_tpu.serving.engine import PREFILL_BLOCK

    bench = _bench()
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    for name in entry["workloads"]:
        with open(os.path.join(ROOT, "benchmarks", "workloads",
                               name + ".json")) as f:
            cell = json.load(f)
        with open(os.path.join(ROOT, configs[cell["config"]])) as f:
            cfg = json.load(f)
        held = cfg.get("num_experts", cfg.get("n_routed_experts"))
        assert held < cfg["published"].get(
            "num_experts", cfg["published"].get("n_routed_experts")), name
        blocks = cell["driver"] in ("lm_serve_dsa", "lm_serve_mtp")
        rows = [PREFILL_BLOCK if blocks and b % PREFILL_BLOCK == 0 else b
                for b in cell["server"]["buckets"]]
        assert max(rows) > routed_experts.DENSE_MAX_TOKENS, name
