"""The cell PR 31 adds rehearses end to end, its configuration is the catalog
row but for the cut, ``lib/sparse_cost`` agrees with hand counts, its readers
find the ``dsa.index`` and ``mla.attend`` ops of the right program in a small
made-up trace, and both controls of its check fail. Run by hand (not part of
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sparse_cells.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import sparse_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ["dsa_index_ms_per_decode_step", "dsa_index_roofline",
           "sparse_attend_roofline", "dsa_prefill_ms_per_request",
           "dsa_keys_attended_share"]


def _run(script, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def _last(r):
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_end_to_end(trace):
    r = _run("benchmarks/run.py", "--workload", "glm-serve-longdoc", "--seed",
             "2147483659", "--seconds", "2", "--trace", str(trace),
             "--rehearse")
    last = _last(r)
    assert last["correct"] is True, r.stdout[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    # index_topk 8 against prompts of 16-48: the rehearsal selects
    assert "selections_judged=" in r.stdout
    share = float(r.stdout.split("keys_attended_share=")[1].split()[0])
    assert 0.1 < share < 0.6
    assert "state_bytes_index=" in r.stdout
    if not trace:
        assert "setup_s.rehearsal" in last["metrics"]
        assert "serve_tpot_p50_ms.rehearsal" in last["metrics"]
        return
    # a CPU trace has no device plane: the counter metrics are there, the
    # device-trace ones are left out and nothing raises
    for name in ("serve_ttft_p95_ms", "routed_pairs_here_per_token",
                 "moe_load_max_over_mean", "dsa_keys_attended_share"):
        assert name + ".rehearsal" in last["metrics"]
    assert "dsa_index_roofline.rehearsal" not in last["metrics"]


@pytest.mark.parametrize("control", ["float8", "dense", "recall95"])
def test_the_controls_fail_the_cell(control):
    """The lower readings of the cell's limits: with the reference's weights
    rounded to float8 e4m3, with the reference's selection switched off
    (every query attends every position), and with the program's decode
    steps selecting at recall 0.95 (at the rehearsal's 8 keys: 7 of 8), the
    check fails."""
    r = _run("benchmarks/tools/float8_reference_glm.py", control,
             "--workload", "glm-serve-longdoc", "--seed", "11", "--seconds",
             "2", "--trace", "0", "--rehearse")
    last = _last(r)
    assert last["correct"] is False and last["failed"] == 0, r.stdout[-3000:]
    if control == "recall95":     # by the overlap, and by nothing else
        lines = [x for x in r.stdout.splitlines() if "below_select_overlap" in x]
        assert lines and all("wrong_selections=0" in x for x in lines)
        assert any("below_select_overlap=0" not in x for x in lines)


def test_knee_tool_sweeps_the_cell():
    r = _run("benchmarks/tools/find_knee_dsa.py", "--workload",
             "glm-serve-longdoc", "--rates", "10",
             "--seconds", "1", "--seeds", "0", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    # a second at 10 a second is 10 requests: offered for the 4 s that 40 take
    assert rows[0]["offered"] == rows[0]["finished"] == 40
    assert rows[0]["offered_s"] == 4.0 and "in_flight_at_end" in rows[0]
    assert "knee_rate_per_s" in rows[-1]


def _glm():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5.2-l5.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the seven reduced keys and nothing else
    differ from the catalog's config (where the catalog is at hand)."""
    cfg = _glm()
    reduced = {"num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "mlp_layer_types",
               "indexer_types", "num_nextn_predict_layers"}
    assert set(cfg["reduced"]) == reduced
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 8, 19360, 0)
    assert cfg["kept_layers"] == [2, 6, 7, 8, 9]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["indexer_types"] == ["full", "full"] + ["shared"] * 3
    assert cfg["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    assert cfg["share"]["chips_per_layer"] == 32
    assert cfg["share"]["held"] * 32 == 256 and cfg["vocab_size"] * 8 == 154880
    assert "multi_token_prediction" in cfg["omitted"]
    assert not any(any(w in key for w in ("_dim", "_rank", "hidden_size",
                                          "intermediate_size", "per_tok"))
                   for key in reduced)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == reduced
    assert cfg["source"] == row["source_url"]
    # the kept layers' entries are the published lists' own
    for key in ("mlp_layer_types", "indexer_types"):
        assert cfg[key] == [row["config"][key][i] for i in cfg["kept_layers"]]


def test_sparse_cost_against_hand_counts():
    cfg = _glm()
    assert sparse_cost.layers(cfg) == (5, 2)
    assert sparse_cost.latent_row_lanes(cfg) == 640      # 576 used
    # one indexer: wq 2048 x 4096, wk 6144 x 128, ww 6144 x 32, gain and bias
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32 + 2 * 128
    assert sparse_cost.indexer_weight_bytes(cfg) == 4 * indexer == 37487616
    # 16 live slots at 20,000 positions: keys_cached counts 5 layers
    cached = 16 * 20000 * 5
    got = sparse_cost.index_step_bytes(cfg, keys_cached=cached, live=16)
    rows = 16 * 2 * (2 * (2048 + 6144 + 128) + 4 * 2048)
    assert got == 16 * 20000 * 2 * 128 * 2 + 2 * 4 * indexer + rows
    assert round(got / 1e6, 1) == 239.6      # 163.8 of keys, 75.0 of weights
    attended = 16 * 2048 * 5
    got = sparse_cost.attend_step_bytes(cfg, keys_attended=attended, live=16)
    wukv = 4 * 512 * 64 * (192 + 256)
    rows = 16 * 5 * 2 * 64 * (192 + 64 + 256)
    assert got == attended * 640 * 2 + 5 * wukv + rows
    assert round(got / 1e6, 1) == 508.6      # 209.7 of rows, 293.6 of wukv
    # both parts wait for memory at a decode step's rows on a v5e
    flops = 16 * 5 * 64 * 2048 * (576 + 512) * 2
    assert got / 819e9 > 3 * flops / 197e12


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (decode) with
    2 us under dsa.index, 3 us under mla.attend and 1 us under mla.proj each;
    (9) twice (a prefill's blocks) with 20 us under dsa.index and 10 us
    under mla.attend each, inside one ``serve.prefill`` span."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev("%sort.1 = f32[16,32768]", t, 2),
                ev("%gather.2 = bf16[16,2048,640]", t + 2, 3),
                ev("%fusion.3 = bf16[16,16384]", t + 6, 1),
                ev("%fusion.5 = bf16[16,6144]", t + 20, 7)]
    for i in range(2):
        t = 3000 + 100 * i
        mods.append(ev("jit__unknown(9)", t, 80))
        ops += [ev("%sort.6 = f32[128,8192]", t + 10, 20),
                ev("%gather.7 = bf16[128,2048,640]", t + 40, 10)]
    host = [ev("bench.trace_window", 900, 2400),
            ev("dl4j.serve.prefill", 2990, 200)]
    scopes = {0: {
        "%sort.1 = f32[16,32768]": "jit(_unknown)/dsa.index/top_k",
        "%gather.2 = bf16[16,2048,640]": "jit(_unknown)/mla.attend/gather",
        "%fusion.3 = bf16[16,16384]": "jit(_unknown)/mla.proj/dot_general",
        "%fusion.5 = bf16[16,6144]": "jit(_unknown)/mul",
        "%sort.6 = f32[128,8192]": "jit(_unknown)/dsa.index/top_k",
        "%gather.7 = bf16[128,2048,640]": "jit(_unknown)/mla.attend/gather"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def _ctx():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "glm-serve-longdoc.json")) as f:
        cell = json.load(f)
    return {"cell": cell, "config": _glm(), "device_kind": "TPU v5 lite",
            "chips": 1}


def test_sparse_readers_on_a_made_up_trace():
    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    assert _hybrid.decode_ms(trace, counters, ctx, "dsa_index", scopes) \
        == pytest.approx(2e-6)
    assert _hybrid.decode_ms(trace, counters, ctx, "mla_attend", scopes) \
        == pytest.approx(3e-6)
    assert _hybrid.decode_ms(trace, counters, ctx, "mla", scopes) \
        == pytest.approx(4e-6)                   # mla.proj and mla.attend
    found = _hybrid.scoped(trace, counters, ctx, "dsa_prefill", scopes)
    assert found["prefill"] == (pytest.approx(60e-9), 2)
    # 819 bytes at 819 GB/s is 1 ns: a third of mla.attend's 3 ns a step
    assert _hybrid.roofline_pct(trace, counters, ctx, "mla_attend", 819.0,
                                scopes) == pytest.approx(100.0 / 3)
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _hybrid.decode_ms(trace, counters, ctx, "dsa_index", bare) is None


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", READERS)
def test_new_readers_return_nothing_without_a_trace(name):
    """What the parent commit's traced run gives them: no trace file of the
    cell, no counter of the new program. None, and nothing raised."""
    mod = _reader(name)
    trace, _ = _trace()
    assert mod.compute(xplane.Trace(), None, {}, _ctx()) is None
    assert mod.compute(trace, None, {"decode_steps_in_trace": 10},
                       {**_ctx(), "cell": {}}) is None
    if name == "dsa_keys_attended_share":
        assert mod.compute(trace, None, {"dsa_keys_attended_share": 0.17},
                           _ctx()) == 0.17


def test_the_readers_are_the_benchmarks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        mod, m = _reader(name), listed[name]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == ["glm-serve-longdoc"]
    assert [m["name"] for m in bench["per_layer"][-5:]] == READERS
