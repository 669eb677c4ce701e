"""The two cells PR 29 adds rehearse end to end, ``lib/hybrid_cost`` agrees
with hand counts, and the hybrid model's readers find the ``kda.*``, ``mla.*``
and ``moe.*`` ops of the right program in a small made-up trace. Run by hand
(not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_hybrid_cells.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import hybrid_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(script, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def _last(r):
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [
    ("ling-serve-reason", 0), ("ling-serve-reason", 1),
    ("sc2-serve-short", 0), ("sc2-serve-short", 1)])
def test_new_cells_rehearse_end_to_end(cell, trace):
    r = _run("benchmarks/run.py", "--workload", cell, "--seed", "2147483659",
             "--seconds", "2", "--trace", str(trace), "--rehearse")
    last = _last(r)
    assert last["correct"] is True, r.stdout[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    if not trace:
        assert "setup_s.rehearsal" in last["metrics"]
        assert "serve_tpot_p50_ms.rehearsal" in last["metrics"]
        return
    # a CPU trace has no device plane: the counter metrics are there, the
    # device-trace ones are left out and nothing raises
    assert "serve_ttft_p95_ms.rehearsal" in last["metrics"]
    if cell == "ling-serve-reason":
        assert "routed_pairs_here_per_token.rehearsal" in last["metrics"]
        assert "moe_load_max_over_mean.rehearsal" in last["metrics"]
        assert "kda_roofline.rehearsal" not in last["metrics"]
        assert "check: routing flipped_share=" in r.stdout
        assert "state_bytes_recurrent=" in r.stdout


def test_float8_reference_fails_the_hybrid_cell():
    """The control that sets the lower reading of the cell's limits: with
    the reference's weights rounded to float8 e4m3 the check fails."""
    r = _run("benchmarks/tools/float8_reference_ling.py", "--workload",
             "ling-serve-reason", "--seed", "11", "--seconds", "2", "--trace",
             "0", "--rehearse")
    last = _last(r)
    assert last["correct"] is False and last["failed"] == 0, r.stdout[-3000:]


def test_knee_tool_sweeps_the_hybrid_cell():
    r = _run("benchmarks/tools/find_knee_moe.py", "--workload",
             "ling-serve-reason", "--rates", "10", "--seconds", "1",
             "--seeds", "0", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert rows[0]["offered"] == rows[0]["finished"] == 10
    assert "knee_rate_per_s" in rows[-1]


def _ling():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-vl-l7.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the four reduced keys and nothing else
    differ from the catalog's config (where the catalog is at hand)."""
    cfg = _ling()
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "first_k_dense_replace", "num_experts",
                                   "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 64, 19648)
    assert cfg["published"] == {"num_hidden_layers": 42,
                                "first_k_dense_replace": 2,
                                "num_experts": 512, "vocab_size": 157184}
    assert cfg["share"]["chips_per_layer"] == 8
    assert cfg["share"]["held"] * 8 == 512 and cfg["vocab_size"] * 8 == 157184
    assert "vision_tower" in cfg["omitted"]
    assert hybrid_cost.layer_counts(cfg) == {"kda": 6, "mla": 1, "moe": 6,
                                             "glu": 1}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]


def test_hybrid_cost_against_hand_counts():
    cfg = _ling()
    # one KDA mixer: 5 projections of 2560 x 4096, beta and the gate
    # 2560 x 32 each, three convolutions of 4 x 4096, A_log, dt_bias, gain
    kda = 5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    assert hybrid_cost.kda_layer_params(cfg) == kda == 52646048
    # one slot's state in one layer: 32 x 128 x 128 float32 + 3 x 12288 bf16
    assert hybrid_cost.kda_slot_state_bytes(cfg) == 2097152 + 73728
    got = hybrid_cost.kda_step_bytes(cfg, live=40)
    assert got == 6 * (kda * 4 + 2 * 40 * 2170880 + 2 * 40 * 2560 * 2)
    assert round(got / 1e9, 2) == 2.31        # 1.26 of weights, 1.04 of state
    # one MLA mixer
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256
           + 32 * 128 * 2560 + 2560 * 32)
    assert hybrid_cost.mla_layer_params(cfg) == mla == 31965696
    assert hybrid_cost.mla_step_bytes(cfg, live=40, context=2000) == (
        mla * 4 + 40 * 2001 * 576 * 2 + 2 * 40 * 2560 * 2)
    # the routed share: one expert is 3 x 2560 x 768 float32
    assert hybrid_cost.expert_bytes(cfg, 4) == 23592960
    fixed = (2560 * 512 + 512 + 3 * 2560 * 768) * 4
    assert hybrid_cost.routed_step_bytes(cfg, live=40, touched=200) == (
        200 * 23592960 + 6 * (fixed + 2 * 40 * 2560 * 2))
    # every held expert of every layer: 9.06 GB of experts, 0.17 of the rest
    full = hybrid_cost.routed_step_bytes(cfg, live=64, touched=6 * 64)
    assert round(full / 1e9, 2) == 9.24
    # all three parts are memory bound at a decode step's rows on a v5e
    for nbytes, flops in (
            (got, hybrid_cost.kda_step_flops(cfg, live=40)),
            (hybrid_cost.mla_step_bytes(cfg, live=40, context=2000),
             hybrid_cost.mla_step_flops(cfg, live=40, context=2000)),
            (full, hybrid_cost.routed_step_flops(cfg, live=64,
                                                 pairs_here=1.0))):
        assert nbytes / 819e9 > 3 * flops / 197e12


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (decode) with
    3 us under kda.*, 1 us under mla.* and 2 us under moe.* each; (9) once
    (prefill) with 20 us under kda.scan. Returns the trace and the
    ``{device: {op name: tf_op}}`` a trace file would give."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev("%fusion.1 = f32[64,32,128,128]", t, 2),
                ev("%fusion.2 = bf16[64,4096]", t + 2, 1),
                ev("%fusion.3 = f32[64,32,10240]", t + 5, 1),
                ev("%convolution.4 = f32[64,64,768]", t + 10, 2),
                ev("%fusion.5 = bf16[64,2560]", t + 20, 7)]
    mods.append(ev("jit__unknown(9)", 3000, 80))
    ops.append(ev("%while.6 = f32[1,32,128,128]", 3010, 20))
    host = [ev("bench.trace_window", 900, 2300)]
    scopes = {0: {
        "%fusion.1 = f32[64,32,128,128]": "jit(_unknown)/kda.step/mul",
        "%fusion.2 = bf16[64,4096]": "jit(_unknown)/kda.proj/dot_general",
        "%fusion.3 = f32[64,32,10240]": "jit(_unknown)/mla.attend/bqhc,btc",
        "%convolution.4 = f32[64,64,768]":
            "jit(_unknown)/moe.experts/nd,edf->enf/dot_general",
        "%fusion.5 = bf16[64,2560]": "jit(_unknown)/mul",
        "%while.6 = f32[1,32,128,128]": "jit(_unknown)/kda.scan/while"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def _ctx():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "ling-serve-reason.json")) as f:
        cell = json.load(f)
    return {"cell": cell, "config": _ling(), "device_kind": "TPU v5 lite",
            "chips": 1}


def test_hybrid_readers_on_a_made_up_trace():
    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    assert _hybrid.decode_ms(trace, counters, ctx, "kda", scopes) \
        == pytest.approx(3e-6)
    assert _hybrid.decode_ms(trace, counters, ctx, "mla", scopes) \
        == pytest.approx(1e-6)
    assert _hybrid.decode_ms(trace, counters, ctx, "routed", scopes) \
        == pytest.approx(2e-6)
    found = _hybrid.scoped(trace, counters, ctx, "kda", scopes)
    assert found["prefill"] == (pytest.approx(20e-9), 1)
    # 819 bytes at 819 GB/s is 1 ns: a third of the 3 ns a step
    assert _hybrid.roofline_pct(trace, counters, ctx, "kda", 819.0,
                                scopes) == pytest.approx(100.0 * 1e-9 / 3e-9)
    # a program without the scopes (the parent commit), a cell without the
    # key: nothing to read, nothing raised
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _hybrid.decode_ms(trace, counters, ctx, "kda", bare) is None
    assert _hybrid.scoped(trace, counters, {**ctx, "cell": {}}, "kda",
                          scopes) is None


@pytest.mark.parametrize("name", [
    "kda_ms_per_decode_step", "kda_roofline", "mla_ms_per_decode_step",
    "kda_prefill_ms_per_request", "routed_share_roofline",
    "routed_pairs_here_per_token"])
def test_new_readers_return_nothing_without_a_trace(name):
    """What the parent commit's traced run gives them: no trace file of the
    cell, no counter of the new program. None, and nothing raised."""
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    trace, _ = _trace()
    assert mod.compute(xplane.Trace(), None, {}, _ctx()) is None
    assert mod.compute(trace, None, {"decode_steps_in_trace": 10},
                       {**_ctx(), "cell": {}}) is None
    if name == "routed_pairs_here_per_token":
        assert mod.compute(trace, None,
                           {"routed_pairs_here_per_token": 1.02},
                           _ctx()) == 1.02
