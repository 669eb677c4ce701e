"""The cell PR 39 adds rehearses end to end, its controls fail it,
``lib/gdn_cost`` agrees with hand counts, and its readers find the ``gdn.*``
ops and the pool-read kernel of the right program in a small made-up trace:
two Mosaic calls in one decode program, one of them under ``moe.experts``,
told apart. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_gdn_cells.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _hybrid, _pool_attn
from benchmarks.lib import gdn_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3next-serve-longctx"


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
    r = _run("benchmarks/run.py", "--workload", CELL, "--seed", "2147483659",
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
    for name in ("serve_ttft_p95_ms", "routed_pairs_here_per_token",
                 "moe_load_max_over_mean", "live_slots_per_step"):
        assert name + ".rehearsal" in last["metrics"]
    for name in ("gdn_roofline", "pool_attn_roofline", "gdn_scan_roofline"):
        assert name + ".rehearsal" not in last["metrics"]
    assert "kv_rows_per_step=" in r.stdout
    assert "state_bytes_recurrent=" in r.stdout and "state_bytes_kv=" in r.stdout


@pytest.mark.parametrize("control", ["float8", "no_decay", "no_gate"])
def test_each_control_fails_the_cell(control):
    """The controls that set the lower readings of the cell's limits: the
    reference with float8 weights, with a state that never forgets, and
    without the attention's output gate."""
    r = _run("benchmarks/tools/float8_reference_gdn.py", control,
             "--workload", CELL, "--seed", "11", "--seconds", "2", "--trace",
             "0", "--rehearse")
    last = _last(r)
    assert last["correct"] is False and last["failed"] == 0, r.stdout[-3000:]


def test_the_knee_tool_with_the_cells_own_limits_sweeps_it():
    r = _run("benchmarks/tools/find_knee_dsa.py", "--workload", CELL,
             "--rates", "30,10", "--seconds", "1", "--seeds", "0",
             "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert rows[0]["offered"] == rows[0]["finished"] == 40
    assert rows[1]["skipped"] and "knee_rate_per_s" in rows[-1]


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-l4.json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the three reduced keys and nothing else
    differ from the catalog's config (where the catalog is at hand)."""
    cfg = _cfg()
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 128, 37984)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    share = cfg["share"]
    assert share["chips_per_layer"] == share["vocab_slices"] == 4
    assert share["held"] * 4 == 512 and cfg["vocab_size"] * 4 == 151936
    assert (share["first_expert"], share["first_vocab_row"]) == (0, 0)
    assert cfg["kept_layers"] == [0, 1, 2, 3] and "mtp" in cfg["omitted"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 16, 2, 256)
    assert (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"]) == (16, 32, 128, 128, 4)
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["shared_expert_intermediate_size"]) == (512, 10, 512)
    assert cfg["rotary_dim"] == cfg["partial_rotary_factor"] * cfg["head_dim"]
    assert cfg["rope_theta"] == 1e7
    assert gdn_cost.layer_counts(cfg) == {"gdn": 3, "attn": 1}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]


def test_the_cell_is_the_issues():
    cell = _cell()
    assert cell["server"]["slots"] == 64
    assert cell["server"]["max_len"] == 32768
    assert cell["server"]["buckets"] == [1024, 2048, 4096, 8192, 12288, 16384,
                                         20480, 28672]
    t = cell["traffic"]
    assert t["prompt_tokens"] == {"median": 8192, "sigma": 0.8, "min": 1024,
                                  "max": 28672}
    assert t["output_tokens"] == {"median": 512, "sigma": 0.7, "min": 64,
                                  "max": 2048}
    assert t["max_total_tokens"] == 30720 and t["schedule_seed"] == 0
    lim = t["limits"]
    assert (lim["ttft_s"], lim["ttft_s_per_1k_prompt"], lim["tpot_s"]) == (
        1.0, 0.30, 0.1)
    sustained = [r["rate_per_s"] for r in t["arrivals"]["sweep"]
                 if r["sustained"]]
    assert t["arrivals"]["rate_per_s"] == pytest.approx(0.8 * max(sustained))
    # every rung a multiple of the row and position blocks the long rungs
    # are taken in (routed_experts.ROW_BLOCK, gdn.SEQ_BLOCK)
    assert all(b % 4096 == 0 for b in cell["server"]["buckets"] if b > 8192)


def test_gdn_cost_against_hand_counts():
    cfg = _cfg()
    # one mixer: W_qkvz 2048 x (2 x 2048 + 2 x 4096), W_ba 2048 x 64, taps
    # 4 x 8192, A_log and dt_bias 32 each, the norm's 128, W_o 4096 x 2048
    mixer = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
             + 4096 * 2048)
    assert gdn_cost.gdn_layer_params(cfg) == mixer == 33718464
    assert gdn_cost.param_bytes(cfg) == 4
    # one slot's state in one layer: 32 x 128 x 128 float32 + 3 x 8192 bf16
    assert gdn_cost.gdn_slot_state_bytes(cfg) == 2097152 + 49152
    got = gdn_cost.gdn_step_bytes(cfg, live=16)
    assert got == 3 * (mixer * 4 + 2 * 16 * 2146304 + 2 * 16 * 2048 * 2)
    assert round(got / 1e6) == 611          # 405 of weights, 206 of state
    # memory bound at a decode step's rows on a v5e
    assert got / 819e9 > 3 * gdn_cost.gdn_step_flops(cfg, live=16) / 197e12
    # the recurrence for one token in one layer: 32 heads x 6 x 128 x 128
    # FLOP; q, k, v, o of 32 x 128 float32 and two gates of 32
    assert gdn_cost.scan_token_flops(cfg) == 32 * 6 * 128 * 128 == 3145728
    assert gdn_cost.scan_token_bytes(cfg) == 4 * (4 * 4096 + 64) == 65792
    # memory bound: 80 ns of bytes against 16 ns of FLOP a token and layer
    least = gdn_cost.scan_seconds(cfg, tokens=10000, flops_per_s=197e12,
                                  bytes_per_s=819e9)
    assert least == pytest.approx(3 * 10000 * 65792 / 819e9)
    assert least > 3 * 10000 * 3145728 / 197e12
    # one position's K and V in the attention layer: 2 x 2 heads x 256 bf16
    assert gdn_cost.kv_row_bytes(cfg) == 2048


KERNEL = ('%{}.{} = bf16[64,16,256]{{2,1,0}} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (decode), each
    time with 3 ns under gdn.*, the pool-read kernel for 2 ns (no scope in
    its name), the reached-experts kernel for 4 ns (named by its scope) and
    1 ns of router; (9) once (prefill) with 30 ns under gdn.scan, 5 under
    gdn.proj and a flash kernel. Returns the trace and the ``{device: {op
    name: tf_op}}`` a trace file would give."""
    def ev(name, start, dur, **stats):
        e = xplane.Event(name, float(start), float(dur))
        e.stats.update(stats)
        return e

    pool, experts = KERNEL.format("pool_decode", 3), KERNEL.format(
        "moe.experts", 4)
    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev("%fusion.1 = f32[64,32,128,128]", t, 2),
                ev("%fusion.2 = bf16[64,12288]", t + 2, 1),
                ev(pool, t + 5, 2), ev(experts, t + 10, 4),
                ev("%fusion.5 = f32[64,512]", t + 20, 1)]
    mods.append(ev("jit__unknown(9)", 3000, 80))
    flash = KERNEL.format("jvp__", 6)
    ops += [ev("%while.6 = f32[1,32,128,128]", 3010, 30),
            ev("%fusion.7 = bf16[1,4096,12288]", 3045, 5),
            ev(flash, 3055, 9)]
    host = [ev("bench.trace_window", 900, 2300),
            ev("dl4j.serve.prefill", 2990, 100, prompt_len=10000)]
    scopes = {0: {
        "%fusion.1 = f32[64,32,128,128]": "jit(_unknown)/gdn.step/mul",
        "%fusion.2 = bf16[64,12288]": "jit(_unknown)/gdn.proj/dot_general",
        pool: "jit(_unknown)/pallas_call",
        experts: "jit(_unknown)/moe.experts/pallas_call",
        "%fusion.5 = f32[64,512]": "jit(_unknown)/moe.route/dot_general",
        "%while.6 = f32[1,32,128,128]":
            "jit(_unknown)/while/body/gdn.scan/while",
        "%fusion.7 = bf16[1,4096,12288]":
            "jit(_unknown)/while/body/gdn.proj/dot_general",
        flash: "jit(_unknown)/pallas_call"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def _ctx():
    return {"cell": _cell(), "config": _cfg(), "device_kind": "TPU v5 lite",
            "chips": 1}


def test_two_kernels_in_one_decode_program_are_told_apart():
    """``pool_attn_*`` read the Mosaic calls without ``moe.experts`` in their
    name; the accepted ``moe_ms_per_decode_step`` reads the other one by its
    scope; no call is counted by both, and none of the prefill's."""
    from benchmarks.layer_metrics import _moe

    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    assert _pool_attn.decode_ms(trace, counters, ctx) == pytest.approx(2e-6)
    found = _moe.scoped_seconds(trace, counters, ctx, scopes)
    assert found["decode"] == (pytest.approx(10 * 5e-9), 10)   # 4 + 1 ns
    # nothing to read, nothing raised
    assert _pool_attn.decode_ms(trace, {"decode_steps_in_trace": 5},
                                ctx) is None
    assert _pool_attn.decode_ms(xplane.Trace(), counters, ctx) is None
    assert _pool_attn.decode_ms(trace, counters, {"cell": {}}) is None


def test_gdn_readers_on_a_made_up_trace():
    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    assert _hybrid.decode_ms(trace, counters, ctx, "gdn", scopes) \
        == pytest.approx(3e-6)
    found = _hybrid.scoped(trace, counters, ctx, "gdn", scopes)
    assert found["prefill"] == (pytest.approx(35e-9), 1)
    only_scan = _hybrid.scoped(trace, counters, ctx, "gdn_scan", scopes)
    assert only_scan["prefill"] == (pytest.approx(30e-9), 1)
    assert only_scan["decode"][0] == 0
    # 819 bytes at 819 GB/s is 1 ns: a third of the 3 ns a step
    assert _hybrid.roofline_pct(trace, counters, ctx, "gdn", 819.0,
                                scopes) == pytest.approx(100.0 * 1e-9 / 3e-9)
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _hybrid.decode_ms(trace, counters, ctx, "gdn", bare) is None


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_pool_reads_roofline_from_the_servers_rows():
    """2 ns a step in the made-up trace; 0.4 rows of 2,048 bytes at 819 GB/s
    are 1 ns: half."""
    trace, _ = _trace()
    got = _reader("pool_attn_roofline").compute(
        trace, None, {"decode_steps_in_trace": 10, "kv_rows_per_step": 0.4},
        _ctx())
    assert got == pytest.approx(100.0 * (0.4 * 2048 / 819e9) / 2e-9)
    assert 49 < got < 51
    assert _reader("pool_attn_ms_per_decode_step").compute(
        trace, None, {"decode_steps_in_trace": 10}, _ctx()) \
        == pytest.approx(2e-6)


NEW = ["gdn_ms_per_decode_step", "gdn_roofline", "gdn_prefill_ms_per_request",
       "gdn_scan_roofline", "pool_attn_ms_per_decode_step",
       "pool_attn_roofline"]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_a_trace(name):
    """What the parent commit's traced run gives them: no trace file of the
    cell, no counter of the new program. None, and nothing raised."""
    mod = _reader(name)
    trace, _ = _trace()
    assert mod.compute(xplane.Trace(), None, {}, _ctx()) is None
    assert mod.compute(trace, None, {"decode_steps_in_trace": 10},
                       {**_ctx(), "cell": {}}) is None


def test_benchmark_json_lists_the_cell_and_its_metrics_last():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == "qwen3-next-80b-a3b-l4"
    assert [m["name"] for m in bench["per_layer"][-6:]] == NEW
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert "decode_attn_ms_per_step" not in listed
    assert not {n for n in listed if n.startswith(("kda_", "routed_share"))}
    assert {"serve_tpot_p50_ms", "decode_step_ms", "moe_ms_per_decode_step",
            "kv_blocks_share", "unscoped_ms_per_decode_step"} <= listed
