"""The cell PR 44 adds rehearses end to end, its controls fail it,
``lib/mixed_attn_cost`` agrees with hand counts, and its readers tell the
three kinds of Mosaic call of one decode program apart in a small made-up
trace: a window layer's pool read (named ``attn.window``), the full layer's
(no scope's name) and the reached-experts kernel (``moe.experts``). Run by
hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mellum2_cells.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _mixed_attn, _pool_attn
from benchmarks.lib import mixed_attn_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "mellum2-serve-mixed", "mellum2-12b-a2.5b-l4"
NEW = ["win_attn_ms_per_decode_step", "win_attn_roofline",
       "full_attn_ms_per_decode_step", "full_attn_roofline",
       "moe_dense_roofline", "flash_prefill_ms_per_request",
       "kv_pool_bytes_share"]


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
    for name in ("serve_ttft_p95_ms", "moe_load_max_over_mean",
                 "live_slots_per_step", "kv_pool_bytes_share"):
        assert name + ".rehearsal" in last["metrics"]
    for name in ("win_attn_roofline", "full_attn_roofline",
                 "moe_dense_roofline", "flash_prefill_ms_per_request"):
        assert name + ".rehearsal" not in last["metrics"]
    # a ring of 16 rows x 3 layers beside 96 rows x 1: (48 + 96) / (4 x 96)
    assert last["metrics"]["kv_pool_bytes_share.rehearsal"]["value"] \
        == pytest.approx(0.375)
    assert "kv_rows_window_per_step=" in r.stdout
    assert "state_bytes_ring=" in r.stdout and "state_bytes_kv=" in r.stdout
    assert "whose cursor crosses a multiple of the ring" in r.stdout


@pytest.mark.parametrize("control", ["float8", "no_window", "one_rope",
                                     "stale_ring"])
def test_each_control_fails_the_cell(control):
    """The controls that set the lower readings of the cell's limits: the
    reference with float8 weights, without the window, with one RoPE for
    both kinds of layer, and with the window a ring one lap late would
    give."""
    r = _run("benchmarks/tools/float8_reference_mellum2.py", control,
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
    assert "knee_rate_per_s" in rows[-1]


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the three keys of the one cut and nothing
    else differ from the catalog's config (where the catalog is at hand)."""
    cfg = _cfg()
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "mlp_layer_types"}
    assert cfg["num_hidden_layers"] == 4 and cfg["kept_layers"] == [0, 1, 2, 3]
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert "mtp" in cfg["omitted"] and "ring" in cfg["assumed"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2304, 32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"]) == (64, 8, 896)
    assert (cfg["sliding_window"], cfg["vocab_size"]) == (1024, 98304)
    ropes = cfg["rope_parameters"]
    assert ropes["sliding_attention"] == {"rope_type": "default",
                                          "rope_theta": 500000}
    assert ropes["full_attention"]["factor"] == 16
    assert mixed_attn_cost.layer_counts(cfg) == {"window": 3, "full": 1}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    assert cfg["layer_types"] == row["config"]["layer_types"][:4]


def test_the_cell_is_the_issues():
    cell = _cell()
    assert cell["server"]["slots"] == 64
    assert cell["server"]["max_len"] == 32768
    assert cell["server"]["buckets"] == [256, 512, 1024, 2048, 4096, 8192,
                                         12288, 16384, 20480, 28672]
    t = cell["traffic"]
    assert t["prompt_tokens"] == {"median": 2048, "sigma": 1.4, "min": 128,
                                  "max": 28672}
    assert t["output_tokens"] == {"median": 256, "sigma": 0.6, "min": 32,
                                  "max": 1024}
    assert t["max_total_tokens"] == 30720 and t["schedule_seed"] == 0
    lim = t["limits"]
    assert (lim["ttft_s"], lim["ttft_s_per_1k_prompt"], lim["tpot_s"]) == (
        1.0, 0.30, 0.1)
    # the knee: the highest rate every seed sustained (a process ends at the
    # first rate its seed sustains, from the highest down)
    sweep = t["arrivals"]["sweep"]
    knee = min(max(r["rate_per_s"] for r in sweep
                   if r["seed"] == seed and r["sustained"])
               for seed in {r["seed"] for r in sweep})
    assert t["arrivals"]["rate_per_s"] == pytest.approx(0.8 * knee)
    assert all(r["offered_s"] >= 51 for r in t["arrivals"]["sweep"]
               if "offered_s" in r)
    assert cell["check"]["longest_max_prompt"] == 16384
    assert cell["check"]["pad_to"] >= 16384 + t["output_tokens"]["max"]


def test_mixed_attn_cost_against_hand_counts():
    cfg = _cfg()
    assert mixed_attn_cost.param_bytes(cfg) == 4
    # one position's K and V in one layer: 2 x 4 heads x 128 bf16
    assert mixed_attn_cost.kv_row_bytes(cfg) == 2048
    # one expert: 3 x 2,304 x 896 float32 (moe_intermediate_size, not the
    # dense intermediate_size 7,168)
    assert mixed_attn_cost.expert_bytes(cfg) == 3 * 2304 * 896 * 4 == 24772608
    # every expert of four layers touched by 25 live rows: 256 experts, four
    # routers of 2,304 x 64 float32, 25 rows of 2,304 bf16 in and out a layer
    got = mixed_attn_cost.routed_step_bytes(cfg, tokens=25, touched=256)
    assert got == 256 * 24772608 + 4 * (2304 * 64 * 4 + 2 * 25 * 2304 * 2)
    assert round(got / 1e9, 2) == 6.35
    # one length of pool: 4 layers x 64 slots x 32,768 rows of 2 KiB = 16 GiB
    whole = mixed_attn_cost.one_length_pool_bytes(cfg, slots=64,
                                                  max_len=32768)
    assert whole == 16 * 2 ** 30
    # the ring of 1,024 rows for three layers beside the full layer's rows
    held = 64 * 2048 * (32768 + 3 * 1024)
    assert round(held / whole, 3) == 0.273


KERNEL = ('%{}.{} = bf16[64,32,128]{{2,1,0}} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (decode), each
    time with three pool reads named ``attn.window`` (1 ns each), one pool
    read with no scope's name (5 ns), the reached-experts kernel (4 ns, named
    by its scope) and 1 ns of router; (9) once (prefill) with two flash
    kernels (9 and 20 ns), an experts' kernel and a grouped matmul of the
    sorted experts (``ragged-dot-*``: Mosaic, no scope path). Returns the trace and the
    ``{device: {op name: tf_op}}`` a trace file would give."""
    def ev(name, start, dur, **stats):
        e = xplane.Event(name, float(start), float(dur))
        e.stats.update(stats)
        return e

    ring = [KERNEL.format("attn.window", i) for i in (1, 2, 3)]
    pool = KERNEL.format("pool_decode", 4)
    experts = KERNEL.format("moe.experts", 5)
    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev(k, t + 2 * j, 1) for j, k in enumerate(ring)]
        ops += [ev(pool, t + 10, 5), ev(experts, t + 20, 4),
                ev("%fusion.5 = f32[64,64]", t + 30, 1)]
    mods.append(ev("jit__unknown(9)", 3000, 80))
    banded, causal = KERNEL.format("jvp__", 6), KERNEL.format("jvp__", 7)
    sorted_experts = KERNEL.format("moe.experts", 8)
    ragged = KERNEL.format("ragged-dot-none", 9)
    ops += [ev(banded, 3010, 9), ev(causal, 3020, 20),
            ev(sorted_experts, 3045, 7), ev(ragged, 3055, 11)]
    host = [ev("bench.trace_window", 900, 2300),
            ev("dl4j.serve.prefill", 2990, 100, prompt_len=10000)]
    # the ten dispatching steps' own counts, and a span that only reads
    host += [ev("dl4j.serve.decode", 1000 + 100 * i, 50, live=1,
                kv_rows_window=0.2 * (i % 5 + 1), kv_rows_full=0.6)
             for i in range(10)] + [ev("dl4j.serve.decode", 2000, 5, live=0)]
    scopes = {0: {
        **{k: "jit(_unknown)/attn.window/pallas_call" for k in ring},
        pool: "jit(_unknown)/pallas_call",
        experts: "jit(_unknown)/moe.experts/pallas_call",
        "%fusion.5 = f32[64,64]": "jit(_unknown)/moe.route/dot_general",
        banded: "jit(_unknown)/pallas_call",
        causal: "jit(_unknown)/pallas_call",
        sorted_experts: "jit(_unknown)/moe.experts/pallas_call",
        ragged: ""}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def _ctx():
    return {"cell": _cell(), "config": _cfg(), "device_kind": "TPU v5 lite",
            "chips": 1}


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_three_kernels_in_one_decode_program_are_told_apart():
    """``win_attn_*`` read the Mosaic calls named ``attn.window``,
    ``full_attn_*`` those with neither that nor ``moe.experts`` in their
    name, the accepted ``moe_ms_per_decode_step`` the experts' by its scope:
    no call is counted twice, none of the prefill's is counted, and the two
    pool reads add up to the accepted ``_pool_attn.decode_ms``."""
    from benchmarks.layer_metrics import _moe

    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    win = _mixed_attn.decode_ms(trace, counters, ctx, "window")
    full = _mixed_attn.decode_ms(trace, counters, ctx, "full")
    assert win == pytest.approx(3e-6) and full == pytest.approx(5e-6)
    assert win + full == pytest.approx(
        _pool_attn.decode_ms(trace, counters, ctx))
    found = _moe.scoped_seconds(trace, counters, ctx, scopes)
    assert found["decode"] == (pytest.approx(10 * 5e-9), 10)   # 4 + 1 ns
    assert [_mixed_attn.kind_of(n) for n in (
        KERNEL.format("attn.window", 1), KERNEL.format("x", 2),
        KERNEL.format("moe.experts", 3), KERNEL.format("ragged-dot-none", 4),
        "%fusion.5 = f32[64,64]")] == [
            "window", "full", "experts", "experts", None]
    # nothing to read, nothing raised
    for kind in ("window", "full"):
        assert _mixed_attn.decode_ms(trace, {"decode_steps_in_trace": 5},
                                     ctx, kind) is None
        assert _mixed_attn.decode_ms(xplane.Trace(), counters, ctx,
                                     kind) is None
        assert _mixed_attn.decode_ms(trace, counters, {"cell": {}},
                                     kind) is None


def test_the_rooflines_from_the_servers_rows():
    """3 ns and 5 ns a step in the made-up trace; the trace's own
    ``serve.decode`` spans hold 0.6 rows a step in each kind (the window
    layers' as a mean of 0.2 .. 1.0; the whole window's counters are not
    read): 0.6 rows of 2,048 bytes at 819 GB/s are 1.5 ns, half of the one
    and three tenths of the other."""
    trace, _ = _trace()
    counters = {"decode_steps_in_trace": 10, "kv_rows_window_per_step": 99.0,
                "kv_rows_full_per_step": 99.0}
    assert _mixed_attn.rows_per_step(trace, "window") == pytest.approx(0.6)
    least = 0.6 * 2048 / 819e9
    win = _reader("win_attn_roofline").compute(trace, None, counters, _ctx())
    full = _reader("full_attn_roofline").compute(trace, None, counters,
                                                 _ctx())
    assert win == pytest.approx(100.0 * least / 3e-9) and 49 < win < 51
    assert full == pytest.approx(100.0 * least / 5e-9) and 29 < full < 31
    assert _reader("win_attn_ms_per_decode_step").compute(
        trace, None, counters, _ctx()) == pytest.approx(3e-6)
    assert _reader("full_attn_ms_per_decode_step").compute(
        trace, None, counters, _ctx()) == pytest.approx(5e-6)


def test_the_prefills_flash_time_leaves_the_experts_kernel_out():
    trace, _ = _trace()
    got = _reader("flash_prefill_ms_per_request").compute(
        trace, None, {"decode_steps_in_trace": 10}, _ctx())
    assert got == pytest.approx(29e-6)      # 9 + 20 ns over one request


def test_the_pools_share_of_one_length():
    counters = {"state_bytes_kv": 4 * 2 ** 30,
                "state_bytes_ring": 3 * 2 ** 27}
    got = _reader("kv_pool_bytes_share").compute(None, None, counters,
                                                 _ctx())
    assert got == pytest.approx((4 + 0.375) / 16)
    # the ring lost: every layer T_max rows
    assert _reader("kv_pool_bytes_share").compute(
        None, None, {"state_bytes_kv": 16 * 2 ** 30}, _ctx()) == 1.0
    assert _reader("kv_pool_bytes_share").compute(None, None, {},
                                                  _ctx()) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_a_trace(name):
    """What a traced run without the new program gives them: no trace file
    of the cell, no counter of the new program. None, and nothing raised."""
    mod = _reader(name)
    trace, _ = _trace()
    assert mod.compute(xplane.Trace(), None, {}, _ctx()) is None
    assert mod.compute(trace, None, {"decode_steps_in_trace": 10},
                       {**_ctx(), "cell": {"server": _cell()["server"]}}) \
        is None


def test_benchmark_json_lists_the_cell_and_its_metrics():
    """By name: a later PR appends after these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "serve-mixed", 1)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tpot_p50_ms"
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert not listed & {"moe_roofline", "pool_attn_roofline",
                         "pool_attn_ms_per_decode_step",
                         "decode_attn_ms_per_step"}
    assert {"serve_tpot_p50_ms", "decode_step_ms", "moe_ms_per_decode_step",
            "kv_blocks_share", "unscoped_ms_per_decode_step"} <= listed
    assert len(bench["configs"]) == 8 and len(bench["workloads"]) == 12
