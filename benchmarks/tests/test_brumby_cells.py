"""The cell PR 47 adds rehearses end to end, its controls fail it,
``lib/retention_cost`` agrees with hand counts, and its readers find the
``ret.*`` ops of the right program in a small made-up trace and take the live
slots from that trace's own ``serve.decode`` spans. Run by hand (not part of
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_brumby_cells.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import retention_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "brumby-serve-continue", "brumby-14b-l4"
NEW = ["ret_ms_per_decode_step", "ret_roofline", "ret_prefill_ms_per_request",
       "ret_scan_roofline"]


def _run(script, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def _last(r):
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _cfg():
    return _load("benchmarks", "configs", CONFIG + ".json")


def _cell():
    return _load("benchmarks", "workloads", CELL + ".json")


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
    for name in ("serve_ttft_p95_ms", "live_slots_per_step"):
        assert name + ".rehearsal" in last["metrics"]
    for name in NEW:
        assert name + ".rehearsal" not in last["metrics"]
    assert "kv_rows_per_step=0 " in r.stdout
    assert "state_bytes_recurrent=" in r.stdout
    assert "state_bytes_kv=0" in r.stdout


@pytest.mark.parametrize("control", ["float8", "no_decay", "p4", "softmax",
                                     "bf16_state"])
def test_each_control_runs_the_cell(control):
    """The controls run through the harness. At the rehearsal's width (64,
    a limit of 0.5) only some of them can fail the cell; what each reads at
    the real size is the workload file's ``check.reason``."""
    r = _run("benchmarks/tools/float8_reference_brumby.py", control,
             "--workload", CELL, "--seed", "11", "--seconds", "2", "--trace",
             "0", "--rehearse")
    last = _last(r)
    assert last["failed"] == 0, r.stdout[-3000:]
    assert "float8_reference_brumby:" in r.stdout
    if control in ("no_decay", "softmax"):
        assert last["correct"] is False, r.stdout[-3000:]


def test_the_knee_tool_with_the_cells_own_limits_sweeps_it():
    r = _run("benchmarks/tools/find_knee_dsa.py", "--workload", CELL,
             "--rates", "30,10", "--seconds", "1", "--seeds", "0",
             "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert rows[0]["offered"] == rows[0]["finished"] == 40
    assert rows[1]["skipped"] and "knee_rate_per_s" in rows[-1]


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the two reduced keys and nothing else
    differ from the catalog's config (where the catalog is at hand)."""
    cfg = _cfg()
    assert set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 37984)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 151936}
    assert cfg["share"]["vocab_slices"] == 4 and cfg["vocab_size"] * 4 == (
        151936)
    assert cfg["kept_layers"] == [0, 1, 2, 3]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"]) == (5120, 40, 8, 128, 17408)
    assert (cfg["rms_norm_eps"], cfg["rope_theta"]) == (1e-6, 1e6)
    assert (cfg["power"], cfg["state_rows"]) == (2, 8704)
    for key in ("weight_storage", "power", "gate", "gate_seeding",
                "head_norms_and_rope", "scale", "ret_eps", "state_rows"):
        assert key in cfg["assumed"], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]


def test_the_program_stores_the_rows_the_configuration_states():
    from deeplearning4j_tpu.models import ret

    cfg = _cfg()
    assert ret.state_rows(cfg["head_dim"]) == cfg["state_rows"]
    assert ret.CHUNK == _cell()["ret_chunk"]
    assert ret.EPS == cfg["ret_eps"]


def test_the_cell_is_the_issues():
    cell = _cell()
    sv, tr = cell["server"], cell["traffic"]
    assert (sv["slots"], sv["max_len"], sv["policy"]) == (32, 32768, "bf16")
    assert sv["buckets"][-8:] == [1024, 2048, 4096, 8192, 12288, 16384,
                                  20480, 28672]
    assert min(sv["buckets"]) <= tr["prompt_tokens"]["min"]
    assert tr["prompt_tokens"] == {"median": 2048, "sigma": 1.0, "min": 256,
                                   "max": 28672}
    assert tr["output_tokens"] == {"median": 512, "sigma": 0.6, "min": 128,
                                   "max": 1024}
    assert tr["max_total_tokens"] == 30720 and tr["schedule_seed"] == 0
    assert tr["limits"] == {k: v for k, v in {
        "ttft_s": 1.0, "ttft_s_per_1k_prompt": 0.3, "tpot_s": 0.1,
        "why": tr["limits"]["why"]}.items()}
    rows = tr["arrivals"]["sweep"]
    held = {r["rate_per_s"] for r in rows if not r.get("skipped")
            and all(x["sustained"] for x in rows
                    if x["rate_per_s"] == r["rate_per_s"])}
    assert rows and tr["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * max(held), abs=0.051)
    assert cell["chips"] == 1 and cell["loop"]["cut_at_seconds"] is False
    assert cell["driver"] == "lm_serve_retention"


def test_retention_cost_against_hand_counts():
    cfg = _cfg()
    # one slot, one layer: S is 8 x 8,704 x 128 float32, Z 8 x 128 x 128
    assert retention_cost.slot_state_bytes(cfg) == 35651584 + 524288
    # read once and written once: 72.4 MB a step; at the symmetric power's
    # own 8,256 rows 68.7 (ISSUE 47's 68.2 keeps z as [8, 8256])
    assert round(2 * retention_cost.slot_state_bytes(cfg) / 1e6, 1) == 72.4
    assert round(2 * retention_cost.slot_state_bytes(cfg, 8256) / 1e6,
                 1) == 68.7
    # q in and o out at 40 heads, k, v and the gate's row at 8, of 128 floats
    assert retention_cost.step_operand_bytes(cfg) == (80 + 24) * 128 * 4
    got = retention_cost.step_bytes(cfg, live=20)
    assert got == 4 * 20 * (2 * 36175872 + 53248)
    assert round(got / 1e9, 2) == 5.79
    # a prompt token in one layer: 48 heads' [1, 8704] x [8704, 128]
    # products, the normaliser's, and 128.5 keys a query inside a chunk
    state = 2 * 48 * 8704 * 128
    norm = 40 * (2 * 128 * 128 + 2 * 128) + 8 * 2 * 128 * 128
    inside = 40 * 2 * 256 * 257 / 2
    assert retention_cost.scan_token_flops(cfg, 256) == state + norm + inside
    assert round(retention_cost.scan_token_flops(cfg, 256) / 1e6) == 111
    assert retention_cost.scan_seconds(
        cfg, tokens=1000, chunk=256, flops_per_s=197e12) == pytest.approx(
            4 * 1000 * (state + norm + inside) / 197e12)


KERNEL = ('%{}.{} = f32[32,8,5,128]{{3,2,1,0}} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (decode), each
    time with the step kernel for 6 ns (named by its scope), 1 ns of XLA ops
    under ret.step, 2 ns under ret.proj and 3 ns of feed-forward; (9) once
    (prefill) with 40 ns under ret.scan and 5 under ret.proj. The host's
    serve.decode spans say 4, 6, 0 (a span that only reads) and 8 slots'
    state moved. Returns the trace and the ``{device: {op name: tf_op}}`` a
    trace file would give."""
    def ev(name, start, dur, **stats):
        e = xplane.Event(name, float(start), float(dur))
        e.stats.update(stats)
        return e

    step = KERNEL.format("ret.step", 3)
    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev("%fusion.1 = f32[32,8,1,128]", t, 1), ev(step, t + 2, 6),
                ev("%fusion.2 = bf16[32,5120]", t + 10, 2),
                ev("%fusion.4 = bf16[32,17408]", t + 20, 3)]
    mods.append(ev("jit__unknown(9)", 3000, 80))
    ops += [ev("%while.6 = f32[1,8,8704,128]", 3010, 40),
            ev("%fusion.7 = bf16[1,4096,5120]", 3055, 5)]
    host = [ev("bench.trace_window", 900, 2300),
            ev("dl4j.serve.prefill", 2990, 100, prompt_len=2000)]
    host += [ev("dl4j.serve.decode", 1000 + 300 * i, 50, live=n,
                **({"state_slots": n, "kv_rows": 0} if n else {}))
             for i, n in enumerate((4, 6, 0, 8))]
    scopes = {0: {
        "%fusion.1 = f32[32,8,1,128]": "jit(_unknown)/ret.step/exp",
        step: "jit(_unknown)/ret.step/jit(_retention_step)/pallas_call",
        "%fusion.2 = bf16[32,5120]": "jit(_unknown)/ret.proj/dot_general",
        "%fusion.4 = bf16[32,17408]": "jit(_unknown)/ffn.dense/dot_general",
        "%while.6 = f32[1,8,8704,128]":
            "jit(_unknown)/ret.scan/while",
        "%fusion.7 = bf16[1,4096,5120]":
            "jit(_unknown)/ret.proj/dot_general"}}
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


def test_ret_readers_on_a_made_up_trace():
    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    assert _hybrid.decode_ms(trace, counters, ctx, "ret", scopes) \
        == pytest.approx(9e-6)              # 1 + 6 + 2 ns a step
    assert _hybrid.decode_ms(trace, counters, ctx, "ret_step", scopes) \
        == pytest.approx(7e-6)
    found = _hybrid.scoped(trace, counters, ctx, "ret", scopes)
    assert found["prefill"] == (pytest.approx(45e-9), 1)
    only_scan = _hybrid.scoped(trace, counters, ctx, "ret_scan", scopes)
    assert only_scan["prefill"] == (pytest.approx(40e-9), 1)
    assert only_scan["decode"][0] == 0
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _hybrid.decode_ms(trace, counters, ctx, "ret", bare) is None


def test_the_roofline_takes_its_live_slots_from_the_traces_own_spans():
    """The spans that dispatched moved 4, 6 and 8 slots' state: 6 on
    average, whatever the run's counters say of the whole window."""
    trace, _ = _trace()
    mod = _reader("ret_roofline")
    assert mod.traced_live(trace) == pytest.approx(6.0)
    assert mod.traced_live(xplane.Trace()) is None
    want = retention_cost.step_bytes(_cfg(), live=6.0) / 819e9
    assert want == pytest.approx(4 * 6 * (2 * 36175872 + 53248) / 819e9)


def test_a_prefills_share_of_the_peak_counts_its_own_tokens():
    """40 ns under ret.scan for a prompt of 2,000 tokens: the least time is
    4 layers x 2,000 tokens x 111 MFLOP over 197 TFLOP/s."""
    cfg = _cfg()
    least = retention_cost.scan_seconds(cfg, tokens=2000, chunk=256,
                                        flops_per_s=197e12)
    assert least == pytest.approx(4 * 2000 * retention_cost.scan_token_flops(
        cfg, 256) / 197e12)
    assert 4.4e-3 < least < 4.6e-3


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_a_trace(name):
    """What the parent commit's traced run gives them: no trace file of the
    cell, no span of the new program. None, and nothing raised."""
    mod = _reader(name)
    trace, _ = _trace()
    assert mod.compute(xplane.Trace(), None, {}, _ctx()) is None
    assert mod.compute(trace, None, {"decode_steps_in_trace": 10},
                       {**_ctx(), "cell": {}}) is None


def test_benchmark_json_lists_the_cell_and_its_metrics_by_name():
    bench = _load("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert len(cells) == 13 and len(configs) == 9
    assert sum(1 for w in cells.values() if w["chips"] == 4) == 1
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "serve-continue", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    assert configs[CONFIG]["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert configs[CONFIG]["file"] == "benchmarks/configs/" + CONFIG + ".json"
    assert len(configs[CONFIG]["why"]) <= 200
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["layer"] == "linear attention"
        assert m["moves"] == "serve_tpot_p50_ms"
        assert m["unit"] == ("%" if name.endswith("roofline") else "ms")
    joined = ["decode_step_ms", "prefill_busy_pct", "gen_late_p95_ms",
              "serve_ttft_p50_ms", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
              "admit_idle_pct", "admit_ms_per_step",
              "admit_programs_per_request", "step_host_idle_pct",
              "queue_wait_p50_ms", "live_slots_per_step",
              "ffn_dense_ms_per_decode_step", "head_ms_per_decode_step",
              "unscoped_ms_per_decode_step"]
    for name in joined:
        assert metrics[name]["workloads"][-1] == CELL, name
    tpot = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tpot_p50_ms")
    assert CELL in tpot["workloads"] and tpot["bound"] == 0.035
    # none that reads a pool, a ring, latent rows or experts
    for name, m in metrics.items():
        if name not in joined + NEW and "workloads" in m:
            assert CELL not in m["workloads"], name
