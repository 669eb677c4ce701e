"""The cell PR 35 adds rehearses end to end, its configuration is the catalog
row but for the cut and counts the parameters ISSUE 35 names, ``lib/mtp_cost``
agrees with hand counts, its readers find the ``mtp`` and ``mla.attend`` ops of
the round program in a small made-up trace, and both controls of its check
fail. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mtp_cells.py -q
"""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import mtp_cost, peaks, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "gigachat-serve-assist"
READERS = ["mtp_ms_per_round", "spec_tokens_per_round",
           "mla_verify_roofline"]


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
    # every decode dispatch is a round, the rounds' drafts are judged, and
    # the pool holds the module's layer (6 of 4 slots x 64 x 128 lanes x 4 B)
    rounds = int(r.stdout.split("mtp: rounds=")[1].split()[0])
    assert rounds > 0 and "drafts_judged=" in r.stdout
    assert f"state_bytes_latent={6 * 4 * 64 * 128 * 4}" in r.stdout
    if not trace:
        assert "setup_s.rehearsal" in last["metrics"]
        assert "serve_tpot_p50_ms.rehearsal" in last["metrics"]
        return
    # a CPU trace has no device plane: the counter metrics are there, the
    # device-trace ones are left out and nothing raises
    for name in ("serve_ttft_p95_ms", "routed_pairs_here_per_token",
                 "moe_load_max_over_mean", "spec_tokens_per_round"):
        assert name + ".rehearsal" in last["metrics"]
    assert 1.0 <= last["metrics"]["spec_tokens_per_round.rehearsal"][
        "value"] < 1.2
    assert "mla_verify_roofline.rehearsal" not in last["metrics"]


@pytest.mark.parametrize("control", ["float8", "module_off"])
def test_the_controls_fail_the_cell(control):
    """The lower readings of the cell's limits: with the reference's weights
    rounded to float8 e4m3 the tokens and the drafts fall beyond the near
    tie; with the hidden-state half of the reference module's input zeroed
    the tokens still pass and the drafts do not."""
    r = _run("benchmarks/tools/float8_reference_mtp.py", control,
             "--workload", CELL, "--seed", "11", "--seconds", "2", "--trace",
             "0", "--rehearse")
    last = _last(r)
    assert last["correct"] is False and last["failed"] == 0, r.stdout[-3000:]
    drafts = [x for x in r.stdout.splitlines() if "drafts_judged" in x]
    assert drafts and any("drafts_beyond_near_tie=0" not in x for x in drafts)
    if control == "module_off":     # by the module, and by nothing before it
        tokens = [x for x in r.stdout.splitlines() if " judged=" in x]
        assert tokens and all(" beyond_near_tie=0 " in x for x in tokens)


def test_the_plain_step_serves_the_same_trace_without_a_module():
    r = _run("benchmarks/tools/float8_reference_mtp.py", "plain",
             "--workload", CELL, "--seed", "11", "--seconds", "2", "--trace",
             "0", "--rehearse")
    last = _last(r)
    assert last["correct"] is True and "mtp: rounds=0 " in r.stdout
    assert f"state_bytes_latent={5 * 4 * 64 * 128 * 4}" in r.stdout


def test_knee_tool_sweeps_the_cell():
    r = _run("benchmarks/tools/find_knee_dsa.py", "--workload", CELL,
             "--rates", "10", "--seconds", "1", "--seeds", "0", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert rows[0]["offered"] == rows[0]["finished"] == 40
    assert "knee_rate_per_s" in rows[-1]


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gigachat3.1-702b-a36b-l5.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    """Every width as published; the four reduced keys and nothing else
    differ from the catalog's config (where the catalog is at hand)."""
    cfg = _config()
    reduced = {"num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == reduced
    entry = next(c for c in _bench()["configs"] if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == reduced
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 8, 16032, 1)
    assert cfg["kept_layers"] == [2, 3, 4, 5, 6]
    assert cfg["published"] == {
        "num_hidden_layers": 64, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 128256,
        "num_nextn_predict_layers": 1}
    assert cfg["share"]["chips_per_layer"] == 32
    assert cfg["share"]["held"] * 32 == 256 and cfg["vocab_size"] * 8 == 128256
    for key in ("mtp_input_order", "mtp_hidden_state", "mtp_loss_weight",
                "rope_interleave", "rope_scaling", "storage", "weights"):
        assert key in cfg["assumed"], key
    assert not any(any(w in key for w in ("_dim", "_rank", "hidden_size",
                                          "intermediate_size", "per_tok"))
                   for key in reduced)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GigaChat3.1-702B-A36B")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == reduced
    assert cfg["source"] == entry["source"] == row["source_url"]


def test_the_cut_counts_the_parameters_of_the_issue():
    """3,515 M parameters, 14.06 GB in float32, from the program's own tree
    (abstract: nothing is allocated); 7,680 B of cache a position."""
    import jax

    from benchmarks.drivers import lm_serve_mtp as drv
    from deeplearning4j_tpu.serving import kv_cache

    lm = drv.build_lm(_config(), policy="bf16", seed=0, max_len=4096)
    shapes = jax.eval_shape(lambda: type(lm)(**lm.get_config()).init().params)

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    def millions(tree):
        return round(count(tree) / 1e6, 1)

    assert millions(shapes["blocks"][0]["mla"]) == 132.6
    assert millions(shapes["blocks"][0]["glu"]) == 396.4
    assert millions(shapes["blocks"][1]) == 530.8
    assert millions(shapes["mtp"]["block"]) == 530.8
    assert millions(shapes["mtp"]["proj"]) == 102.8
    assert millions([shapes["embed"], shapes["head"]]) == 229.8
    total = count(shapes)
    assert total // 10 ** 6 == 3515 and round(4 * total / 1e9, 2) == 14.06
    assert kv_cache.kv_pool_nbytes(lm, 1, 1, "bfloat16") == 7680
    assert kv_cache.kv_pool_nbytes(lm, 16, 4096, "bfloat16") == 503316480
    assert lm.mla["softmax_mult"] == pytest.approx(2.0047, abs=1e-4)


def test_mtp_cost_against_hand_counts():
    cfg = _config()
    assert mtp_cost.attention_layers(cfg) == 6
    assert mtp_cost.latent_row_lanes(cfg) == 640      # 576 used
    # 8 live slots at a cursor of 2,000: two queries each, six layers
    flops = mtp_cost.verify_attend_flops(cfg, context=2000, live=8)
    absorb = 2 * 64 * 512 * (128 + 192) * 2
    attend = 2 * 64 * 2001.5 * (2 * 512 + 64) * 2
    assert flops == 6 * 8 * (absorb + attend)
    assert round(flops / 1e9, 1) == 28.8
    got = mtp_cost.verify_attend_bytes(cfg, context=2000, live=8)
    wukv = 4 * 512 * 64 * (128 + 192)
    rows = 8 * 2002 * 640 * 2
    io = 8 * 2 * 2 * 64 * (128 + 64 + 192)
    assert got == 6 * (rows + wukv + io)
    assert round(got / 1e6, 1) == 379.4      # 123.0 of rows, 251.7 of wukv
    # memory-bound on a v5e at these sizes; the floor is the larger
    pk = peaks.peaks_for("TPU v5 lite")
    floor = mtp_cost.verify_attend_floor_s(cfg, pk, context=2000, live=8)
    assert floor == got / 819e9 > flops / 197e12
    assert round(1e3 * floor, 3) == 0.463
    # sixteen slots deep in their contexts come near the balance: 0.56 ms of
    # FLOP against 0.92 ms of bytes, a third of them wukv in float32
    deep = dict(context=4000, live=16)
    assert round(1e3 * mtp_cost.verify_attend_flops(cfg, **deep) / 197e12,
                 2) == 0.56
    assert round(1e3 * mtp_cost.verify_attend_floor_s(cfg, pk, **deep),
                 2) == 0.92


def _trace():
    """Two programs called jit__unknown: (7) runs ten times (the round) with
    3 us under mla.attend, 2 us under mtp/mla.attend, 1 us under mtp.proj,
    1 us under mtp/lm.head and 7 us unscoped each; (9) twice (a prefill's
    blocks) with 10 us under mla.attend each."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 60))
        ops += [ev("%fusion.1 = f32[16,64,2,4096]", t, 3),
                ev("%fusion.2 = f32[16,64,2,4096]", t + 3, 2),
                ev("%fusion.3 = bf16[16,2,7168]", t + 6, 1),
                ev("%fusion.4 = f32[16,16032]", t + 8, 1),
                ev("%fusion.5 = bf16[16,7168]", t + 20, 7)]
    for i in range(2):
        t = 3000 + 100 * i
        mods.append(ev("jit__unknown(9)", t, 80))
        ops.append(ev("%fusion.7 = f32[1,64,128,4096]", t + 40, 10))
    host = [ev("bench.trace_window", 900, 2400),
            ev("dl4j.serve.prefill", 2990, 200)]
    scopes = {0: {
        "%fusion.1 = f32[16,64,2,4096]": "jit(_unknown)/mla.attend/dot",
        "%fusion.2 = f32[16,64,2,4096]": "jit(_unknown)/mtp/mla.attend/dot",
        "%fusion.3 = bf16[16,2,7168]": "jit(_unknown)/mtp.proj/dot_general",
        "%fusion.4 = f32[16,16032]": "jit(_unknown)/mtp/lm.head/dot_general",
        "%fusion.5 = bf16[16,7168]": "jit(_unknown)/mul",
        "%fusion.7 = f32[1,64,128,4096]": "jit(_unknown)/mla.attend/dot"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def _ctx():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    return {"cell": cell, "config": _config(), "device_kind": "TPU v5 lite",
            "chips": 1}


def test_mtp_readers_on_a_made_up_trace():
    trace, scopes = _trace()
    ctx, counters = _ctx(), {"decode_steps_in_trace": 10}
    # everything of the module: its own attention, M, its pass through the head
    assert _hybrid.decode_ms(trace, counters, ctx, "mtp", scopes) \
        == pytest.approx(4e-6)
    # the layers' attention and the module's, and no prefill block's
    assert _hybrid.decode_ms(trace, counters, ctx, "mla_attend", scopes) \
        == pytest.approx(5e-6)
    rx = re.compile(ctx["cell"]["mtp_scopes"])
    assert not rx.search("jit(_unknown)/mla.attend/dot")
    assert not rx.search("jit(_unknown)/xmtp/dot")
    assert rx.search("jit(_unknown)/mtp.embed/gather")
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _hybrid.decode_ms(trace, counters, ctx, "mtp", bare) is None


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
    if name == "spec_tokens_per_round":
        assert mod.compute(trace, None, {"spec_rounds": 200,
                                         "spec_emitted": 203}, _ctx()) == 1.015


def test_the_readers_are_the_benchmarks():
    """Looked up by name: later PRs append after them."""
    bench = _bench()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        mod, m = _reader(name), listed[name]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gigachat3.1-702b-a36b-l5", "serve-assist", 1)
    assert len(cell["why"]) <= 200
    tpot = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tpot_p50_ms")
    assert CELL in tpot["workloads"]
    for name in ("decode_step_ms", "mla_ms_per_decode_step",
                 "moe_ms_per_decode_step", "live_slots_per_step"):
        assert CELL in listed[name]["workloads"]


def test_the_compile_rehearsal_reads_the_cells_sizes():
    """``compile_rehearsal_mtp.py`` builds the model of the workload file:
    its report names the weights and the pool before anything compiles (the
    compiles themselves take minutes and are run by hand)."""
    r = subprocess.run(
        [sys.executable, "compile_rehearsal_mtp.py", "none"],
        cwd=os.path.join(ROOT, "benchmarks", "tools"),
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT},
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "compile rehearsal (gigachat mtp)" in r.stdout
