"""The two cells PR 25 adds rehearse end to end, ``lib/moe_cost`` agrees with
a hand count, and the routed-experts readers find the ``moe.*`` ops of the
right program in a small made-up trace. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_moe_cells.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.layer_metrics import _moe
from benchmarks.lib import moe_cost, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, "benchmarks/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell,trace", [
    ("olmoe-serve-chat", 0), ("olmoe-serve-chat", 1), ("sc2-train-2k", 0)])
def test_new_cells_rehearse_end_to_end(cell, trace):
    r = _run("--workload", cell, "--seed", "2147483659", "--seconds", "2",
             "--trace", str(trace), "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, r.stdout[-3000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    if trace:
        # a CPU trace has no device plane: the counter metric is there, the
        # device-trace ones are left out and nothing raises
        assert "moe_load_max_over_mean.rehearsal" in last["metrics"]
        assert "moe_roofline.rehearsal" not in last["metrics"]
    else:
        assert "setup_s.rehearsal" in last["metrics"]
        assert len(last["metrics"]) >= 2
    if cell == "olmoe-serve-chat":
        assert "check: routing flipped_share=" in r.stdout


def test_float8_reference_rounds_like_the_cast_and_fails_the_cell():
    """The control that sets the lower reading of a limit: its arithmetic
    rounding is the float8 e4m3 cast's, and with it the cell's check fails."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.tools import float8_reference as f8

    rng = np.random.default_rng(0)
    x = jnp.asarray(np.concatenate([rng.normal(size=4096) * 0.02,
                                    rng.normal(size=512) * 3.0,
                                    [0.0, 1e-5, -448.0]]), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(f8.e4m3(x)),
        np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.float32)))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable, "benchmarks/tools/float8_reference.py", "--workload",
         "olmoe-serve-chat", "--seed", "11", "--seconds", "2", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0, r.stdout[-3000:]


def test_knee_tool_rehearses():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable, "benchmarks/tools/find_knee_moe.py", "--rates",
         "10", "--seconds", "1", "--seeds", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert rows[0]["offered"] == rows[0]["finished"] == 10
    assert "knee_rate_per_s" in rows[-1]


OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 4}


def test_moe_cost_against_a_hand_count():
    # one expert: 3 matrices of 2048 x 1024 float32
    assert moe_cost.expert_bytes(OLMOE, 4) == 3 * 2048 * 1024 * 4 == 25165824
    # a full decode step: all 4 x 64 experts reached by 32 rows a layer
    got = moe_cost.routed_step_bytes(OLMOE, tokens=32, touched=256)
    experts = 256 * 25165824                       # 6,442,450,944
    routers = 4 * 2048 * 64 * 4                    # 2,097,152
    rows = 4 * 2 * 32 * 2048 * 2                   # 1,048,576: in and out
    assert got == experts + routers + rows == 6445596672
    # half the experts reached, bf16 storage
    assert moe_cost.routed_step_bytes(
        OLMOE, tokens=8, touched=128, param_bytes=2) == (
            128 * 12582912 + 4 * (2048 * 64 * 2 + 2 * 8 * 2048 * 2))
    # the algorithm's work: router + 8 experts x 3 projections a row
    per_row = 2 * 2048 * 64 + 8 * 3 * 2 * 2048 * 1024
    assert moe_cost.routed_step_flops(OLMOE, tokens=32) == 4 * 32 * per_row
    # memory bound at a decode step's 32 rows on a v5e, by a wide margin
    assert got / 819e9 > 20 * moe_cost.routed_step_flops(
        OLMOE, tokens=32) / 197e12


def _encode(fields):
    """A protocol-buffer message from ``[(field, value)]``: ints as varints,
    bytes and str length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    msg = b""
    for field, value in fields:
        if isinstance(value, int):
            msg += varint(field << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            msg += varint(field << 3 | 2) + varint(len(value)) + value
    return msg


def test_xplane_meta_reads_the_stats_kept_once_per_kind_of_op(tmp_path):
    from benchmarks.lib import xplane_meta

    def entry(key, value):
        return _encode([(1, key), (2, value)])

    stat_names = {1: "tf_op", 2: "hlo_category", 3: "jit(_unknown)/moe.route/top_k"}
    ops = {10: ("%fusion.1 = f32[8]", [(1, (5, "jit(_unknown)/moe.experts/mul")),
                                       (2, (5, "loop fusion"))]),
           11: ("%sort.2 = f32[8,64]", [(1, (7, 3))]),      # a ref value
           12: ("%copy.3 = f32[8]", [(2, (5, "copy"))])}    # no tf_op
    plane = [(1, 7), (2, "/device:TPU:0"),
             (3, _encode([(2, "XLA Ops"), (4, b"\x08\x0a" * 1000)]))]
    plane += [(5, entry(k, _encode([(1, k), (2, v)])))
              for k, v in stat_names.items()]
    plane += [(4, entry(k, _encode(
        [(1, k), (2, name)] + [(5, _encode([(1, sid), value]))
                               for sid, value in stats])))
        for k, (name, stats) in ops.items()]
    host = [(2, "/host:CPU"), (4, entry(1, _encode([(2, "ignored")])))]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_encode([(1, _encode(plane)), (1, _encode(host))]))
    assert xplane_meta.op_metadata(str(path)) == {0: {
        "%fusion.1 = f32[8]": {"tf_op": "jit(_unknown)/moe.experts/mul"},
        "%sort.2 = f32[8,64]": {"tf_op": "jit(_unknown)/moe.route/top_k"}}}
    assert xplane_meta.op_metadata(str(path), ("hlo_category",))[0][
        "%copy.3 = f32[8]"] == {"hlo_category": "copy"}


def _trace():
    """Two programs called jit__unknown: (7) runs ten times with 2 us of
    moe ops each (one nested in another), (9) once with 9 us. Returns the
    trace and the ``{device: {op name: tf_op}}`` a trace file would give."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 50))
        ops += [ev("%fusion.1 = bf16[32,64]", t, 10),
                ev("%while.2 = f32[8]", t + 10, 2),
                ev("%convolution.3 = f32[64,1024,32]", t + 10, 1.5)]
    mods.append(ev("jit__unknown(9)", 3000, 80))
    ops.append(ev("%ragged-dot-none = f32[4096,1024]", 3010, 5))
    ops.append(ev("%fusion.9 = f32[64]", 3020, 4))
    host = [ev("bench.trace_window", 900, 2300)]
    scopes = {0: {
        "%fusion.1 = bf16[32,64]": "jit(_unknown)/mul",
        "%while.2 = f32[8]": "jit(_unknown)/moe.experts/while",
        "%convolution.3 = f32[64,1024,32]":
            "jit(_unknown)/moe.experts/nd,edf->enf/dot_general",
        "%ragged-dot-none = f32[4096,1024]": "jit(_unknown)/moe.experts/ragged_dot",
        "%fusion.9 = f32[64]": "jit(_unknown)/moe.route/top_k"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), scopes


def test_scoped_seconds_tells_decode_from_prefill():
    ctx = {"cell": {"moe_scopes": r"moe\.(route|experts)",
                    "config": "olmoe-1b-7b-l4", "traffic_name": "serve-chat",
                    "decode_program": {"module": "^jit__unknown$",
                                       "runs": "decode_steps_in_trace"},
                    "prefill_program": {"module": "^jit__unknown$",
                                        "except": "decode_program"}}}
    trace, scopes = _trace()
    counters = {"decode_steps_in_trace": 10}
    got = _moe.scoped_seconds(trace, counters, ctx, scopes)
    assert got["decode"] == (pytest.approx(10 * 2e-9), 10)
    assert got["prefill"] == (pytest.approx(9e-9), 1)
    # a program without the scopes (the parent commit), a cell without the
    # key, a run that left no trace file: nothing to read, nothing raised
    bare = {0: {op: "jit(_unknown)/mul" for op in scopes[0]}}
    assert _moe.scoped_seconds(trace, counters, ctx, bare) is None
    assert _moe.scoped_seconds(trace, counters, {"cell": {}}, scopes) is None
    assert _moe.scoped_seconds(trace, counters, ctx) is None
