"""The harness is driven by data: a cell, a configuration, a kind of run and a
per-layer metric dropped in as new files and new entries are found with no edit
to any file that was there. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DUMMY_DRIVER = '''
import time
from benchmarks.lib.outcome import Outcome

def run(ctx):
    import jax, jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((ctx.config["size"], ctx.config["size"]))
    f(x).block_until_ready()
    t0 = ctx.begin_window()
    n = 0
    while time.monotonic() - t0 < ctx.seconds:
        with ctx.spans.span("dummy_step"):
            f(x).block_until_ready()
        n += 1
        ctx.tick()
    ctx.end_window()
    return Outcome(correct=True, attempted=n, failed=0,
                   end_to_end={"dummy_rate": n / ctx.seconds},
                   counters={"dummy_steps": n}, notes=[f"dummy: steps={n}"])
'''
DUMMY_METRIC = '''
NAME, UNIT, LAYER, MOVES = "dummy_steps_seen", "count", "dummy", "dummy_rate"

def compute(trace, spans, counters, ctx):
    return counters.get("dummy_steps")
'''
EMPTY_METRIC = '''
def compute(trace, spans, counters, ctx):
    return None            # nothing to read: the harness leaves it out
'''


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark with one more cell, configuration, driver and
    two per-layer metrics -- added files and added entries only."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file()}
    b = tmp_path / "benchmarks"
    (b / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (b / "layer_metrics" / "dummy_steps_seen.py").write_text(DUMMY_METRIC)
    (b / "layer_metrics" / "dummy_nothing.py").write_text(EMPTY_METRIC)
    (b / "configs" / "dummy.json").write_text(json.dumps({"size": 8}))
    (b / "workloads" / "dummy-cell.json").write_text(json.dumps({
        "config": "dummy", "traffic_name": "dummy-traffic", "driver": "dummy",
        "chips": 1, "trace_seconds": 0.2,
        "env": {"DL4J_NAN_GUARD": "off"}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dummy", "source": "none",
                             "file": "benchmarks/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy",
                               "traffic": "dummy-traffic", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    for name in ("dummy_steps_seen", "dummy_nothing"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "dummy",
            "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def _run(checkout, *args, program=True, env=None):
    e = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    e["JAX_PLATFORMS"] = "cpu"
    e["DL4J_SERVE_SLOTS"] = "3"          # must be cleared by the harness
    if program:
        e["PYTHONPATH"] = ROOT
    e.update(env or {})
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=checkout, env=e,
        capture_output=True, text=True, timeout=300)


def test_dummy_cell_is_found_and_last_line_has_the_contract_keys(checkout):
    r = _run(checkout, "--workload", "dummy-cell", "--seed", "3",
             "--seconds", "0.5", "--trace", "0", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is True and last["attempted"] > 0
    # a CPU rehearsal never prints a number under a device metric's name
    assert set(last["metrics"]) == {"setup_s.rehearsal", "dummy_rate.rehearsal"}
    assert all(set(m) == {"value", "unit"} for m in last["metrics"].values())
    # the last-but-one line: the DL4J_* variables in force -- the caller's
    # were cleared, the workload file's were set
    assert lines[-2] == 'environment {"DL4J_NAN_GUARD": "off"}'


def test_traced_run_reports_per_layer_metrics_and_breakdown(checkout):
    r = _run(checkout, "--workload", "dummy-cell", "--seed", "3",
             "--seconds", "1", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert {"busy_s", "window_s"} <= set(last["device"])
    assert last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    # the reader that found nothing is left out; compiles_in_window moves
    # setup_s, which every cell reports
    assert set(last["metrics"]) == {"dummy_steps_seen.rehearsal",
                                    "compiles_in_window.rehearsal"}
    assert last["metrics"]["compiles_in_window.rehearsal"]["value"] == 0


def test_no_tpu_means_no_result(checkout):
    r = _run(checkout, "--workload", "dummy-cell", "--seed", "3",
             "--seconds", "0.5", "--trace", "0")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_without_the_program_there_is_no_result(checkout):
    r = _run(checkout, "--workload", "dummy-cell", "--seed", "3",
             "--seconds", "0.5", "--trace", "0", "--rehearse", program=False)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


@pytest.mark.parametrize("cell,flags", [
    ("sc2-train-8k", {}),
    ("sc2-serve-steady", {}),
    ("sc2-serve-saturated", {}),
    ("resnet18-dp4",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
])
def test_every_cell_rehearses_end_to_end(cell, flags):
    r = _run(ROOT, "--workload", cell, "--seed", "5", "--seconds", "2",
             "--trace", "0", "--rehearse", env=flags)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, r.stdout[-2000:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert "setup_s.rehearsal" in last["metrics"] and len(last["metrics"]) >= 2
