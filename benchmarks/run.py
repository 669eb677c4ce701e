"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: pin the program's environment, load, warm up the cell's own
shapes (all of that is ``setup_s``), measure for ``--seconds``, check the
outputs, print one JSON object as the last line. ``--trace 0`` reports the
cell's end-to-end metrics with the profiler off; ``--trace 1`` is a run of
its own that traces a few seconds in the middle of the window and reports the
cell's per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.

Everything that belongs to one cell, one configuration, one kind of run or one
per-layer metric is a file found by name:

    BENCHMARK.json                        the lists: metrics, configs, cells
    benchmarks/workloads/<cell>.json      config, driver, chips, traffic, checks
    benchmarks/configs/<config>.json      the model configuration as run
    benchmarks/drivers/<driver>.py        run(ctx) -> Outcome for a kind of run
    benchmarks/layer_metrics/<metric>.py  compute(trace, spans, counters, cell)

so a later PR adds files and entries and edits nothing that is here.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result. ``--rehearse`` (never passed by the driver) runs
the cell's ``rehearsal`` sizes on whatever JAX finds, to test the control flow;
its numbers carry the suffix ``.rehearsal`` and are no device metrics.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Context:
    """What the harness hands a driver."""

    def __init__(self, args, cell, config):
        from benchmarks.lib.spans import Spans

        self.workload = args.workload
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.cell = cell
        self.config = config
        self.spans = Spans()
        self.devices = None                   # set by main() after the check
        self.compile_times: List[float] = []  # clock at each compile request
        self.t_window: Optional[float] = None
        self.t_window_end: Optional[float] = None
        self.trace_dir = os.path.join(ROOT, ".bench_out",
                                      f"trace-{args.workload}")
        self._trace_state = "off"             # off -> on -> done
        self.trace_t0 = float("inf")          # clock when the profiler started
        self._window_ann = None
        trace_len = float(cell.get("trace_seconds", 4.0))
        self._trace_from = min(0.4 * self.seconds,
                               max(0.0, self.seconds - trace_len))
        self._trace_to = self._trace_from + trace_len

    @property
    def trace_state(self) -> str:
        """"off" before the profiler starts, "on" while it runs, "done"."""
        return self._trace_state

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    # ---- the measured window -------------------------------------------
    def begin_window(self) -> float:
        """Set-up is over: every shape is warm. Returns the clock."""
        self.t_window = time.monotonic()
        return self.t_window

    def end_window(self) -> None:
        self.t_window_end = time.monotonic()
        self._stop_trace()
        # before the correctness check frees and allocates: what set-up and
        # the window needed
        self.memory_at_window_end = [d.memory_stats() or {}
                                     for d in self.devices]

    def tick(self) -> None:
        """Drivers call this between steps inside the window; a traced run
        turns the profiler on and off from here."""
        if not self.trace or self._trace_state == "done":
            return
        elapsed = time.monotonic() - self.t_window
        if self._trace_state == "off" and elapsed >= self._trace_from:
            self._start_trace()
        elif self._trace_state == "on" and elapsed >= self._trace_to:
            self._stop_trace()

    def _start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        self.trace_t0 = time.monotonic()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.spans.annotate = True
        self._window_ann = jax.profiler.TraceAnnotation("bench.trace_window")
        self._window_ann.__enter__()
        self._trace_state = "on"

    def _stop_trace(self) -> None:
        if self._trace_state != "on":
            return
        import jax

        self._window_ann.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self._trace_state = "done"

    def before_trace(self, name: str) -> List[float]:
        """Durations of the spans called ``name`` that ended before the
        profiler started (all of them in an untraced run): starting and
        stopping the profiler stalls the host, and host-clock per-layer
        numbers should not carry that."""
        return [b - a for n, a, b in self.spans.records
                if n == name and b <= self.trace_t0]

    def compiles_in_window(self) -> int:
        hi = self.t_window_end if self.t_window_end is not None \
            else float("inf")
        return sum(1 for t in self.compile_times if self.t_window <= t <= hi)


# --------------------------------------------------------------------------
def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks/{kind}/{name}.py not found")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _metrics_for(entries, workload):
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def _pin_environment(cell) -> Dict[str, str]:
    """Every ``DL4J_*`` variable selects a code path by hand; clear them all
    and set only what the workload file names."""
    for k in [k for k in os.environ if k.startswith("DL4J_")]:
        del os.environ[k]
    pinned = {str(k): str(v) for k, v in cell.get("env", {}).items()}
    os.environ.update(pinned)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return pinned


def _apply_rehearsal(cell, config):
    over = cell.get("rehearsal") or {}
    config = {**config, **over.get("config", {})}
    cell = {**cell, **over.get("cell", {})}
    if "traffic" in over:
        cell["traffic"] = {**cell.get("traffic", {}), **over["traffic"]}
    return cell, config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = _load_json(os.path.join(HERE, "workloads", args.workload + ".json"))
    for k in ("config", "traffic", "chips"):
        have = cell["traffic_name"] if k == "traffic" else cell[k]
        if have != entry[k]:
            raise SystemExit(f"{args.workload}: {k} is {have!r} in the "
                             f"workload file, {entry[k]!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    if args.rehearse:
        cell, config = _apply_rehearsal(cell, config)
    pinned = _pin_environment(cell)

    # ---- the device, before anything is built --------------------------
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform == "tpu":
            raise SystemExit("--rehearse is for machines without a TPU")
    elif platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found platform={platform!r}",
              file=sys.stderr)
        return 3
    chips = int(cell["chips"])
    if len(devices) < chips:
        print(f"benchmark: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3

    ctx = Context(args, cell, config)
    ctx.devices = devices[:chips]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: ctx.compile_times.append(time.monotonic())
        if event == COMPILE_EVENT else None)

    from deeplearning4j_tpu.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    ctx.log(f"cell {args.workload}: config={cell['config']} "
            f"driver={cell['driver']} chips={chips} seed={args.seed} "
            f"seconds={args.seconds} trace={args.trace} "
            f"compile_cache={cache_dir}")

    driver = _load_module("drivers", cell["driver"])
    outcome = driver.run(ctx)        # a benchmarks.lib.outcome.Outcome
    if ctx.t_window is None or ctx.t_window_end is None:
        raise SystemExit(f"driver {cell['driver']} never marked its window")
    setup_s = ctx.t_window - T_PROCESS_START
    compiles = ctx.compiles_in_window()
    ctx.spans.count("compiles_in_window", compiles)
    ctx.spans.counters.update(outcome.counters)
    correct = bool(outcome.correct) and compiles == 0
    if compiles:
        outcome.notes.append(
            f"VOID: {compiles} compile request(s) inside the window")

    # ---- the device stamp ----------------------------------------------
    # this runtime keeps a program's temporaries in a region it reserves
    # beside the allocator's buffers ("bytes_reserved"); the chip's peak is
    # both (PERF.md section 2)
    peak = max((int(st.get("peak_bytes_in_use", 0))
                + int(st.get("peak_bytes_reserved", 0))
                for st in ctx.memory_at_window_end), default=0)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    ctx.spans.count("memory_peak_bytes", peak)
    ctx.log("memory_stats_at_window_end " + json.dumps(
        ctx.memory_at_window_end[0], sort_keys=True, default=str))

    suffix = ".rehearsal" if args.rehearse else ""
    result = {"correct": correct, "attempted": int(outcome.attempted),
              "failed": int(outcome.failed), "metrics": {}, "device": device}
    if not args.trace:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        for m in _metrics_for(bench["end_to_end"], args.workload):
            if m["name"] not in values:
                raise SystemExit(f"driver {cell['driver']} gave no "
                                 f"{m['name']} for {args.workload}")
            result["metrics"][m["name"] + suffix] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        from benchmarks.lib import xplane

        path = xplane.find_xplane(ctx.trace_dir)
        trace = xplane.load_xplane(path) if path else xplane.Trace()
        busy_s, window_s = xplane.busy_and_window(trace)
        device["busy_s"], device["window_s"] = busy_s, window_s
        for m in _metrics_for(bench["per_layer"], args.workload):
            reader = _load_module("layer_metrics", m["name"])
            value = reader.compute(trace, ctx.spans, ctx.spans.counters,
                                   {"cell": cell, "config": config,
                                    "device_kind": devices[0].device_kind,
                                    "chips": chips})
            if value is not None:
                result["metrics"][m["name"] + suffix] = {
                    "value": float(value), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": xplane.top_ops(trace),
                               "idle_gaps": xplane.top_idle_gaps(trace)}

    ctx.log(f"setup: setup_s={setup_s:.3f} "
            f"window_s={ctx.t_window_end - ctx.t_window:.3f}")
    for note in outcome.notes:
        ctx.log(note)
    ctx.log("counters " + json.dumps(ctx.spans.counters, sort_keys=True))
    ctx.log("environment " + json.dumps(pinned, sort_keys=True))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
