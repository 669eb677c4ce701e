"""Share of their roofline the flash kernels reach, all three together: the
least time the chip could take for every call seen in the trace
(``lib/flops.flash_kernel_cost`` from the cell's shapes over the peaks) divided
by the device time those calls took. The kernels are compute bound at these
shapes; the bound of each kind is printed by ``tools/trace_report.py``."""

from benchmarks.layer_metrics import _flash
from benchmarks.lib import flops, peaks

NAME, UNIT, LAYER, MOVES = ("flash_roofline", "%", "attention kernels",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    evs = _flash.kernel_events(trace, ctx)
    if not evs or not any(evs.values()):
        return None
    cfg, tr = ctx["config"], ctx["cell"]["train"]
    pk = peaks.peaks_for(ctx["device_kind"])
    least = took = 0.0
    for kind, events in evs.items():
        f, b = flops.flash_kernel_cost(
            kind, batch=tr["batch"], heads=cfg["num_attention_heads"],
            t=tr["seq_len"], head_dim=flops.lm_head_dim(cfg),
            window=cfg.get("sliding_window"))
        least += len(events) * flops.roofline_seconds(f, b, pk)[0]
        took += sum(e.dur for e in events) / 1e9
    return 100.0 * least / took if took else None
