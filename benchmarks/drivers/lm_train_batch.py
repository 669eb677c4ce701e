"""Driver ``lm_train_batch``: ``lm_train``'s window, with the first loss held
to the plain reference over the **whole** first batch.

``lm_train`` compares the step's first loss, a mean over every sequence of
the batch, with the reference on sequence 0 alone. At batch 1 that is the same
quantity; at batch 4 the two differ by the spread of the loss between
sequences (per mille), which no limit tight enough to see a precision fault
can admit. Here the reference runs once a sequence and the means are of the
same positions, so the limit is about rounding alone, as ``sc2-train-8k``'s.

The path, the batch, the clock and ``train_mfu`` are ``lm_train``'s (see its
docstring): ``make_train_step()`` once, ``fit_batch`` per step on a fresh
batch made on the device from the seed, the median step.

Workload file keys: those of ``lm_train``.
"""

from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp

from benchmarks.drivers import _lm_common as common
from benchmarks.lib import flops, peaks, reference_lm
from benchmarks.lib.outcome import Outcome


def run(ctx) -> Outcome:
    cfg, tr = ctx.config, ctx.cell["train"]
    batch, t = int(tr["batch"]), int(tr["seq_len"])
    lm = common.build_lm(cfg, policy=tr["policy"], seed=ctx.seed, max_len=t,
                         lr=float(tr["lr"]), remat=bool(tr.get("remat")),
                         attn_impl=tr["attn_impl"])
    lm.params, init = common.make_params(lm, ctx.seed)
    lm.opt_state = common.make_adam_state(lm)

    vocab, skew = lm.vocab_size, float(tr["token_skew"])
    data_key = jax.random.fold_in(jax.random.PRNGKey(ctx.seed), 0xDA7A)

    @jax.jit
    def make_batch(i):
        u = jax.random.uniform(jax.random.fold_in(data_key, i), (batch, t))
        return jnp.minimum((vocab * u ** skew).astype(jnp.int32), vocab - 1)

    step = lm.make_train_step()
    tokens0 = make_batch(0)
    with ctx.spans.span("warmup"):
        first_loss = lm.fit_batch(tokens0, train_step=step)
        lm.fit_batch(make_batch(1), train_step=step)

    losses, i = [], 2
    t0 = ctx.begin_window()
    while time.monotonic() - t0 < ctx.seconds:
        with ctx.spans.span("lm_step"):
            losses.append(lm.fit_batch(make_batch(i), train_step=step))
        i += 1
        ctx.tick()
    ctx.end_window()
    elapsed = ctx.t_window_end - t0

    step_s = ctx.before_trace("lm_step")
    median_s = statistics.median(step_s)
    per_step = batch * t
    peak = (1.0 if ctx.rehearse else
            peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops"])
    flops_tok = flops.lm_train_flops_per_token(cfg, t)
    mfu = 100.0 * per_step / median_s * flops_tok / peak

    # ---- correct: the first loss against the plain reference on the same
    # weights (made again from the seed, once the trained state is freed),
    # every sequence of the batch: equal lengths, so the mean of the
    # sequences' means is the mean over all positions
    lm.params = lm.opt_state = None
    gc.collect()
    params = init(jax.random.PRNGKey(ctx.seed))
    ref_each = [float(reference_lm.mean_nll(params, tokens0[b], cfg))
                for b in range(batch)]
    ref_loss = statistics.fmean(ref_each)
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    finite = all(x == x and abs(x) != float("inf")
                 for x in [first_loss] + losses)
    tail = statistics.fmean(losses[-3:])
    ok = finite and rel <= float(ctx.cell["check"]["loss_rel_tol"]) \
        and tail < first_loss
    return Outcome(
        correct=ok, attempted=len(losses),
        failed=sum(1 for x in losses if x != x),
        end_to_end={"train_mfu": mfu},
        counters={"steps": len(losses), "median_step_ms": 1e3 * median_s,
                  "items_per_step": per_step,
                  "flops_per_item": flops_tok},
        notes=[f"train: attention={tr['attn_impl']} steps={len(losses)} "
               f"tokens_per_s_mean={len(losses) * per_step / elapsed:.1f} "
               f"tokens_per_s_median_step={per_step / median_s:.1f} "
               f"step_ms_median={1e3 * median_s:.3f} "
               f"step_ms_min={1e3 * min(step_s):.3f} "
               f"step_ms_max={1e3 * max(step_s):.3f}",
               f"check: first_loss={first_loss:.6f} "
               f"reference_loss={ref_loss:.6f} (mean of {batch} sequences, "
               f"spread {max(ref_each) - min(ref_each):.2e}) "
               f"rel_diff={rel:.2e} "
               f"tol={ctx.cell['check']['loss_rel_tol']} "
               f"loss_last3_mean={tail:.6f} finite={finite}"])
