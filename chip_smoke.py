"""chip_smoke.py — the quickest proof that the system still starts on a TPU.

Drives the two main paths once, end to end, on the attached TPU, through
the entry points a user calls, at the full width of models the repo
supports (depth as configured, weights random from a seed):

  device       jax.devices(), versions, compile-cache directory; not a TPU
               => exit non-zero before anything is built
  train_lm     TransformerLM d512/L8/H8/vocab 8192, 16 x 1024 tokens,
               mixed_bf16, Pallas flash attention: per-step and fused-K
               training steps
  kernels      every Pallas flash entry point against the XLA attention op
               and its jax.grad, the serving decode kernel against the XLA
               op over the pool's slab, the routed experts against a loop
               (and their reached form against the dense one at Mellum2's
               widths) and the delta-rule decode step against kda_step,
               compiled, on the chip
  train_graph  ResNet-18 (ComputationGraph) through fit_epochs
  serve        DecodeServer on the d512/L8 LM, ragged prompts, checked
               against lm.generate
  mesh         (>= 4 devices) ParallelWrapper.fit_epochs over data=4 and
               DecodeServer on a 2x2 mesh, against the one-chip results

One process, no arguments needed; ``--phases a,b`` runs a subset (``device``
always runs). The phases are plain functions that take their sizes as
parameters so tests/test_chip_smoke.py can run them tiny on the CPU; the
script itself never runs small and never runs on the CPU. Times are
informational ("chip_smoke, not a benchmark").

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase makes the exit code non-zero.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import traceback

import numpy as np

PHASES = ("device", "train_lm", "kernels", "train_graph", "serve", "mesh")

# the full sizes; tests/test_chip_smoke.py passes small ones instead
LM_WIDTH = dict(vocab_size=8192, d_model=512, num_heads=8, num_layers=8,
                max_len=1024)  # bench.py's headline transformer
GRAPH_SIZES = dict(batch=256, image=32, n_batches=4, epochs=2)
SERVE_SIZES = dict(lm_kwargs=LM_WIDTH, slots=8, max_len=1024,
                   prompt_lens=(16, 40, 64, 100, 128, 200, 384, 512),
                   new_tokens=(32, 48, 64, 32, 48, 64, 32, 48))
# the names Pallas gives the three flash kernels in the lowered module
FLASH_KERNELS = ("_fwd_kernel", "_bwd_dkdv_kernel", "_bwd_dq_kernel")


def _timed(fn):
    """``(result, seconds)`` with the result's device work finished."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _rel_err(got, want) -> float:
    """max|got - want| over max|want|, in float32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_phase() -> dict:
    """Print what JAX is running on; exit non-zero unless it is a TPU.
    Nothing is built and no child process starts before that check."""
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={stamp['platform']} "
          f"device_kind={stamp['kind']!r} count={stamp['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    if stamp["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"platform={stamp['platform']!r}", file=sys.stderr)
        raise SystemExit(1)

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    host_lib = "built" if native.is_available() else "not built (no g++)"
    print(f"device: compile_cache={cache_dir} host_library={host_lib}",
          flush=True)
    return {"device": stamp, "compile_cache": cache_dir,
            "host_library": host_lib}


# ---------------------------------------------------------------------------
# train_lm
# ---------------------------------------------------------------------------
def train_lm_phase(*, lm_kwargs=LM_WIDTH, batch=16, steps=3, fused_k=2,
                   attn_impl="auto", expect_mosaic=True) -> dict:
    """A few ``fit_batch`` steps through ``make_train_step()``, then fused
    ``make_multi_train_step(fused_k)`` calls, on one fixed token batch."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerLM

    lm = TransformerLM(seed=0, dtype_policy="mixed_bf16",
                       attn_impl=attn_impl, **lm_kwargs).init()
    impl = lm._attn_impl(lm.max_len, train=True)
    assert impl == "flash", f"training attention resolved to {impl!r}"
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, lm.vocab_size, (batch, lm.max_len)), jnp.int32)

    step = lm.make_train_step()
    lowered = step.lower(lm.params, lm.opt_state, tokens,
                         jnp.asarray(0, jnp.int32)).as_text()
    mosaic_calls = lowered.count("tpu_custom_call")
    if expect_mosaic:
        # compiled Mosaic kernels: not interpreted, not the XLA path
        missing = [k for k in FLASH_KERNELS if k not in lowered]
        assert mosaic_calls >= 3 * lm.num_layers and not missing, (
            f"train step holds {mosaic_calls} Mosaic custom calls, "
            f"missing kernels {missing}")

    def per_step(n):
        return [lm.fit_batch(tokens, train_step=step, block=False)
                for _ in range(n)]

    def fused():
        return [lm.fit_batch_multi(tokens, multi_step=multi, k=fused_k,
                                   block=False)]

    losses, first_s = _timed(lambda: per_step(1))
    more, step_s = _timed(lambda: per_step(steps))
    multi = lm.make_multi_train_step(fused_k)
    more_fused, fused_first_s = _timed(fused)
    last, fused_s = _timed(fused)
    losses = [float(x) for x in losses + more + more_fused + last]
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert lm.step_count == 1 + steps + 2 * fused_k
    return {"first_call_s": first_s + fused_first_s,
            "steady_s": step_s + fused_s,
            "attn_impl": impl, "mosaic_custom_calls": mosaic_calls,
            "step_ms": 1e3 * step_s / steps,
            "fused_step_ms": 1e3 * fused_s / fused_k,
            "loss_first": losses[0], "loss_last": losses[-1]}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def kernels_phase(*, batch=4, heads=8, head_dim=64,
                  cases=((1024, None), (4096, 1024)), dtype="bfloat16",
                  decode=(8, 4096, 1024), decode_heads=((8, 2), (16, 16)),
                  moe=(2048, 1024, 64, 8, (8, 32, 512, 4096)),
                  reached=(2304, 896, 64, 8, 64, 12),
                  delta=(16, 32, 128), tol=2e-2, interpret=None) -> dict:
    """Flash forward, dk/dv and dq against ``dot_product_attention`` and its
    ``jax.grad``, for each ``(seq_len, window)`` case. ``interpret=None``
    is the library default: compiled by Mosaic on a TPU. The reference runs
    one batch row at a time (its [h, t, t] score matrix is the memory the
    kernel exists to avoid). Then the serving decode kernel, which reads
    one layer of a ``decode = (slots, t_max, window)`` KV pool in place,
    against ``grouped_query_attention`` over that layer's slab, for each
    ``(query heads, kv heads)`` of ``decode_heads`` (StarCoder2's 2 kv
    heads under a window; OLMoE's 16, full causal), and again with every
    other slot holding no request (``live``). Then the routed
    experts (``moe = (hidden, expert width, experts, per token, token
    counts)``: OLMoE's widths; 8 rows and a decode step's 32, a 512-token
    prompt and a 4,096-token one, so all three of ``routed_ffn``'s forms:
    reached, dense, sorted) against a masked loop over
    the experts, and past the dense form once more with a quarter of a
    four times wider router's experts held here, so that the sorted form
    runs in passes (``_moe_share_error``); and the reached form against the
    dense one (``reached = (hidden, expert width, experts, per token, rows,
    live rows)``: a decode step of Mellum2's 64 slots with 12 live, whose
    widths give the kernel blocks of 384 and 128 rows; ``_reached_error``).
    Both sides of that check feed
    the MXU bf16 operands and accumulate in float32; they differ in the
    order of the sum over
    experts and in where the weighted hidden state is rounded to bf16
    (2^-9 an element), which ``tol`` holds with room and a wrong expert,
    weight or dropped token does not. Last the delta-rule decode step
    (``delta = (slots, heads, head size)``: one layer of the two hybrid
    cells' state), a decay a head and a decay a channel, against
    ``kda.kda_step`` in float32 (``_delta_errors``)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import (
        dot_product_attention, grouped_query_attention)
    from deeplearning4j_tpu.pallas.decode_attention import (
        pool_decode_attention)
    from deeplearning4j_tpu.pallas.flash_attention import (
        flash_attention, flash_default_interpret)

    def out_and_grads(attn):
        def f(q, k, v, do):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(f)

    errors = {}
    first_s = steady_s = 0.0
    for t, window in cases:
        keys = jax.random.split(jax.random.PRNGKey(t), 4)
        q, k, v, do = (jax.random.normal(kk, (batch, t, heads, head_dim),
                                         jnp.dtype(dtype)) for kk in keys)
        flash = out_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=interpret))
        ref = out_and_grads(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, window=window))
        got, s = _timed(lambda: flash(q, k, v, do))
        first_s += s
        got, s = _timed(lambda: flash(q, k, v, do))
        steady_s += s
        rows = [ref(*(x[i:i + 1] for x in (q, k, v, do)))
                for i in range(batch)]
        want = [jnp.concatenate(parts) for parts in zip(*rows)]
        tag = f"t{t}" + ("" if window is None else f"_w{window}")
        for name, g, w in zip(("fwd", "dq", "dk", "dv"), got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), (
                f"{tag} {name}: non-finite values")
            errors[f"{tag}_{name}"] = _rel_err(g, w)

    # two layers of a pool of 128-wide kv heads, slots at position 0, at
    # the pool's end and spread between
    slots, t_max, window = decode
    for n, (h, hkv) in enumerate(decode_heads):
        win = window if hkv < h else None
        keys = jax.random.split(jax.random.PRNGKey(t_max + n), 3)
        pool_k, pool_v = (
            jax.random.normal(kk, (2, slots, t_max, hkv, 128),
                              jnp.dtype(dtype)) for kk in keys[:2])
        q = jax.random.normal(keys[2], (slots, 1, h, 128), jnp.dtype(dtype))
        positions = (jnp.arange(slots) * (t_max - 1) // (slots - 1))[:, None]
        live = jnp.arange(t_max)[None, None, :] <= positions[:, :, None]
        if win is not None:
            live &= (jnp.arange(t_max)[None, None, :]
                     > positions[:, :, None] - win)
        kernel = jax.jit(lambda q, k, v, p, win=win: pool_decode_attention(
            q, k, v, 1, p, window=win,
            interpret=flash_default_interpret() if interpret is None
            else interpret))
        got, s = _timed(lambda: kernel(q, pool_k, pool_v, positions))
        first_s += s
        got, s = _timed(lambda: kernel(q, pool_k, pool_v, positions))
        steady_s += s
        want = jax.jit(lambda q, k, v, live=live: grouped_query_attention(
            q, k[1], v[1], mask=live))(q, pool_k, pool_v)
        assert got.shape == want.shape and got.dtype == want.dtype
        tag = f"decode_t{t_max}_kv{hkv}" + ("" if win is None
                                             else f"_w{win}")
        errors[tag] = _rel_err(got, want)
        # every other slot holds no request: its keys (NaN) are not read
        # and its rows are zeros; the live rows do not change by a bit
        dead = jnp.arange(slots) % 2 == 1
        nan = jnp.where(dead[None, :, None, None, None], jnp.nan, 0)
        some = jax.jit(lambda q, k, v, p, win=win: pool_decode_attention(
            q, k, v, 1, p, window=win, live=~dead,
            interpret=flash_default_interpret() if interpret is None
            else interpret))(q, pool_k + nan.astype(pool_k.dtype),
                             pool_v + nan.astype(pool_v.dtype), positions)
        assert bool(jnp.all(jnp.where(dead[:, None, None, None],
                                      some == 0, some == got))), (
            f"{tag}: live rows moved or dead rows were read")
    errors.update(_moe_errors(*moe, dtype=jnp.dtype(dtype)))
    errors.update(_reached_error(
        *reached, dtype=jnp.dtype(dtype),
        interpret=flash_default_interpret() if interpret is None
        else interpret))
    errors.update(_delta_errors(*delta, interpret=interpret))
    bad = {k: e for k, e in errors.items() if not e <= tol}
    assert not bad, f"kernels off the XLA op beyond {tol}: {bad}"
    return {"first_call_s": first_s, "steady_s": steady_s,
            "rel_err": {k: round(e, 5) for k, e in errors.items()}}


def _delta_errors(slots, heads, dim, *, interpret):
    """``pallas/delta_step.py`` with every other slot owing no token, against
    ``kda.kda_step`` with those rows' ``g`` and ``beta`` zero, both float32;
    ``{tag: rel_err}`` over output and state together. A dead slot's state
    (NaN here) is not read: its bits stay and its output is zero."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import kda
    from deeplearning4j_tpu.pallas.delta_step import delta_step

    errors = {}
    live = jnp.arange(slots) % 2 == 0
    for tag, width in (("delta_head", 1), ("delta_channel", dim)):
        ks = jax.random.split(jax.random.PRNGKey(width), 6)
        q = kda.l2norm(jax.random.normal(ks[0], (slots, heads, dim)))
        k = kda.l2norm(jax.random.normal(ks[1], (slots, heads, dim)))
        v = jax.random.normal(ks[2], (slots, heads, dim))
        g = -3.0 * jax.random.uniform(ks[3], (slots, heads, width))
        beta = jax.random.uniform(ks[4], (slots, heads))
        state = jax.random.normal(ks[5], (slots, heads, dim, dim))
        gm, bm = kda.mask_dead(g[:, None], beta[:, None], live[:, None])
        want = jax.jit(kda.kda_step)(q, k, v, gm[:, 0], bm[:, 0], state)
        nan = jnp.where(live[:, None, None, None], state, jnp.nan)
        o, s = jax.jit(functools.partial(delta_step, interpret=interpret))(
            q, k, v, g, beta, nan, live)
        rows = np.asarray(live)
        o, s = np.asarray(o), np.asarray(s)
        assert np.isnan(s[~rows]).all() and not o[~rows].any(), (
            f"{tag}: a dead slot's state was read or moved")
        errors[tag] = max(_rel_err(o[rows], np.asarray(want[0])[rows]),
                          _rel_err(s[rows], np.asarray(want[1])[rows]))
    return errors


def _moe_errors(d_model, d_ff, n_experts, per_token, token_counts, *, dtype):
    """``routed_ffn`` against a masked loop over the experts, float32
    weights cast to ``dtype`` per use on both sides; ``{tag: rel_err}``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deeplearning4j_tpu.models import routed_experts

    key = jax.random.PRNGKey(d_model)
    p = jax.jit(lambda k: routed_experts.init_experts(
        k, d_model, d_ff, n_experts, jnp.float32))(key)

    def cast(w):
        return w.astype(dtype)

    def dot(a, b):
        return jnp.dot(a, cast(b), preferred_element_type=jnp.float32)

    # the weights are arguments: a jitted closure would bake 1.6 GB of
    # constants into each program; ``first``: the router's index of the
    # first expert held
    @functools.partial(jax.jit, static_argnames="first")
    def loop(x, p, first=0):
        w, e = routed_experts.route(x, p["router"], per_token)

        def one(i, acc):
            wi = jnp.sum(jnp.where(e - first == i, w, 0.0), -1, keepdims=True)
            hid = jax.nn.silu(dot(x, p["w_gate"][i])) * dot(x, p["w_up"][i])
            return acc + wi * dot(hid.astype(dtype), p["w_down"][i])

        return lax.fori_loop(0, p["w_gate"].shape[0], one,
                             jnp.zeros(x.shape, jnp.float32))

    ffn = jax.jit(lambda x, p, first=0: routed_experts.routed_ffn(
        x, p, experts_per_token=per_token, cast=cast, first=first),
        static_argnames="first")
    errors = {}
    for n in token_counts:
        x = jax.random.normal(jax.random.fold_in(key, n), (n, d_model),
                              jnp.float32).astype(dtype)
        errors[f"moe_n{n}"] = _rel_err(ffn(x, p)[0], loop(x, p).astype(dtype))
        if n > routed_experts.DENSE_MAX_TOKENS:
            errors[f"moe_share_n{n}"] = _moe_share_error(
                x, p, per_token, ffn, loop)
    return errors


def _reached_error(d_model, d_ff, n_experts, per_token, rows, n_live, *,
                   dtype, interpret):
    """``pallas/reached_experts.py`` on float32 matrices as stored against
    ``_dense_experts`` on their ``dtype`` casts, ``n_live`` of ``rows`` rows
    live: ``{tag: rel_err}``, the tag naming the kernel's blocks. A dead row
    comes back zero and an expert no live row chose is not fetched."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import routed_experts
    from deeplearning4j_tpu.pallas import reached_experts as kernel

    key = jax.random.PRNGKey(d_model + rows)
    p = jax.jit(lambda k: routed_experts.init_experts(
        k, d_model, d_ff, n_experts, jnp.float32))(key)
    blocks = kernel.expert_blocks(rows, d_model, d_ff, jnp.float32)
    assert blocks is not None, (rows, d_model, d_ff)
    x = jax.random.normal(jax.random.fold_in(key, 1), (rows, d_model),
                          jnp.float32).astype(dtype)
    live = np.zeros((rows,), bool)
    live[np.random.default_rng(rows).permutation(rows)[:n_live]] = True
    live = jnp.asarray(live)

    @jax.jit
    def both(x, p, live):
        w, e = routed_experts.route(x, p["router"], per_token)
        w = jnp.where(live[:, None], w, 0.0)
        load = routed_experts._load(live, e, n_experts)
        mats = (p["w_gate"], p["w_up"], p["w_down"])
        dense = routed_experts._dense_experts(
            x, w, e, *(m.astype(dtype) for m in mats))
        got = kernel.reached_experts(
            x, routed_experts._combine(w, e, n_experts), load, *mats,
            blocks=blocks, interpret=interpret)
        return got, dense, load

    got, dense, load = both(x, p, live)
    assert 0 < int(jnp.sum(load > 0)) < n_experts, "nothing to skip"
    assert not bool(jnp.any(got[~live])), "a dead row is not zero"
    return {f"reached_n{rows}_b{blocks[0]}x{blocks[1]}":
            _rel_err(got, dense)}


def _moe_share_error(x, p, per_token, ffn, loop, share=4, keep=64):
    """The sorted form where this chip holds one in ``share`` of the
    router's experts (the second run of them), so that it runs in passes
    over the pairs held here: against the masked loop over the held experts
    (``_moe_errors``' two programs), and the first ``keep`` rows bit for bit
    whatever the others choose -- re-drawn, or all following the row that
    chose most experts held here, which takes several passes."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import routed_experts

    n, d = x.shape
    held = p["w_gate"].shape[0]
    p = dict(p, router=jax.random.normal(
        jax.random.PRNGKey(share), (d, share * held), jnp.float32) * 0.05)
    assert routed_experts._pass_rows(
        n, per_token, held, share * held, False) < n * per_token
    base, info = ffn(x, p, first=held)
    local = info["experts"] - held
    leader = jnp.argmax(jnp.sum((local >= 0) & (local < held), axis=1))
    redrawn = jax.random.normal(jax.random.PRNGKey(keep), x.shape,
                                jnp.float32).astype(x.dtype)
    runs = [int(info["run"])]
    for others in (redrawn, jnp.broadcast_to(x[leader], x.shape)):
        some, info = ffn(x.at[keep:].set(others[keep:]), p, first=held)
        assert bool(jnp.all(some[:keep] == base[:keep])), (
            "sorted experts in passes: a row's bits moved with the other "
            "rows' routing")
        runs.append(int(info["run"]))
    assert runs[2] > runs[0] > 0, f"the crowd took no more passes: {runs}"
    assert bool(jnp.all(ffn(x, p, first=held)[0] == base)), (
        "not the same bits twice")
    return _rel_err(base, loop(x, p, first=held).astype(x.dtype))


# ---------------------------------------------------------------------------
# train_graph (and the mesh phase's data-parallel half)
# ---------------------------------------------------------------------------
def _fit_resnet(*, batch, image, n_batches, epochs, mesh=None):
    """ResNet-18 through ``fit_epochs`` on seeded synthetic data; over
    ``mesh`` through ``ParallelWrapper``. Returns the net, the ``[E, N]``
    history of the first run, its seconds, a second run's seconds, and
    the dataset cache (mesh runs only)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import resnet18

    rng = np.random.default_rng(0)
    n = batch * n_batches
    ds = DataSet(rng.random((n, image, image, 3), np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)])
    net = resnet18(num_classes=10, dtype_policy="bf16").init()
    cache = None
    if mesh is None:
        run = lambda: net.fit_epochs(ListDataSetIterator(ds, batch), epochs)
    else:
        from deeplearning4j_tpu.parallel import ParallelWrapper

        wrapper = ParallelWrapper(net, mesh=mesh)
        cache = wrapper.build_epoch_cache(ListDataSetIterator(ds, batch))
        assert cache is not None, "dataset exceeded the per-shard budget"
        run = lambda: wrapper.fit_epochs(cache, epochs)
    hist, first_s = _timed(run)
    # None means a fallback (streaming / per-step) ran instead of the
    # fused epoch program
    assert hist is not None, "fit_epochs fell back off the fused path"
    hist = np.asarray(hist, np.float32)
    assert hist.shape == (epochs, n_batches), hist.shape
    assert np.all(np.isfinite(hist)), f"non-finite loss history: {hist}"
    assert net._train_dispatches == 1, net._train_dispatches
    _, steady_s = _timed(run)
    return net, hist, first_s, steady_s, cache


def train_graph_phase(**sizes) -> dict:
    """The DL4J path: a ComputationGraph trained by one fused program."""
    _, hist, first_s, steady_s, _ = _fit_resnet(**(sizes or GRAPH_SIZES))
    return {"first_call_s": first_s, "steady_s": steady_s,
            "history": hist,
            "loss_first": float(hist[0, 0]), "loss_last": float(hist[-1, -1])}


# ---------------------------------------------------------------------------
# serve (and the mesh phase's tensor-parallel half)
# ---------------------------------------------------------------------------
def _serve(*, lm_kwargs, slots, max_len, prompt_lens, new_tokens, mesh=None):
    """Two passes of ragged requests through a fresh ``DecodeServer``: the
    first compiles the decode program and one prefill per ladder rung, the
    second (other lengths, same rungs) must build nothing. Returns the
    server, the first pass's requests, and both passes' seconds."""
    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.serving import DecodeServer

    lm = TransformerLM(seed=0, dtype_policy="bf16", **lm_kwargs).init()
    server = DecodeServer(lm, slots=slots, max_len=max_len, mesh=mesh)
    rng = np.random.default_rng(0)

    def one_pass(shrink):
        reqs = [server.submit(
            rng.integers(1, lm.vocab_size, n - shrink).astype(np.int32), m)
            for n, m in zip(prompt_lens, new_tokens)]
        server.drain()
        return reqs

    reqs, first_s = _timed(lambda: one_pass(0))
    builds = server.engine.program_builds
    again, steady_s = _timed(lambda: one_pass(1))
    for r in reqs + again:
        assert r.state == "finished", (r.state, len(r.tokens))
        assert len(r.tokens) == r.max_new_tokens
    assert server.engine.program_builds == builds, (
        f"program builds grew after the first pass over the prompt "
        f"ladder: {builds} -> {server.engine.program_builds}")
    return lm, server, reqs, first_s, steady_s


def _assert_same_greedy(lm, got, want, what, tie_tol=2.0 ** -5):
    """Two greedy decodes of one prompt (``prompt + generated`` each) must
    be the same tokens. Two different bf16 programs may round a near-tie
    between the top two logits differently, after which the sequences
    rightly part ways — so the FIRST differing position is allowed if a
    teacher-forced ``lm.forward`` over the shared prefix scores both
    candidates within ``tie_tol`` x max|logit| of the best logit. Returns
    ``None`` when equal, else ``{"at", "gap"}`` for the summary."""
    import jax.numpy as jnp

    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    differ = np.flatnonzero(got != want)
    if differ.size == 0:
        return None
    at = int(differ[0])
    logits = np.asarray(lm.forward(
        lm.params, jnp.asarray(want[None, :at], jnp.int32))[0, -1],
        np.float32)
    scale = float(np.max(np.abs(logits)))
    gap = float(np.max(logits) - min(logits[got[at]], logits[want[at]]))
    assert gap <= tie_tol * scale, (
        f"{what}: differ at position {at} ({got[at]} vs {want[at]}) and "
        f"it is no near-tie: logit gap {gap:.4f} vs scale {scale:.4f}\n"
        f"{got}\n{want}")
    return {"at": at, "gap": round(gap / scale, 5)}


def serve_phase(**sizes) -> dict:
    """Continuous batching on one device, one request checked token for
    token against ``lm.generate``."""
    lm, server, reqs, first_s, steady_s = _serve(**(sizes or SERVE_SIZES))
    probe = reqs[0]
    want, generate_s = _timed(lambda: lm.generate(
        np.asarray(probe.prompt, np.int32)[None], probe.max_new_tokens))
    tie = _assert_same_greedy(lm, probe.output, np.asarray(want)[0],
                              "served tokens vs lm.generate")
    return {"first_call_s": first_s + generate_s, "steady_s": steady_s,
            "requests": 2 * len(reqs),
            "program_builds": server.engine.program_builds,
            "near_tie": tie, "outputs": [r.output for r in reqs]}


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------
def _devices_of(x) -> set:
    return {s.device for s in x.addressable_shards}


def mesh_phase(*, graph_sizes=GRAPH_SIZES, serve_sizes=SERVE_SIZES,
               graph_history=None, serve_outputs=None, hist_tol=5e-2) -> dict:
    """Data-parallel ``fit_epochs`` over ``data=4`` and ``DecodeServer`` on
    a 2x2 mesh, each against its one-chip run (taken from the earlier
    phases when they ran, repeated here otherwise)."""
    import jax

    from deeplearning4j_tpu.parallel import build_mesh
    from deeplearning4j_tpu.parallel.mesh import MeshSpec
    from deeplearning4j_tpu.parallel.sharding_registry import parse_mesh_shape

    devices = jax.devices()[:4]

    # ---- data parallel training
    if graph_history is None:
        graph_history = _fit_resnet(**graph_sizes)[1]
    net, hist, dp_first_s, dp_steady_s, cache = _fit_resnet(
        mesh=build_mesh(MeshSpec(data=4), devices=devices), **graph_sizes)
    for stack in cache.features + cache.labels:
        assert _devices_of(stack) == set(devices), (
            f"batch stack on {len(_devices_of(stack))} device(s)")
    for leaf in jax.tree_util.tree_leaves(net.params):
        assert _devices_of(leaf) == set(devices), "params not on all 4"
    hist_err = _rel_err(hist, graph_history)
    assert hist_err <= hist_tol, (
        f"data=4 loss history off the one-chip history by {hist_err}:\n"
        f"{hist}\n{graph_history}")

    # ---- tensor parallel serving
    if serve_outputs is None:
        serve_outputs = [r.output for r in _serve(**serve_sizes)[2]]
    _, server, reqs, tp_first_s, tp_steady_s = _serve(
        mesh=build_mesh(parse_mesh_shape("2x2"), devices=devices),
        **serve_sizes)
    assert server.stats()["kv_shards"] == 2, server.stats()["kv_shards"]
    pool = server.engine.cache
    assert len(_devices_of(pool.k)) == 4 and len(_devices_of(pool.v)) == 4
    assert len({s.index for s in pool.k.addressable_shards}) == 2, (
        "KV pool is not split in two over the model axis")
    from deeplearning4j_tpu.models.transformer import TransformerLM

    ref_lm = TransformerLM(seed=0, dtype_policy="bf16",
                           **serve_sizes["lm_kwargs"]).init()
    ties = [_assert_same_greedy(ref_lm, r.output, want,
                                f"2x2-mesh vs one-chip server, request {i}")
            for i, (r, want) in enumerate(zip(reqs, serve_outputs))]
    return {"first_call_s": dp_first_s + tp_first_s,
            "steady_s": dp_steady_s + tp_steady_s,
            "dp_history_rel_err": round(hist_err, 5),
            "kv_shards": 2, "devices": len(devices),
            "near_ties": [t for t in ties if t]}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset of: " + ", ".join(PHASES[1:]))
    chosen = [p.strip() for p in parser.parse_args(argv).phases.split(",")
              if p.strip()]
    unknown = sorted(set(chosen) - set(PHASES))
    if unknown:
        parser.error(f"unknown phase(s) {unknown}")

    t_start = time.perf_counter()
    stamp = device_phase()["device"]
    import jax

    results = {}

    def mesh():
        if jax.device_count() < 4:
            return {"not_run": f"{jax.device_count()} device(s)"}
        return mesh_phase(
            graph_history=results.get("train_graph", {}).get("history"),
            serve_outputs=results.get("serve", {}).get("outputs"))

    phases = {"train_lm": train_lm_phase, "kernels": kernels_phase,
              "train_graph": train_graph_phase, "serve": serve_phase,
              "mesh": mesh}
    failed = []
    for name in PHASES[1:]:
        if name not in chosen:
            continue
        print(f"{name}: running", flush=True)
        t0 = time.perf_counter()
        try:
            results[name] = phases[name]()
        except Exception:
            # recorded, reported below, and the exit code is non-zero
            failed.append(name)
            print(f"{name}: FAILED after {time.perf_counter() - t0:.1f}s\n"
                  f"{traceback.format_exc()}", flush=True)

    print(f"summary: platform={stamp['platform']} "
          f"device_kind={stamp['kind']!r} count={stamp['count']} "
          f"wall_s={time.perf_counter() - t_start:.1f}")
    for name in PHASES[1:]:
        if name in failed:
            print(f"  {name}: FAILED")
        elif name in results and "not_run" in results[name]:
            print(f"  {name}: not run, {results[name]['not_run']}")
        elif name in results:
            r = results[name]
            extra = {k: v for k, v in r.items() if k not in (
                "first_call_s", "steady_s", "history", "outputs")}
            print(f"  {name}: ok first_call_s={r['first_call_s']:.1f} "
                  f"steady_s={r['steady_s']:.2f} {json.dumps(extra)}")
        else:
            print(f"  {name}: not selected")
    print(json.dumps({"ok": not failed, "device": stamp,
                      **({"failed": failed} if failed else {})}),
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
