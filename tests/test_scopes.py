"""The scope vocabulary (``deeplearning4j_tpu/scopes.py``) on the lowered
programs of ``TransformerLM`` and the serving engine, and the readers that
split a device trace by it (``benchmarks/layer_metrics/_scopes.py``).

(a) every matmul of the train step, a prefill program and the decode program
carries a name of the vocabulary; (b) none is open where the attention core
or a Pallas kernel is called, so the kernels keep their names in a trace;
(c) the scopes are metadata: the lowered text without locations does not
change when they are taken away; (d) the readers on a made-up trace.
"""

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax._src import source_info_util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from lowered_paths import op_paths  # noqa: E402  (tests/ is on the path)

from benchmarks.layer_metrics import _scopes  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402
from deeplearning4j_tpu import scopes  # noqa: E402
from deeplearning4j_tpu.models import transformer  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.pallas import decode_attention  # noqa: E402
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402

SLOTS, MAX_LEN, BUCKET = 3, 64, 16
NAMES = _scopes.name_pattern(scopes.SCOPES)


def _dense(**over):
    kw = dict(vocab_size=128, d_model=32, num_heads=4, num_kv_heads=2,
              num_layers=2, d_ff=64, max_len=MAX_LEN, pos_encoding="rope",
              dtype_policy="mixed_bf16", attn_impl="xla", seed=1)
    kw.update(over)
    return TransformerLM(**kw).init()


def _olmoe():
    return _dense(norm="rmsnorm", qk_norm=True, num_kv_heads=4, d_ff=16,
                  num_experts=8, experts_per_token=2, tie_embeddings=False,
                  dtype_policy="float32")


def _hybrid():
    mixers = ("kda", "mla", "kda")
    return TransformerLM(
        vocab_size=128, d_model=32, num_heads=2, num_layers=3, d_ff=16,
        max_len=MAX_LEN, pos_encoding="rope", dtype_policy="float32",
        attn_impl="xla", norm="rmsnorm", num_experts=8, experts_per_token=2,
        norm_topk_prob=True, tie_embeddings=False, seed=2,
        rope_interleaved=True, mixers=mixers, ffns=("glu", "moe", "moe"),
        glu_width=48, kda={"head_dim": 16, "conv": 4, "lower": -5.0},
        mla={"kv_lora_rank": 16, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16},
        moe={"n_group": 4, "topk_group": 2, "scale": 2.5, "bias": True,
             "shared_width": 16, "first": 0, "held": 4}).init()


def _qwen3next(**attn):
    """Gated DeltaNet layers beside a gated attention layer with its own
    head size, softmax-routed experts over a share, a gated shared expert."""
    return TransformerLM(
        vocab_size=128, d_model=32, num_heads=2, num_kv_heads=1, num_layers=3,
        d_ff=16, max_len=MAX_LEN, pos_encoding="rope",
        dtype_policy="float32", attn_impl="xla", norm="rmsnorm",
        num_experts=8, experts_per_token=2, norm_topk_prob=True,
        tie_embeddings=False, seed=2, mixers=("gdn", "gdn", "attn"),
        ffns=("moe",) * 3,
        gdn={"key_heads": 1, "value_heads": 2, "head_dim": 16, "conv": 4},
        attn={"head_dim": 32, "rotary_dim": 8, "head_norm": True,
              "gate": True, **attn},
        moe={"shared_width": 16, "shared_gate": True, "first": 0,
             "held": 4}).init()


MODELS = {"dense": _dense, "olmoe": _olmoe, "hybrid": _hybrid,
          "qwen3next": _qwen3next}


def _lower(lm, program, **kw):
    """The lowered train step, prefill program (one rung) or decode program
    of ``lm``, jitted as the model and the engine jit them."""
    if program == "train":
        return lm.make_train_step(donate=False).lower(
            lm.params, lm.opt_state, jnp.zeros((2, BUCKET), jnp.int32),
            jnp.asarray(0, jnp.int32))
    engine = eng.DecodeEngine(lm, SLOTS, max_len=MAX_LEN, buckets=(BUCKET,))
    cache = engine.cache
    if program == "prefill":
        fn = functools.partial(eng._serve_prefill_impl, lm,
                               engine._sample_row, False)
        return jax.jit(fn).lower(
            lm.params, cache.state, jnp.zeros((1, BUCKET), jnp.int32),
            jnp.asarray(5, jnp.int32), jnp.asarray(1, jnp.int32),
            jax.random.PRNGKey(0))
    fn = functools.partial(eng._serve_decode_loop_impl, lm,
                           engine._sample_row, **kw)
    return jax.jit(fn).lower(lm.params, cache.state, cache.loop)


CASES = [(m, p) for m in MODELS for p in ("train", "prefill", "decode")]


# ---- (a) no matmul is left unscoped -----------------------------------------
def _matmul_paths(text):
    """The name-stack path of every ``dot_general`` of a lowered module."""
    return op_paths(text, ("stablehlo.dot_general",))


@pytest.mark.parametrize("model,program", CASES)
def test_every_matmul_carries_a_name_of_the_vocabulary(model, program):
    text = _lower(MODELS[model](), program).as_text(debug_info=True)
    dots = _matmul_paths(text)
    assert dots, "the program has no matmul?"
    bare = sorted({d for d in dots if not NAMES.search(d)})
    assert not bare, bare
    if program == "train":      # forward and backward under the same name
        under = {n for d in dots if "transpose(jvp(" in d
                 for n in NAMES.findall(d)}
        assert {"lm.head", "ffn.dense" if model == "dense" else
                "moe.experts"} <= under


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="no scope of"):
        scopes.scope("ffn.dens")
    assert set(_scopes.NEW) <= set(scopes.SCOPES)


def test_the_package_opens_scopes_through_the_vocabulary_only():
    """A ``jax.named_scope`` written out somewhere else would be a name no
    reader knows to ask for."""
    found = []
    pkg = os.path.join(ROOT, "deeplearning4j_tpu")
    for base, _, files in os.walk(pkg):
        for name in files:
            path = os.path.join(base, name)
            if name.endswith(".py") and path != scopes.__file__:
                with open(path) as f:
                    if "named_scope(" in f.read():
                        found.append(os.path.relpath(path, ROOT))
    assert not found, found


# ---- (b) no scope is open round the attention core or a kernel --------------
def _open_names():
    return NAMES.findall(str(source_info_util.current_name_stack()))


@pytest.mark.parametrize("remat,scan", [(False, False), (True, False),
                                        (False, True), (True, True)])
def test_no_scope_is_open_where_training_calls_flash_attention(
        monkeypatch, remat, scan):
    seen = []

    def flash(q, k, v, causal=False, window=None, **kw):
        seen.append(_open_names())
        return transformer.dot_product_attention(q, k, v, causal=causal,
                                                 window=window)

    monkeypatch.setattr(transformer, "flash_attention", flash)
    lm = _dense(attn_impl="flash", remat=remat, scan_layers=scan)
    text = _lower(lm, "train").as_text(debug_info=True)
    assert seen and not any(seen), seen
    # the scopes reached the bodies that remat and scan trace apart: a
    # backward matmul reads ``transpose(jvp(attn.proj))``, under remat
    # ``transpose(jvp(jvp()))/checkpoint/attn.proj``, and a scan's body is
    # named from ``attn.proj`` on (``_matmul_paths``)
    dots = _matmul_paths(text)
    for name in ("attn.proj", "ffn.dense"):
        mine = [d for d in dots if name in NAMES.findall(d)]
        assert len(mine) >= 6, (name, mine)       # 2 forward, 4 backward
        assert scan or any("transpose(" in d for d in mine)


@pytest.mark.parametrize("program", ["prefill", "decode", "generate"])
def test_no_scope_is_open_at_the_attention_call_site(monkeypatch, program):
    """``_block`` hands q, k, v to the caller's ``attention`` (the pool's
    write and read, ``generate``'s cache) or to the grouped XLA op with
    nothing of the vocabulary open."""
    seen = []
    real_block = TransformerLM._block
    real_gqa = transformer.grouped_query_attention

    def block(self, blk, h, *, attention=None, **kw):
        def spy(*a):
            seen.append(_open_names())
            return attention(*a)
        return real_block(self, blk, h, attention=attention and spy, **kw)

    def gqa(*a, **kw):
        seen.append(_open_names())
        return real_gqa(*a, **kw)

    monkeypatch.setattr(TransformerLM, "_block", block)
    monkeypatch.setattr(transformer, "grouped_query_attention", gqa)
    lm = _dense()
    if program == "generate":
        lm.make_generate(4, 3).lower(lm.params, jnp.zeros((1, 4), jnp.int32),
                                     jax.random.PRNGKey(0))
    else:
        _lower(lm, program)
    assert len(seen) >= lm.num_layers and not any(seen), seen


def test_no_scope_is_open_round_the_decode_kernel(monkeypatch):
    """The kernel's wrapper scopes its work list and operand layout
    (``kv.write``) and closes the scope before ``pallas_call``: the Mosaic
    call keeps its name."""
    seen = []
    real = decode_attention.pl.pallas_call

    def pallas_call(*a, **kw):
        call = real(*a, **kw)

        def run(*operands):
            seen.append(_open_names())
            return call(*operands)
        return run

    monkeypatch.setattr(decode_attention.pl, "pallas_call", pallas_call)
    lm = _dense(d_model=256, num_heads=2, num_kv_heads=1, d_ff=64,
                dtype_policy="float32")
    text = _lower(lm, "decode", pool_kernel=True).as_text(debug_info=True)
    assert len(seen) == lm.num_layers and not any(seen), seen
    assert "kv.write/scatter" in text and "kv.write/jit(cumsum)" in text


def test_a_recurrence_beside_the_pool_kernel_keeps_both_names(monkeypatch):
    """The mixture's decode program: ``gdn.proj`` and ``gdn.step`` name the
    recurrent layers (``gdn.scan`` the prefill's), nothing of ``kda.*``
    does, the pool kernel of the one attention layer is called with no
    scope open, and the recurrence's kernel (``pallas/delta_step.py``: one
    jitted function the layers share, traced once) is called from inside
    ``gdn.step``, which names it for the ``gdn_*`` readers."""
    seen = []
    real = decode_attention.pl.pallas_call

    def pallas_call(*a, **kw):
        call = real(*a, **kw)

        def run(*operands):
            seen.append(_open_names())
            return call(*operands)
        return run

    monkeypatch.setattr(decode_attention.pl, "pallas_call", pallas_call)
    lm = _qwen3next(head_dim=128)
    text = _lower(lm, "decode", pool_kernel=True).as_text(debug_info=True)
    # the pool's call, and the recurrence's where this process had not
    # traced that shape before (inside its own jit no outer scope is open)
    assert seen and not any(seen), seen
    assert "gdn.proj/" in text and "gdn.step/jit(_delta_step)" in text
    assert not re.search(r"gdn\.scan/|kda\.(proj|step|scan)/", text)
    prefill = _lower(lm, "prefill").as_text(debug_info=True)
    assert "gdn.scan/" in prefill and "gdn.step/" not in prefill


# ---- (c) a scope is metadata ------------------------------------------------
@pytest.mark.parametrize("model,program", CASES)
def test_the_lowered_text_is_the_same_without_the_scopes(
        monkeypatch, model, program):
    with_scopes = _lower(MODELS[model](), program)
    assert NAMES.search(with_scopes.as_text(debug_info=True))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lower(MODELS[model](), program)
    assert not re.search(r'loc\("[^"]*(ffn\.dense|lm\.head|moe\.experts)',
                         without.as_text(debug_info=True))
    assert with_scopes.as_text() == without.as_text()


# ---- (d) the readers on a made-up trace -------------------------------------
CELL = {"config": "starcoder2-3b-l4", "traffic_name": "serve-steady",
        "decode_program": {"module": "^jit__unknown$",
                           "runs": "decode_steps_in_trace"},
        "step_program": "^jit_step$"}


def _trace():
    """Ten executions of ``jit__unknown(7)`` (decode) of 50 ns, one of
    ``jit__unknown(9)`` (a prefill) and two of ``jit_step(3)``, with the
    ``{device: {op name: tf_op}}`` a trace file would give."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mosaic = '%attn.4 = bf16[64,32] custom-call(), custom_call_target=' \
             '"tpu_custom_call"'
    mods, ops = [], []
    for i in range(10):
        t = 1000 + 100 * i
        mods.append(ev("jit__unknown(7)", t, 50))
        ops += [ev("%fusion.1 = bf16[64,3072]", t, 10),     # ffn.dense
                ev("%while.2 = f32[8]", t + 10, 8),          # kda.scan, 8 - 6
                ev("%fusion.3 = f32[8]", t + 11, 6),         # body: lm.head
                ev(mosaic, t + 20, 5),
                ev("%copy.5 = bf16[64]", t + 30, 4),         # no tf_op
                ev("%fusion.6 = s32[64]", t + 40, 3)]        # kv.write
    mods.append(ev("jit__unknown(9)", 2500, 80))
    ops.append(ev("%fusion.9 = bf16[1024,3072]", 2510, 70))
    for t in (3000, 3500):
        mods.append(ev("jit_step(3)", t, 400))
        ops += [ev("%fusion.11 = bf16[8192,12288]", t, 100),
                ev("%fusion.12 = f32[3072,12288]", t + 100, 50),
                ev("%fusion.13 = f32[49152,3072]", t + 150, 30),
                ev("%convert.14 = bf16[3072,12288]", t + 200, 20),
                ev("%add.15 = bf16[8192,3072]", t + 300, 7)]
    host = [ev("bench.trace_window", 900, 3100)]
    tf_op = {0: {
        "%fusion.1 = bf16[64,3072]": "jit(_unknown)/ffn.dense/dot_general",
        "%while.2 = f32[8]": "jit(_unknown)/kda.scan/while",
        "%fusion.3 = f32[8]": "jit(_unknown)/kda.scan/while/body/lm.head/mul",
        mosaic: "jit(_unknown)/moe.experts/pallas_call",
        "%fusion.6 = s32[64]": "jit(_unknown)/kv.write/scatter",
        "%fusion.9 = bf16[1024,3072]": "jit(_unknown)/ffn.dense/dot_general",
        "%fusion.11 = bf16[8192,12288]":
            "jit(step)/jit(main)/jvp(ffn.dense)/dot_general",
        "%fusion.12 = f32[3072,12288]":
            "jit(step)/jit(main)/transpose(jvp(ffn.dense))/dot_general",
        "%fusion.13 = f32[49152,3072]": "jit(step)/jit(main)/opt.update/sqrt",
        "%convert.14 = bf16[3072,12288]":
            "jit(step)/jit(main)/opt.cast/convert_element_type",
        "%add.15 = bf16[8192,3072]": "jit(step)/jit(main)/jvp()/add"}}
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host), tf_op


def test_decode_time_by_scope_counts_each_op_once():
    trace, tf_op = _trace()
    ctx, counters = {"cell": CELL}, {"decode_steps_in_trace": 10}
    ms = _scopes.decode_ms(trace, counters, ctx, tf_op)
    # the while less its body, the body under the innermost name, the kernel
    # under no scope's name though it sits under one, the op with no tf_op
    assert ms == pytest.approx({
        "ffn.dense": 10e-6, "kda.scan": 2e-6, "lm.head": 6e-6,
        "mosaic": 5e-6, "unscoped": 4e-6, "kv.write": 3e-6})
    assert sum(ms.values()) == pytest.approx(30e-6)      # the ops' busy time
    assert _scopes.of(ms, "lm.head", "lm.embed") == pytest.approx(6e-6)
    assert _scopes.of(ms, "attn.proj", "kv.write") == pytest.approx(3e-6)
    assert _scopes.of(ms, "attn.proj") is None
    # the prefill's ffn.dense op (7 times a step's) is another program's
    assert _scopes.of(ms, "ffn.dense") == pytest.approx(10e-6)


def test_step_time_by_scope_matches_forward_and_backward():
    trace, tf_op = _trace()
    ms = _scopes.step_ms(trace, {}, {"cell": CELL}, tf_op)
    assert ms == pytest.approx({"ffn.dense": 150e-6, "opt.update": 30e-6,
                                "opt.cast": 20e-6, "unscoped": 7e-6})


def test_every_program_by_scope_for_the_report():
    trace, tf_op = _trace()
    got = _scopes.by_scope(trace, tf_op, tuple(scopes.SCOPES))
    assert {k: v["runs"] for k, v in got.items()} == {
        "jit__unknown(7)": 10, "jit__unknown(9)": 1, "jit_step(3)": 2}
    assert got["jit__unknown(9)"]["device_ms"] == pytest.approx(80e-6)
    assert got["jit__unknown(9)"]["ms"] == pytest.approx({"ffn.dense": 70e-6})


@pytest.mark.parametrize("why", ["a program without the scopes",
                                 "no trace file", "no such program",
                                 "a program without the vocabulary"])
def test_nothing_to_read_gives_none(monkeypatch, why):
    trace, tf_op = _trace()
    ctx, counters = {"cell": dict(CELL)}, {"decode_steps_in_trace": 10}
    if why == "a program without the scopes":    # the parent of PR 33
        tf_op = {0: {op: re.sub(r"ffn\.dense|lm\.head|kv\.write|opt\.\w+",
                                "", s) for op, s in tf_op[0].items()}}
    elif why == "no trace file":
        tf_op = None
    elif why == "no such program":
        counters = {"decode_steps_in_trace": 5}
        ctx["cell"]["step_program"] = "^jit_other$"
    else:
        monkeypatch.setattr(_scopes, "vocabulary", lambda: None)
    assert _scopes.decode_ms(trace, counters, ctx, tf_op) is None
    assert _scopes.step_ms(trace, counters, ctx, tf_op) is None
    assert _scopes.of(None, "lm.head") is None


def test_span_attr_readers():
    from benchmarks.layer_metrics import kv_blocks_share, live_slots_per_step

    def span(start, **stats):
        return xplane.Event("dl4j.serve.decode", float(start), 10.0,
                            {k: str(v) for k, v in stats.items()})

    host = [span(0, live=4, kv_blocks=8, kv_blocks_pool=64),
            span(20, live=2, kv_blocks=4, kv_blocks_pool=32),
            span(40, live=0),                        # reads the last block
            xplane.Event("dl4j.serve.step", 0.0, 50.0, {"live": "9"})]
    trace = xplane.Trace({}, host)
    assert kv_blocks_share.compute(trace, None, {}, {}) == pytest.approx(
        12 / 96)
    assert live_slots_per_step.compute(trace, None, {}, {}) == 3.0
    # a model whose pool has no kernel read; a program that opens no span
    bare = xplane.Trace({}, [span(0, live=4), xplane.Event(
        "dl4j.serve.emit", 5.0, 1.0, {})])
    assert kv_blocks_share.compute(bare, None, {}, {}) is None
    assert live_slots_per_step.compute(bare, None, {}, {}) == 4.0
    none = xplane.Trace({}, [xplane.Event("dl4j.train.step", 0.0, 1.0, {})])
    assert live_slots_per_step.compute(none, None, {}, {}) is None


@pytest.mark.parametrize("program", ["a work list of live slots",
                                     "every slot gathered", "before PR 34",
                                     "no span"])
def test_rows_gathered_per_attended(program):
    """``dsa_rows_gathered_per_attended`` sums ``rows_gathered`` over
    ``keys_attended`` of the ``serve.decode`` spans that carry both: 1 for
    two slots past ``index_topk`` (8) in 5 layers, more while a cursor is
    below it, slots / live for a program that gathers for all 4 slots;
    nothing where the program books no ``rows_gathered``."""
    from benchmarks.layer_metrics import dsa_rows_gathered_per_attended as m

    def span(**stats):
        return xplane.Event("dl4j.serve.decode", 0.0, 10.0,
                            {k: str(v) for k, v in stats.items()})

    host = {"a work list of live slots": [
                span(live=2, keys_attended=80, rows_gathered=80),
                span(live=2, keys_attended=60, rows_gathered=80),
                span(live=0)],
            "every slot gathered": [
                span(live=2, keys_attended=80, rows_gathered=160)],
            "before PR 34": [span(live=2, keys_attended=80, keys_cached=400)],
            "no span": [xplane.Event("dl4j.train.step", 0.0, 1.0, {})]}
    want = {"a work list of live slots": 160 / 140, "every slot gathered": 2.0}
    got = m.compute(xplane.Trace({}, host[program]), None, {}, {})
    assert got == (pytest.approx(want[program]) if program in want else None)
