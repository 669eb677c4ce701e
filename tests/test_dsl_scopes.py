"""The ``dsl.*`` scopes (``deeplearning4j_tpu/scopes.py``) on the lowered
programs of the config DSL, and the readers that split a device trace by them
(``benchmarks/layer_metrics/_dsl_scopes.py``).

(a) every convolution and matmul of a ``ComputationGraph`` chunk program, a
``MultiLayerNetwork`` step and a TBPTT program carries its layer's kind and,
inside it, the user's name for the layer, forward and backward; the
optimizer's ops carry ``dsl.update``; (b) the scopes are metadata: the
lowered text without locations does not change when they are taken away;
(c) a layer named like a vocabulary entry is still read by its kind; (d) the
readers on a made-up trace.
"""

import contextlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from lowered_paths import op_paths  # noqa: E402  (tests/ is on the path)

from benchmarks.layer_metrics import _dsl_scopes, _scopes  # noqa: E402
from benchmarks.lib import program_spans as ps  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402
from deeplearning4j_tpu import scopes  # noqa: E402
from deeplearning4j_tpu.models.zoo import _res_block  # noqa: E402
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater  # noqa: E402,E501
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.conf.enums import BackpropType, PoolingType  # noqa: E402,E501
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.layers.base import (  # noqa: E402
    _IMPL_REGISTRY,
    LayerImpl,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.train_step import step_state  # noqa: E402
from deeplearning4j_tpu.ops.losses import LossFunction  # noqa: E402

NAMES = _scopes.name_pattern(scopes.SCOPES)
MATMULS = ("stablehlo.convolution", "stablehlo.dot_general")


def _builder():
    return (NeuralNetConfiguration.Builder().seed(3).learning_rate(1e-3)
            .updater(Updater.ADAM))


def _resnet(names=lambda n: n):
    """ResNet-18's shape at a tenth of its depth: a stem, a block with an
    identity skip, a block with a projected one, pooling, the head; every
    layer and vertex named by the user (``names`` renames them)."""
    g = _builder().dtype_policy("mixed_bf16").graph_builder().add_inputs("in")
    g.add_layer(names("stem"), L.ConvolutionLayer(
        n_in=3, n_out=8, kernel_size=(3, 3), convolution_mode="same"), "in")
    g.add_layer(names("stem_bn"), L.BatchNormalization(
        n_in=8, n_out=8, activation="relu"), names("stem"))
    prev = _res_block(g, "s0b0", names("stem_bn"), 8, 1, 8)
    prev = _res_block(g, "s1b0", prev, 16, 2, 8)
    g.add_layer("gap", L.GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                prev)
    g.add_layer(names("out"), L.OutputLayer(
        n_in=16, n_out=10, loss_function=LossFunction.MCXENT), "gap")
    g.set_outputs(names("out"))
    return ComputationGraph(g.build()).init()


def _lower_chunk(net):
    """The chunk program (``epoch_run_fn``) over 2 batches of 4 tiny images,
    jitted as ``epoch_train_step`` jits it."""
    xs = (jnp.zeros((2, 4, 8, 8, 3), jnp.float32),)
    ys = (jnp.zeros((2, 4, 10), jnp.float32),)
    return jax.jit(net._epoch_run_fn(True), donate_argnums=(0, 1, 2)).lower(
        *step_state(net), xs, ys, (None,), (None,),
        jax.random.split(jax.random.PRNGKey(0), 2))


def _conv_dense():
    conf = (_builder().list()
            .layer(0, L.ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3),
                                         convolution_mode="same",
                                         activation="relu"))
            .layer(1, L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(2, L.DenseLayer(n_out=8, activation="tanh"))
            .layer(3, L.OutputLayer(n_in=8, n_out=5,
                                    loss_function=LossFunction.MCXENT))
            .set_input_type(InputType.convolutional(8, 8, 3)).build())
    return MultiLayerNetwork(conf).init()


def _lower_step(net):
    batch = (jnp.zeros((4, 8, 8, 3), jnp.float32),
             jnp.zeros((4, 5), jnp.float32), None, None)
    return net._train_step.lower(*step_state(net), batch,
                                 jax.random.PRNGKey(0))


def _lstm():
    conf = (NeuralNetConfiguration.Builder().seed(0).learning_rate(0.02)
            .updater(Updater.SGD).list()
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(4).t_bptt_backward_length(4)
            .layer(0, L.GravesLSTM(n_in=3, n_out=6, activation="tanh"))
            .layer(1, L.RnnOutputLayer(n_in=6, n_out=4,
                                       loss_function=LossFunction.MCXENT))
            .build())
    return MultiLayerNetwork(conf).init()


def _lower_tbptt(net):
    batch = (jnp.zeros((2, 8, 3), jnp.float32),
             jnp.zeros((2, 8, 4), jnp.float32), None, None)
    return net._tbptt_train_step.lower(
        *step_state(net), batch, jax.random.split(jax.random.PRNGKey(0), 2),
        net._zero_rnn_state(2))


PROGRAMS = {"graph chunk": (_resnet, _lower_chunk),
            "multilayer step": (_conv_dense, _lower_step),
            "lstm tbptt": (_lstm, _lower_tbptt)}


def _lower(program):
    build, lower = PROGRAMS[program]
    return lower(build())


# ---- (a) every layer's ops carry its kind and its name ----------------------
@pytest.mark.parametrize("program,layers", [
    ("graph chunk", {"stem": "dsl.conv", "s0b0_c1": "dsl.conv",
                     "s0b0_c2": "dsl.conv", "s1b0_c1": "dsl.conv",
                     "s1b0_c2": "dsl.conv", "s1b0_proj": "dsl.conv",
                     "out": "dsl.dense"}),
    ("multilayer step", {"0": "dsl.conv", "2": "dsl.dense",
                         "3": "dsl.dense"}),
    ("lstm tbptt", {"0": "dsl.recurrent", "1": "dsl.dense"})])
def test_every_convolution_and_matmul_carries_kind_and_layer_name(
        program, layers):
    text = _lower(program).as_text(debug_info=True)
    paths = op_paths(text, MATMULS)
    assert paths, "the program has no matmul?"
    seen = {}
    for p in paths:
        kind = NAMES.findall(p)
        name, way = _dsl_scopes.layer_of(p)
        assert kind and kind[-1].startswith("dsl.") and name, p
        assert p.index(kind[-1]) < p.index(scopes.LAYER_PREFIX + name), p   # kind, then name
        seen.setdefault((name, kind[-1]), set()).add(way)
    # each layer under its own kind, forward and transposed
    assert seen == {(n, k): {"forward", "backward"}
                    for n, k in layers.items()}, seen


def test_the_graph_chunk_names_every_stage_of_the_step():
    text = _lower("graph chunk").as_text(debug_info=True)
    stacks = set(re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M))
    labels = {NAMES.findall(s)[-1] for s in stacks if NAMES.findall(s)}
    assert {"dsl.data", "dsl.conv", "dsl.norm", "dsl.pool", "dsl.dense",
            "dsl.act", "dsl.vertex", "dsl.loss", "dsl.cast",
            "dsl.update"} <= labels
    # the batch gather and the epoch's permutation are the program's input
    # work; a residual add is a vertex with the user's name on it
    assert {"dsl.data/dynamic_slice", "dsl.data/jit(_shuffle)",
            "dsl.data/jit(_threefry_split)"} <= stacks
    assert any(re.search(r"dsl\.vertex\)*/layer\.s0b0_add/add", s) for s in stacks)
    # batch norm's statistics, forward, and its reductions backward
    assert any(re.search(r"jvp\(dsl\.norm\)/layer\.stem_bn/reduce_sum", s)
               for s in stacks)
    assert any(re.search(
        r"transpose\(jvp\(dsl\.norm\)\)/layer\.stem_bn/reduce_sum", s)
        for s in stacks)
    # a convolution's bias gradient is a reduction under dsl.conv
    assert any(re.search(
        r"transpose\(jvp\(dsl\.conv\)\)/layer\.s0b0_c1/reduce_sum", s)
        for s in stacks)


@pytest.mark.parametrize("program", ["graph chunk", "multilayer step"])
def test_the_optimizers_ops_carry_dsl_update(program):
    """Adam's moments and the parameter update: every ``sqrt`` of the
    program is the updater's, and sits under ``dsl.update``."""
    text = _lower(program).as_text(debug_info=True)
    roots = op_paths(text, ("stablehlo.sqrt",))
    assert roots and all(NAMES.findall(p)[-1:] == ["dsl.update"]
                         for p in roots), roots
    if program == "graph chunk":    # the mixed policy's casts, both ways
        casts = {p for p in op_paths(text, ("stablehlo.convert",))
                 if NAMES.findall(p)[-1:] == ["dsl.cast"]}
        assert len(casts) >= 1, casts


def test_every_layer_impl_has_a_kind_of_the_vocabulary():
    impls = set(_IMPL_REGISTRY.values())
    assert len(impls) >= 19
    for cls in impls:
        assert cls.kind in scopes.SCOPES and cls.kind.startswith("dsl."), cls
        assert cls.kind != "dsl.layer", cls      # the library's have a name
    assert LayerImpl.kind == "dsl.layer"


# ---- (b) a scope is metadata ------------------------------------------------
@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_lowered_text_is_the_same_without_the_scopes(monkeypatch,
                                                         program):
    with_scopes = _lower(program)
    assert re.search(r'loc\("[^"]*dsl\.(dense|update)',
                     with_scopes.as_text(debug_info=True))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lower(program)
    assert not re.search(r'loc\("[^"]*(dsl\.\w+|layer\.\w+)/',
                         without.as_text(debug_info=True))
    assert with_scopes.as_text() == without.as_text()


def test_the_names_reach_the_compiled_programs_metadata():
    """What a device trace shows as ``tf_op`` is the compiled instruction's
    ``op_name``, and XLA's exporter cuts a location's name at the first
    ``@``: with ``@<name>`` for the layer, the name and the primitive after
    it were gone from every op of the real chunk program compiled for a
    v5e (PR 49). The prefix is letters and a dot, and arrives whole."""
    net = _conv_dense()
    text = _lower_step(net).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(re.search(r"jvp\(dsl\.conv\)/layer\.0/conv_general_dilated", n)
               for n in names), sorted(names)[:20]
    assert any(re.search(
        r"transpose\(jvp\(dsl\.dense\)\)/layer\.3/dot_general", n)
        for n in names)
    assert "@" not in scopes.LAYER_PREFIX
    assert _dsl_scopes.layer_of(scopes.LAYER_PREFIX + "x/add") == (
        "x", "forward")


# ---- (c) the user's names cannot pass for the vocabulary's ------------------
def test_a_layer_named_like_a_vocabulary_entry_is_read_by_its_kind():
    rename = {"stem": "dsl.norm", "stem_bn": "lm.head", "out": "a/b (c)"}
    net = _resnet(lambda n: rename.get(n, n))
    paths = op_paths(_lower_chunk(net).as_text(debug_info=True), MATMULS)
    label = _scopes.labeller(tuple(scopes.SCOPES))
    got = {(_dsl_scopes.layer_of(p)[0], label("%op", p)) for p in paths}
    assert ("dsl.norm", "dsl.conv") in got       # the stem, a convolution
    assert ("a_b__c_", "dsl.dense") in got       # no "/" or "(" in a path
    assert {k for _, k in got} == {"dsl.conv", "dsl.dense"}
    stacks = re.findall(r'^#loc\d+ = loc\("([^"]*layer\.lm\.head[^"]*)"',
                        _lower_chunk(net).as_text(debug_info=True), re.M)
    assert stacks and {label("%op", s) for s in stacks} == {"dsl.norm"}


def test_a_layer_name_outside_the_vocabulary_is_still_refused():
    with pytest.raises(ValueError, match="no scope of"):
        scopes.scope("dsl.convolution")
    with pytest.raises(ValueError, match="no scope of"):
        with scopes.layer_scope("s0b0_c1", "dsl.conv"):   # kind comes first
            pass
    assert not any(n.startswith(scopes.LAYER_PREFIX) for n in scopes.SCOPES)


# ---- (d) the readers on a made-up trace -------------------------------------
CTX = {"cell": {"config": "resnet18-cifar10", "traffic_name": "dp4-epochs"}}
RUN = "jit(run)/jit(main)/while/body/while/body/"
TF_OP = {
    "%while.1 = (f32[8])": "jit(run)/jit(main)/while",
    "%while.2 = (f32[8])": "jit(run)/jit(main)/while/body/while",
    "%fusion.3 = bf16[64,32,32,64]":
        RUN + "jvp(dsl.conv)/layer.s0b0_c1/conv_general_dilated",
    "%fusion.4 = f32[64]":
        RUN + "transpose(jvp(dsl.conv))/layer.s0b0_c1/reduce_sum",
    "%fusion.5 = f32[64]": RUN + "jvp(dsl.norm)/layer.s0b0_b1/reduce_sum",
    "%fusion.6 = f32[128]":
        RUN + "transpose(jvp(dsl.norm))/layer.s1b0_b1/reduce_sum",
    "%fusion.7 = bf16[64,32,32,64]":
        RUN + "transpose(jvp(dsl.vertex))/layer.s0b0_add/add",
    "%fusion.8 = f32[11000000]": RUN + "dsl.update/sqrt",
    "%convert.9 = bf16[3,3,64,64]": RUN + "dsl.cast/convert_element_type",
    "%gather.10 = f32[64,32,32,3]": RUN + "dsl.data/dynamic_slice",
    "%fusion.11 = f32[]": RUN + "jvp(dsl.loss)/reduce_sum",
    "%all-reduce.12 = f32[11000000]": "",
    "%sort.13 = s32[3]": "jit(run)/jit(main)/while/body/dsl.data/sort",
}
# (op, offset in a step, duration): 100 ns of ops a step
STEP = [("%fusion.3 = bf16[64,32,32,64]", 0, 30),
        ("%fusion.4 = f32[64]", 30, 10), ("%fusion.5 = f32[64]", 40, 15),
        ("%fusion.6 = f32[128]", 55, 5),
        ("%fusion.7 = bf16[64,32,32,64]", 60, 8),
        ("%fusion.8 = f32[11000000]", 68, 12),
        ("%convert.9 = bf16[3,3,64,64]", 80, 4),
        ("%gather.10 = f32[64,32,32,3]", 84, 6),
        ("%fusion.11 = f32[]", 90, 3), ("%all-reduce.12 = f32[11000000]", 93,
                                        7)]


def _trace(chunks=3, devices=2, spans=None, epochs=2, steps=3, run=True):
    """``chunks`` executions of ``jit_run(4)`` a device, each ``epochs``
    epochs (an outer ``while``; a sort of 10 ns at the head of each) of
    ``steps`` optimizer steps (an inner ``while``) of 100 ns of ops; one
    ``epoch.chunk`` span a launch carrying the program's step count, all
    inside ``epoch.run`` spans with two eager programs beside each chunk."""
    def ev(name, start, dur, **stats):
        return xplane.Event(name, float(start), float(dur),
                            {k: str(v) for k, v in stats.items()})

    epoch_ns = 10 + 100 * steps
    chunk_ns = 20 + epochs * epoch_ns
    devs, host = {}, []
    for c in range(chunks):
        t0 = 1000 + 2000 * c
        host += [ev("dl4j.epoch.chunk", t0 - 60, 40, steps=epochs * steps,
                    epochs=epochs),
                 ev(ps.LAUNCH, t0 - 100, 5), ev(ps.LAUNCH, t0 - 50, 5),
                 ev(ps.LAUNCH, t0 + chunk_ns + 50, 5)]
        if run:
            host.append(ev("dl4j.epoch.run", t0 - 150, chunk_ns + 300,
                           steps=epochs * steps))
    # a program launched outside every epoch.run: the harness's own
    host.append(ev(ps.LAUNCH, 1000 + 2000 * chunks, 5))
    host.append(ev("bench.trace_window", 500, 2000 * chunks + 1000))
    for d in range(devices):
        mods, ops = [], []
        for c in range(chunks):
            t0 = 1000 + 2000 * c
            # the eager crumbs run on the first device alone
            if d == 0:
                mods += [ev("jit__threefry_split(2)", t0 - 80, 20),
                         ev("jit_squeeze(3)", t0 + chunk_ns + 60, 10)]
                ops += [ev("%custom-call.20 = u32[4,2]", t0 - 80, 20),
                        ev("%slice.21 = f32[]", t0 + chunk_ns + 60, 10)]
            mods.append(ev("jit_run(4)", t0, chunk_ns))
            ops.append(ev("%while.1 = (f32[8])", t0 + 5, chunk_ns - 10))
            for e in range(epochs):
                te = t0 + 10 + e * epoch_ns
                ops.append(ev("%sort.13 = s32[3]", te, 10))
                ops.append(ev("%while.2 = (f32[8])", te + 10, 100 * steps))
                for s in range(steps):
                    ops += [ev(op, te + 10 + 100 * s + at, dur)
                            for op, at, dur in STEP]
        devs[d] = xplane.DeviceTrace(sorted(ops, key=lambda e: (e.start,
                                                                -e.dur)),
                                     sorted(mods, key=lambda e: e.start))
    host.sort(key=lambda e: (e.start, -e.dur))
    return (xplane.Trace(devs, spans if spans is not None else host),
            {d: dict(TF_OP) for d in range(devices)})


def test_the_chunk_programs_time_by_kind_adds_up():
    trace, tf_op = _trace()
    keys, steps, device_ms = _dsl_scopes.chunk(trace)
    assert keys == {"jit_run(4)"} and steps == 6
    assert device_ms == pytest.approx(640e-6)        # 20 + 2 x (10 + 300) ns
    assert _dsl_scopes.step_device_ms(trace) == pytest.approx(640e-6 / 6)
    ms = _dsl_scopes.step_ms(trace, CTX, tf_op)
    ns = {k: 1e6 * v for k, v in ms.items()}
    # a step's ops, each once; the sorts, a third of 10 ns a step; the outer
    # while less its bodies (10 ns an execution), the inner one nothing
    assert ns == pytest.approx({
        "dsl.conv": 40, "dsl.norm": 20, "dsl.vertex": 8, "dsl.update": 12,
        "dsl.cast": 4, "dsl.data": 6 + 10 / 3, "dsl.loss": 3,
        "unscoped": 7 + 10 / 6})
    assert sum(ns.values()) == pytest.approx(630 / 6)    # the ops' busy time
    assert 1e6 * _dsl_scopes.of(ms, *_dsl_scopes.LAYERS) == pytest.approx(11)
    assert _dsl_scopes.of(ms, "dsl.embed") == 0.0    # read, and nothing there
    assert 1e6 * _dsl_scopes.outside(ms) == pytest.approx(7 + 10 / 6)


def test_the_nine_metrics_on_the_made_up_trace(monkeypatch):
    trace, tf_op = _trace()
    monkeypatch.setattr(_dsl_scopes._moe, "trace_scopes", lambda ctx: tf_op)
    _dsl_scopes._read.clear()
    want = {"dsl_step_device_ms": 640 / 6, "dsl_conv_ms_per_step": 40,
            "dsl_norm_ms_per_step": 20, "dsl_other_layers_ms_per_step": 11,
            "dsl_update_ms_per_step": 16, "dsl_data_ms_per_step": 6 + 10 / 3,
            "dsl_unscoped_ms_per_step": 7 + 10 / 6}
    got = {}
    for name in want:
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert (reader.NAME, reader.MOVES) == (name, "train_mfu")
        got[name] = 1e6 * reader.compute(trace, None, {}, CTX)
    assert got == pytest.approx(want)
    assert sum(got.values()) - got["dsl_step_device_ms"] == pytest.approx(
        630 / 6)
    # the chunk program and the two crumbs beside it, not the harness's own
    from benchmarks.layer_metrics import (dsl_host_idle_ms_per_chunk,
                                          dsl_programs_per_chunk)
    assert dsl_programs_per_chunk.compute(trace, None, {}, CTX) == 3.0
    # inside a run of 940 ns the first device's ops take 630 + 20 + 10
    assert 1e6 * dsl_host_idle_ms_per_chunk.compute(
        trace, None, {}, CTX) == pytest.approx(940 - 660)
    _dsl_scopes._read.clear()


def test_time_by_the_users_layer_names_forward_and_backward():
    trace, tf_op = _trace()
    ops = _dsl_scopes.step_ms(trace, CTX, tf_op, detail=True)
    rows = {k: 1e6 * v for k, v in _dsl_scopes.by_layer(ops).items()}
    assert rows == pytest.approx({
        ("s0b0_c1", "dsl.conv", "forward"): 30,
        ("s0b0_c1", "dsl.conv", "backward"): 10,     # the bias gradient
        ("s0b0_b1", "dsl.norm", "forward"): 15,
        ("s1b0_b1", "dsl.norm", "backward"): 5,
        ("s0b0_add", "dsl.vertex", "backward"): 8,
        ("", "dsl.update", "forward"): 12, ("", "dsl.cast", "forward"): 4,
        ("", "dsl.data", "forward"): 6 + 10 / 3,
        ("", "dsl.loss", "forward"): 3,
        ("", "unscoped", "forward"): 7 + 10 / 6})
    assert _dsl_scopes.layer_of(
        "jit(run)/transpose(jvp(dsl.conv))/transpose(jvp(layer.a.b-c))/mul;x") == (
            "a.b-c", "backward")


@pytest.mark.parametrize("why", [
    "no trace", "no epoch.chunk span", "a run count that does not match",
    "a program without the names", "a checkout without the names",
    "no trace file"])
def test_nothing_to_read_gives_none(monkeypatch, why):
    trace, tf_op = _trace()
    device_ms = pytest.approx(640e-6 / 6)
    if why == "no trace":
        trace = xplane.Trace()
        device_ms = None
    elif why == "no epoch.chunk span":       # a program from before PR 23
        trace = _trace(spans=[xplane.Event("bench.trace_window", 500.0,
                                           7000.0)])[0]
        device_ms = None
    elif why == "a run count that does not match":
        spans = [e for e in trace.host if e.name != "dl4j.epoch.chunk"
                 or e.start < 2000]
        trace = _trace(spans=spans)[0]
        device_ms = None
    elif why == "a program without the names":       # the parent's, or one
        tf_op = {d: {op: re.sub(r"dsl\.\w+", "", s)  # from a warm cache
                     for op, s in ops.items()} for d, ops in tf_op.items()}
    elif why == "a checkout without the names":
        monkeypatch.setattr(_scopes, "vocabulary",
                            lambda: ("lm.head", "opt.update"))
    else:
        tf_op = None
    monkeypatch.setattr(_dsl_scopes._moe, "trace_scopes", lambda ctx: tf_op)
    _dsl_scopes._read.clear()
    assert _dsl_scopes.step_device_ms(trace) == device_ms
    ms = _dsl_scopes.step_ms(trace, CTX)
    assert ms is None
    assert _dsl_scopes.of(ms, "dsl.conv") is None
    assert _dsl_scopes.outside(ms) is None
    _dsl_scopes._read.clear()


def test_a_program_without_epoch_run_gives_the_span_metrics_nothing():
    """The parent of PR 49 opens ``epoch.chunk`` and no ``epoch.run``."""
    from benchmarks.layer_metrics import (dsl_host_idle_ms_per_chunk,
                                          dsl_programs_per_chunk)

    trace, _ = _trace(run=False)
    assert dsl_programs_per_chunk.compute(trace, None, {}, CTX) is None
    assert dsl_host_idle_ms_per_chunk.compute(trace, None, {}, CTX) is None
    assert _dsl_scopes.step_device_ms(trace) == pytest.approx(640e-6 / 6)


def test_the_lm_cells_readers_do_not_see_the_dsl_names():
    """``_scopes.NEW`` is what tells an LM step program read; no ``dsl.*``
    name is in it, and a DSL program gives ``step_ms`` nothing."""
    assert not any(n.startswith("dsl.") for n in _scopes.NEW)
    trace, tf_op = _trace()
    ctx = {"cell": dict(CTX["cell"], step_program="^jit_run$")}
    assert _scopes.step_ms(trace, {}, ctx, tf_op) is None
