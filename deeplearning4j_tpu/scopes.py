"""The scope vocabulary: every ``jax.named_scope`` the model, serving and
config-DSL training code opens, listed once.

A scope is metadata on the lowered program: it reaches a device trace as the
``tf_op`` stat of XLA's own ops (``jit(step)/transpose(jvp(ffn.dense))/
dot_general``) and changes nothing the compiler sees, so there is nothing to
switch off. The readers under ``benchmarks/layer_metrics/`` sum device time
by these names; an op whose ``tf_op`` holds none of them is what
``unscoped_ms_per_step`` / ``unscoped_ms_per_decode_step`` report.

The one thing a scope does rename is a Mosaic custom call lowered inside it:
the kernel's instruction takes the name stack as its name
(``moe.experts...mosaic``; the flash kernels are ``jvp__`` /
``transpose_jvp___`` because nothing is open round them). So **no scope of
this list is open where ``attention(q, k, v)``, ``flash_attention``,
``ring_attention`` or ``grouped_query_attention`` is called from the block,
nor round the decode-attention kernel's ``pallas_call``**: those kernels
keep the names the accepted readers know (``tests/test_scopes.py`` holds
this). The one exception names a kernel on purpose: ``attn.window`` is opened
round the decode kernel's call for a window layer of a model that gives a
window a layer, so that a trace tells its ring reads from the full layers'. ``attn.core`` is opened inside the XLA attention op itself
(``ops/attention.py``), which holds no kernel. The routed experts' kernel
sits under ``moe.experts`` since PR 30 and is read by that name, and the
delta-rule decode step's (``pallas/delta_step.py``) under ``gdn.step``
since PR 45 (under ``kda.step`` once ``kda.recur`` takes it there), power
retention's (``pallas/retention_step.py``) under ``ret.step``.

The config DSL's names (``dsl.*``, PR 49) are by KIND of layer: a
``LayerImpl`` says its kind as a class attribute (``dsl.layer`` on the base
class), both network classes run a layer through ``nn/layers/base.
forward_layer``, and that opens ``layer_scope(kind, name)``: the kind and,
inside it, ``layer.<the user's name for the layer>``. The readers label by
the kind, whatever a user calls a layer (no vocabulary name starts with
``layer.``);
``benchmarks/tools/dsl_layer_report.py`` reads the names. No kernel sits under
``nn/`` today; the rule above holds for one that comes.

Norms between the halves of a block stay bare on purpose: XLA fuses the next
norm's statistics into the fusion that ends the previous matmul, and which op
of a multi-output fusion gives it its ``tf_op`` is XLA's choice (PERF.md
section 5 says where each straddling fusion landed on the chip).
"""

from __future__ import annotations

import contextlib
import re

import jax

# name -> what is under it (docs/observability.md has the table with the
# metrics that read each)
SCOPES = {
    "lm.embed": "token gather, learned positions, cast into the compute dtype",
    "attn.proj": "wq/wk/wv, QK-norm, head reshapes, RoPE, the kv-head repeat; "
                 "reopened for the output gate, wo and its residual add",
    "attn.window": "the decode kernel's read of a layer with a window, of a "
                   "model that gives a window a layer (attn['windows']): "
                   "opened round the pallas_call alone, to name it",
    "attn.core": "the XLA attention op (scores, mask, softmax, values) where "
                 "no kernel applies",
    "kv.write": "a decode-family step's new rows into the slot pool or a "
                "latent cache, and the decode kernel's work list and operand "
                "layout (not the kernel)",
    "ffn.dense": "the dense feed-forward: glu or mlp matmuls, activation, "
                 "biases, residual add",
    "lm.head": "final norm, unembedding, output cast; the loss's log-softmax "
               "and mean in training, the sampler in serving",
    "opt.cast": "the step's compute-dtype copy of the parameters and the "
                "gradients' cast back to the master dtype",
    "opt.update": "the per-leaf Adam update",
    "moe.route": "router logits, top-k, the load per expert",
    "moe.experts": "the routed experts (dense, sorted or reached form)",
    "moe.shared": "the shared expert",
    "kda.proj": "KDA projections, convolutions, gates, output",
    "kda.step": "KDA's one-position recurrence (decode)",
    "kda.scan": "KDA's chunked recurrence (prefill, training)",
    "gdn.proj": "Gated DeltaNet projections, convolution, gates, output norm "
                "and output projection",
    "gdn.step": "Gated DeltaNet's one-position recurrence (decode): the "
                "delta-step kernel over the live slots, or kda_step",
    "gdn.scan": "Gated DeltaNet's chunked recurrence (prefill, training)",
    "ret.proj": "power retention's projections, head norms, RoPE, gate and "
                "output projection",
    "ret.step": "power retention's one-position recurrence (decode): the "
                "retention-step kernel over the live slots, or ret_step",
    "ret.scan": "power retention's chunked recurrence (prefill, training)",
    "mla.proj": "MLA query/latent projections, norms, RoPE, output",
    "mla.attend": "MLA attention over latent rows or expanded keys",
    "dsa.index": "the lightning indexer: index keys, scores, selection",
    "dsa.pool": "pooled index keys (dsa['pool']): the open pool's running "
                "sum and the write of a pool's mean key into the index cache",
    "hc.map": "hyper-connections (hc=): the n-stream residual's norm, the "
              "projection onto the three maps, sigmoids, Sinkhorn sweeps",
    "hc.mix": "hyper-connections: a sub-layer's input H_pre X and its "
              "write-back H_res X + H_post^T y; the entry's copies and the "
              "exit's sum of the streams",
    "mtp": "the multi-token-prediction module's own block and its pass "
           "through the shared head; the block's scopes are open inside it "
           "(mtp/mla.proj, mtp/moe.experts, ...)",
    "mtp.embed": "the module's token gather: the embedding of the token one "
                 "position on",
    "mtp.proj": "the module's two input norms and M, [2 D, D]",
    "spec.accept": "a speculative round's accept / resample rule, its "
                   "emitted block and the loop state's advance",
    # the config DSL (nn/): a layer's scope is its kind, LayerImpl.kind,
    # and inside it the user's own name for the layer (layer_scope)
    "dsl.data": "a chunk program's own input work: the epoch's permutation "
                "and key splits, the gather of a batch from the resident "
                "stacks; TBPTT's cut into windows",
    "dsl.conv": "ConvolutionImpl: input dropout, casts, the convolution, the "
                "bias add, the activation -- so, backward, the weight, data "
                "and bias gradients",
    "dsl.norm": "BatchNormImpl, LRNImpl: statistics, running averages, "
                "normalisation, scale and shift, activation",
    "dsl.pool": "SubsamplingImpl, GlobalPoolingImpl",
    "dsl.dense": "DenseImpl, OutputImpl, RnnOutputImpl, the pretrain "
                 "layers' forward (auto-encoders, RBM)",
    "dsl.embed": "EmbeddingImpl",
    "dsl.recurrent": "the LSTM / GRU family of nn/layers/recurrent.py",
    "dsl.act": "ActivationImpl, DropoutImpl, LossLayerImpl's forward",
    "dsl.vertex": "a ComputationGraph vertex: merge, element-wise (a "
                  "residual add), subset, stack, the rest",
    "dsl.layer": "a LayerImpl of a kind with no name of its own (a user's "
                 "registered layer): the default on the base class",
    "dsl.loss": "compute_loss and the L1/L2 penalties",
    "dsl.cast": "optimizer_step's compute-dtype copy of the parameters and "
                "the gradients' cast back to the master dtype",
    "dsl.update": "net._apply_updaters: gradient normalisation, the "
                  "updater's math, the parameter update; the sentinel's "
                  "finite check and the metrics pack where compiled in",
}

# a user's layer or vertex name as a path component: no vocabulary name
# starts with it, so a layer called "lm.head" is read by its kind. Letters and
# a dot, as the vocabulary's own names: XLA's exporter cuts an op's name at
# the first "@" (a layer's name and the primitive after it never reached the
# compiled program's metadata with that prefix; tests/test_dsl_scopes.py)
LAYER_PREFIX = "layer."
_NOT_IN_A_NAME = re.compile(r"[^\w.\-]")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is no scope of scopes.py: "
                         f"{sorted(SCOPES)}")
    return jax.named_scope(name)


@contextlib.contextmanager
def layer_scope(kind: str, name):
    """The scope of one layer or vertex of a DSL network: its kind, a name
    of the vocabulary, and inside it ``layer.<the user's name>`` (an index
    for a ``MultiLayerNetwork``), so an op's ``tf_op`` reads
    ``.../transpose(jvp(dsl.conv))/layer.s0b0_c1/conv_general_dilated``. The readers label by the kind; the name is for
    ``benchmarks/tools/dsl_layer_report.py``."""
    with scope(kind), jax.named_scope(
            LAYER_PREFIX + _NOT_IN_A_NAME.sub("_", str(name))):
        yield
