"""deeplearning4j-tpu: a TPU-native deep learning framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of
deeplearning4j (v0.4-rc3.9 era): layer/network abstractions, a config DSL with
JSON round-trip, optimizers, evaluation, data pipeline, NLP/embedding models,
clustering/t-SNE, and distributed training — rebuilt TPU-first.

Where the reference dispatches every INDArray op synchronously to an external
native backend (ND4J; see /root/reference SURVEY), this framework compiles the
entire training step (forward + backward + updater) to a single XLA program via
``jax.jit`` / ``pjit``, shards over ``jax.sharding.Mesh`` for data/tensor/
sequence parallelism, and keeps the host side (ETL, checkpoints, CLI, UI) in
Python/C++.
"""

__version__ = "0.1.0"

# The top-level conveniences resolve lazily (PEP 562): the network classes
# pull in jax, and control-plane consumers (scripts/, a parent that must
# leave the chip to its child) must be able to import
# ``deeplearning4j_tpu.monitor`` (stdlib-only) WITHOUT any jax/backend
# initialization. ``from deeplearning4j_tpu import MultiLayerNetwork`` is
# unchanged for users.
_LAZY_ATTRS = {
    "NeuralNetConfiguration": "deeplearning4j_tpu.nn.conf",
    "MultiLayerConfiguration": "deeplearning4j_tpu.nn.conf",
    "ComputationGraphConfiguration": "deeplearning4j_tpu.nn.conf",
    "MultiLayerNetwork": "deeplearning4j_tpu.nn.multilayer",
    "ComputationGraph": "deeplearning4j_tpu.nn.graph",
}

__all__ = ["__version__", *_LAZY_ATTRS]


def __getattr__(name):
    target = _LAZY_ATTRS.get(name)
    if target is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(target), name)
    globals()[name] = value  # cache: subsequent lookups skip __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY_ATTRS))
