"""One mesh for everything: the DP×TP×PP sharding-spec registry.

Before this module, every parallel wrapper carried its own ad-hoc
``NamedSharding``/``out_shardings`` call sites — the epoch cache placed
batches one way, ``ParallelWrapper`` pinned program outputs another,
``tensor_parallel``/``fsdp`` each invented their own placement walk, and
the serving engine sharded over nothing. This module is the single point
of truth GSPMD (arXiv 2105.04663) asks for: ONE named mesh over the
``data`` × ``model`` × ``pipe`` axes (``parallel/mesh.py`` names), and
ONE per-model registry mapping every parameter, updater-state, and
activation leaf to a ``PartitionSpec``. Training (`fit_epochs`), the
DP/FSDP wrapper, elastic topology reshard (arXiv 2112.01075 — a full
host tensor lands on ANY topology, so 8×1 → 4×2 is a device_put with
the new mesh's specs), and the serving decode engine all consume the
SAME specs, so a model's placement story is written exactly once.

Registry contract (the "no silent replication" rule): every leaf of the
model's param tree MUST be covered by an explicit spec — a ``P()``
(replicate, on purpose) or a sharded spec. An unmapped leaf raises
:class:`UnmappedLeafError` at registry construction instead of silently
falling back to replicated, because a silently-replicated large leaf is
an HBM regression nobody sees until a model stops fitting.

Lint: dl4j-lint rule 9 (``adhoc-out-shardings``) flags ``NamedSharding(``
construction and ``out_shardings=`` keywords OUTSIDE this module; the
handful of sanctioned low-level builders (``mesh.py``, ``fsdp.py``, ...)
carry per-site suppressions with reasons, and everything else routes
through :func:`named` / the registry API.

Env knobs (resolved by :func:`mesh_from_env`):

- ``DL4J_MESH_SHAPE`` — ``"8x1"`` / ``"4x2"`` / ``"2x2x2"`` as
  data×model[×pipe]; the full-topology override.
- ``DL4J_TP_SHARDS`` — just the ``model`` axis size; ``data`` takes the
  remaining devices (``MeshSpec(data=-1, model=N)``).
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    MeshSpec,
    build_mesh,
)

logger = logging.getLogger(__name__)

__all__ = [
    "UnmappedLeafError",
    "ShardingRegistry",
    "named",
    "replicated_sharding",
    "batch_spec",
    "batch_sharding",
    "stage_spec",
    "model_axis_size",
    "pipe_axis_size",
    "parse_mesh_shape",
    "mesh_from_env",
]


class UnmappedLeafError(KeyError):
    """A param/updater leaf has no PartitionSpec in the registry — the
    registry refuses to guess (silent replication is an HBM regression,
    silent sharding a numerics one)."""


# ---------------------------------------------------------------------------
# sanctioned sharding builders — the ONE module where NamedSharding is
# constructed for model/batch placement (dl4j-lint rule 9 exempts this file)
# ---------------------------------------------------------------------------
def named(mesh: Mesh, spec: P) -> NamedSharding:
    """THE sanctioned ``NamedSharding`` constructor: modules that need a
    concrete sharding build it here so rule 9 keeps ad-hoc construction
    out of the rest of the tree."""
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on ``mesh``."""
    return named(mesh, P())


def batch_spec(ndim: int, *, stacked: bool = False,
               axis: str = DATA_AXIS) -> P:
    """The activation/batch PartitionSpec: batch dim over ``data``,
    everything else replicated. ``stacked=True`` is the epoch cache's
    ``[N, B, ...]`` layout (N batches resident; the BATCH dim is axis 1)."""
    if stacked:
        return P(None, axis, *([None] * max(0, ndim - 2)))
    return P(axis, *([None] * max(0, ndim - 1)))


def batch_sharding(mesh: Mesh, ndim: int, *, stacked: bool = False,
                   axis: str = DATA_AXIS) -> NamedSharding:
    return named(mesh, batch_spec(ndim, stacked=stacked, axis=axis))


def stage_spec(ndim: int, *, axis: str = PIPE_AXIS) -> P:
    """Stacked pipeline-stage params ``[S, ...]``: leading stage axis over
    ``pipe`` (the layout ``pipeline_parallel.spmd_pipeline`` consumes)."""
    return P(axis, *([None] * max(0, ndim - 1)))


def model_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the ``model`` (tensor-parallel) axis; 1 when absent."""
    if mesh is None:
        return 1
    return int(mesh.shape.get(MODEL_AXIS, 1))


def pipe_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the ``pipe`` (pipeline) axis; 1 when absent."""
    if mesh is None:
        return 1
    return int(mesh.shape.get(PIPE_AXIS, 1))


# ---------------------------------------------------------------------------
# env-driven mesh resolution
# ---------------------------------------------------------------------------
def parse_mesh_shape(text: str) -> MeshSpec:
    """``"8x1"`` / ``"4x2"`` / ``"2x2x2"`` → MeshSpec(data, model[, pipe]).
    One value means pure DP; a fourth value is rejected (the registry
    axes are data×model×pipe)."""
    parts = [p.strip() for p in str(text).lower().split("x") if p.strip()]
    if not 1 <= len(parts) <= 3:
        raise ValueError(
            f"DL4J_MESH_SHAPE={text!r} must be DPxTP or DPxTPxPP "
            "(e.g. '8x1', '4x2', '2x2x2')")
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(
            f"DL4J_MESH_SHAPE={text!r}: non-integer mesh dimension")
    if any(d < 1 for d in dims):
        raise ValueError(f"DL4J_MESH_SHAPE={text!r}: dims must be >= 1")
    dims += [1] * (3 - len(dims))
    return MeshSpec(data=dims[0], model=dims[1], pipe=dims[2])


def mesh_from_env(devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """Resolve ``DL4J_MESH_SHAPE`` (full topology, wins) then
    ``DL4J_TP_SHARDS`` (model axis only, data takes the rest) into a
    built mesh; ``None`` when neither is set."""
    shape = os.environ.get("DL4J_MESH_SHAPE", "").strip()
    if shape:
        return build_mesh(parse_mesh_shape(shape), devices=devices)
    tp = os.environ.get("DL4J_TP_SHARDS", "").strip()
    if tp:
        n = int(tp)
        if n < 1:
            raise ValueError(f"DL4J_TP_SHARDS={tp!r} must be >= 1")
        return build_mesh(MeshSpec(data=-1, model=n), devices=devices)
    return None


# ---------------------------------------------------------------------------
# strict spec-tree expansion
# ---------------------------------------------------------------------------
def _is_leaf(x) -> bool:
    return not isinstance(x, (dict, list, tuple))


def _expand(tree, spec, path: Tuple[Any, ...], name: str):
    """Expand a (possibly sentinel-bearing) spec tree against the model's
    actual param tree, leaf for leaf. Structure mismatches and missing
    keys raise :class:`UnmappedLeafError` naming the leaf path."""
    from deeplearning4j_tpu.parallel.tensor_parallel import _ReplicateAll

    if isinstance(spec, _ReplicateAll):
        # explicit whole-subtree replicate declaration — expand to P()
        # per leaf so lookups stay total
        if isinstance(tree, dict):
            return {k: _expand(v, spec, path + (k,), name)
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return [_expand(v, spec, path + (i,), name)
                    for i, v in enumerate(tree)]
        return P()
    if isinstance(tree, dict):
        if not isinstance(spec, dict):
            raise UnmappedLeafError(
                f"registry[{name}]: param subtree at {path!r} is a dict "
                f"but its spec is {type(spec).__name__}")
        out = {}
        for k, v in tree.items():
            if k not in spec:
                raise UnmappedLeafError(
                    f"registry[{name}]: no PartitionSpec for param leaf "
                    f"{path + (k,)!r} — every leaf needs an explicit "
                    "spec (P() to replicate on purpose)")
            out[k] = _expand(v, spec[k], path + (k,), name)
        return out
    if isinstance(tree, (list, tuple)):
        if not isinstance(spec, (list, tuple)) or len(spec) != len(tree):
            raise UnmappedLeafError(
                f"registry[{name}]: param list at {path!r} has "
                f"{len(tree)} entries but the spec does not match")
        return [_expand(v, s, path + (i,), name)
                for i, (v, s) in enumerate(zip(tree, spec))]
    if not isinstance(spec, P):
        raise UnmappedLeafError(
            f"registry[{name}]: spec for leaf {path!r} is "
            f"{type(spec).__name__}, expected PartitionSpec")
    return spec


def _replicate_all_tree(tree):
    """Explicit replicate-everything spec tree matching ``tree``."""
    if isinstance(tree, dict):
        return {k: _replicate_all_tree(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_replicate_all_tree(v) for v in tree]
    return P()


def _divisible_or_replicated(tree, spec, mesh, name, path=()):
    """Demote specs whose sharded dimension does not tile the mesh axis
    to an explicit P() — LOUDLY (a warning naming the leaf), never
    silently: uneven sharding is unsupported by device_put, and an
    in-dim split that does not divide would be numerically wrong anyway
    (the GQA wk/wv fallback generalized to every leaf)."""
    if isinstance(tree, dict):
        return {k: _divisible_or_replicated(v, spec[k], mesh, name,
                                            path + (k,))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_divisible_or_replicated(v, s, mesh, name, path + (i,))
                for i, (v, s) in enumerate(zip(tree, spec))]
    shape = getattr(tree, "shape", None)
    if shape is None or spec == P():
        return spec
    if len(spec) > len(shape):
        logger.warning(
            "registry[%s]: spec %s for leaf %r has more entries than its "
            "rank %d — replicating", name, spec, path, len(shape))
        return P()
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        n = 1
        for ax in axes:
            n *= int(mesh.shape.get(ax, 1))
        if n > 1 and shape[i] % n:
            logger.warning(
                "registry[%s]: leaf %r dim %d (size %d) does not tile "
                "mesh axes %r (size %d) — replicating this leaf",
                name, path, i, shape[i], axes, n)
            return P()
    return spec


class ShardingRegistry:
    """Per-model mapping of every param/updater/activation leaf to a
    PartitionSpec on one named mesh.

    Construction goes through the classmethods — ``for_network`` (MLN and
    ComputationGraph, reusing ``tensor_parallel``'s Megatron-style layer
    rules when the mesh carries a ``model`` axis) and ``for_transformer``
    (``TransformerLM.param_specs``). Both expand the spec tree strictly
    against the model's live param tree: every leaf covered, unmapped
    leaves raise. The registry then answers every placement question the
    framework asks — param/updater shardings (``place_network``), batch
    placement (``batch_sharding``), fused-program ``out_shardings``
    (``epoch_out_shardings``), the serving KV-pool spec
    (``kv_pool_spec``), and the collective-axis
    declaration the contract checker enforces (``declared_axes``).
    """

    def __init__(self, mesh: Mesh, spec_tree, *, name: str = "model"):
        self.mesh = mesh
        self.name = name
        self.spec_tree = spec_tree

    # ------------------------------------------------------------------
    # constructors
    # ------------------------------------------------------------------
    @classmethod
    def for_network(cls, net, mesh: Mesh) -> "ShardingRegistry":
        """Registry for a MultiLayerNetwork or ComputationGraph: TP layer
        specs over ``model`` when the mesh carries that axis (>1), else
        explicit replicate-all. Strict against ``net.params``."""
        net._ensure_init()
        name = type(net).__name__
        if model_axis_size(mesh) > 1:
            raw = _network_specs(net)
        else:
            raw = _replicate_all_tree(net.params)
        expanded = _expand(net.params, raw, (), name)
        return cls(mesh,
                   _divisible_or_replicated(net.params, expanded, mesh,
                                            name),
                   name=name)

    @classmethod
    def for_transformer(cls, lm, mesh: Mesh, *,
                        shard_data_embed: bool = False) -> "ShardingRegistry":
        """Registry for a TransformerLM: the model's own Megatron
        ``param_specs`` over ``model`` when present, else replicate-all."""
        lm._ensure_init()
        if model_axis_size(mesh) > 1:
            raw = lm.param_specs(mesh=mesh,
                                 shard_data_embed=shard_data_embed)
        else:
            raw = _replicate_all_tree(lm.params)
        expanded = _expand(lm.params, raw, (), "TransformerLM")
        return cls(mesh,
                   _divisible_or_replicated(lm.params, expanded, mesh,
                                            "TransformerLM"),
                   name="TransformerLM")

    @classmethod
    def for_embedding_tables(cls, tables: Dict[str, Any], mesh: Mesh, *,
                             row_shard: bool = False,
                             name: str = "Word2Vec") -> "ShardingRegistry":
        """Registry for sparse embedding tables (word2vec's syn0/syn1neg,
        GloVe's w/wc): ``row_shard=True`` splits the VOCAB dim over
        ``model`` — ``P('model', None)``, the layout GSPMD partitions the
        fused skip-gram program's gathers/scatters around once a table
        outgrows one chip — else explicit replicate-all (the DP path:
        every device carries the tables, deltas all-reduce over
        ``data``). Same strictness as the network constructors: uneven
        vocab demotes LOUDLY via ``_divisible_or_replicated``."""
        if row_shard and model_axis_size(mesh) > 1:
            raw = {k: P(MODEL_AXIS, None) for k in tables}
        else:
            raw = _replicate_all_tree(tables)
        expanded = _expand(tables, raw, (), name)
        return cls(mesh,
                   _divisible_or_replicated(tables, expanded, mesh, name),
                   name=name)

    # ------------------------------------------------------------------
    # lookup
    # ------------------------------------------------------------------
    def spec_for(self, *path) -> P:
        """Strict leaf lookup by path (e.g. ``spec_for("0", "W")``)."""
        node = self.spec_tree
        for i, key in enumerate(path):
            try:
                node = node[key]
            except (KeyError, IndexError, TypeError):
                raise UnmappedLeafError(
                    f"registry[{self.name}]: no PartitionSpec at "
                    f"{tuple(path[:i + 1])!r}")
        if not isinstance(node, P):
            raise UnmappedLeafError(
                f"registry[{self.name}]: {tuple(path)!r} names a subtree, "
                "not a leaf")
        return node

    def leaf_specs(self, tree) -> List[P]:
        """Flat specs aligned with ``tree_flatten(tree)`` order; strict —
        a tree with leaves the registry does not cover raises."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        try:
            flat_spec = treedef.flatten_up_to(self.spec_tree)
        except (ValueError, KeyError, TypeError) as e:
            raise UnmappedLeafError(
                f"registry[{self.name}]: param tree does not match the "
                f"registered spec tree ({e})")
        for s in flat_spec:
            if not isinstance(s, P):
                raise UnmappedLeafError(
                    f"registry[{self.name}]: non-PartitionSpec entry "
                    f"{s!r} in expanded specs")
        return flat_spec

    def param_shardings(self, tree):
        """Pytree of NamedShardings matching ``tree``'s structure — what
        a jit's ``out_shardings`` pin or a placement walk consumes."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        specs = self.leaf_specs(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [named(self.mesh, s) for s in specs])

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def place(self, tree):
        """device_put every param leaf under its registered spec."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        specs = self.leaf_specs(tree)
        return jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(x, named(self.mesh, s))
            for x, s in zip(flat, specs)
        ])

    def state_shardings(self, state_tree):
        """NamedShardings for an updater/optimizer-state tree that NESTS
        (possibly zero or one level of dict, e.g. adam ``{m, v}``) below
        the param leaves. A state leaf inherits its param's spec when the
        ranks agree (the PR-14 rule tensor_parallel proved out); scalars,
        empties, and rank-mismatched leaves replicate."""
        return self._walk_state(state_tree, self.spec_tree, ())

    def _walk_state(self, tree, spec, path):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                sub = spec[k] if isinstance(spec, dict) and k in spec else spec
                if isinstance(spec, dict) and k not in spec and isinstance(v, (dict, list, tuple)):
                    raise UnmappedLeafError(
                        f"registry[{self.name}]: updater subtree at "
                        f"{path + (k,)!r} has no matching param spec")
                out[k] = self._walk_state(v, sub, path + (k,))
            return out
        if isinstance(tree, (list, tuple)):
            subs = (spec if isinstance(spec, (list, tuple))
                    and len(spec) == len(tree) else [spec] * len(tree))
            return [self._walk_state(v, s, path + (i,))
                    for i, (v, s) in enumerate(zip(tree, subs))]
        nd = getattr(tree, "ndim", None)
        size = getattr(tree, "size", None)
        if (nd in (None, 0) or size == 0 or not isinstance(spec, P)
                or len(spec) != nd):
            return named(self.mesh, P())
        return named(self.mesh, spec)

    def place_state(self, state_tree):
        """device_put an updater/optimizer-state tree mirroring params."""
        sh = self.state_shardings(state_tree)
        return jax.tree_util.tree_map(
            jax.device_put, state_tree, sh,
            is_leaf=lambda x: x is None)

    def place_network(self, net) -> "ShardingRegistry":
        """Place a network's full trainable state — params under the
        registered specs, updater state mirrored leaf-for-leaf, net state
        replicated — and stamp the registry on the network for the
        contract checker (``net._sharding_registry``)."""
        net.params = self.place(net.params)
        net.updater_state = self.place_state(net.updater_state)
        net.net_state = jax.device_put(net.net_state,
                                       replicated_sharding(self.mesh))
        net._sharding_registry = self
        return self

    def with_fsdp(self, params) -> "ShardingRegistry":
        """Compose FSDP (arXiv 2004.13336 weight-update sharding over
        ``data``) with the registered TP specs: leaves the registry
        replicates get their largest data-divisible dim sharded over
        ``data``; TP-sharded leaves keep their TP spec (sharding the
        same leaf over both axes would need a spec merge GSPMD cannot
        always honor — the composition stays memory-dominant either
        way)."""
        from deeplearning4j_tpu.parallel.fsdp import fsdp_spec

        flat, treedef = jax.tree_util.tree_flatten(params)
        specs = self.leaf_specs(params)
        composed = [
            fsdp_spec(x.shape, self.mesh) if s == P() else s
            for x, s in zip(flat, specs)
        ]
        return ShardingRegistry(
            self.mesh, jax.tree_util.tree_unflatten(treedef, composed),
            name=self.name + "+fsdp")

    # ------------------------------------------------------------------
    # activations / datasets / programs
    # ------------------------------------------------------------------
    def batch_sharding(self, ndim: int, *,
                       stacked: bool = False) -> NamedSharding:
        """Activation/batch placement: batch dim over ``data``."""
        return batch_sharding(self.mesh, ndim, stacked=stacked)

    def epoch_out_shardings(self, params_tree, state_tree, *,
                            guard: bool = False, metrics_stride: int = 0):
        """``out_shardings`` tuple for the fused epoch program: params
        and updater state pinned to their registered specs (donated
        buffers keep their layout across chunks), net state and the
        loss/trip/metrics histories replicated."""
        repl = replicated_sharding(self.mesh)
        out = (self.param_shardings(params_tree),
               self.state_shardings(state_tree), repl, repl)
        if guard:
            out = out + (repl,)
        if metrics_stride:
            out = out + (repl,)
        return out

    # ------------------------------------------------------------------
    # serving: the KV slot pool shares the model's mesh + specs
    # ------------------------------------------------------------------
    def kv_pool_spec(self, n_kv_heads: int) -> P:
        """Spec for a ``[L, S, T_max, Hkv, Dh]`` K/V pool: heads tile the
        ``model`` axis (the same Megatron head split the attention params
        use), so each TP shard holds ``Hkv/tp`` heads of every slot and
        the pool budget becomes per-shard. Falls back to replicated —
        loudly — when the kv heads do not tile the axis (the GQA
        fallback ``TransformerLM.param_specs`` mirrors: wk/wv replicate
        too, so the pool layout always matches what the projections
        emit)."""
        tp = model_axis_size(self.mesh)
        if tp > 1 and n_kv_heads % tp == 0:
            return P(None, None, None, MODEL_AXIS, None)
        if tp > 1:
            logger.warning(
                "KV pool TP fallback: %d kv heads do not tile the model "
                "axis (size %d) — pool stays replicated", n_kv_heads, tp)
        return P()

    # ------------------------------------------------------------------
    # contracts
    # ------------------------------------------------------------------
    @property
    def declared_axes(self) -> set:
        """Mesh axes this registry maps anything over — the ONLY axes a
        collective in this model's programs may reduce/permute over
        (``analysis/contracts.check_network_contracts`` enforces it).
        ``data`` is always declared (batch sharding is part of the
        registry's activation mapping); ``pipe`` is declared when the
        mesh carries it (stage params ride ``stage_spec``)."""
        axes = {DATA_AXIS}
        for s in jax.tree_util.tree_leaves(
                self.spec_tree,
                is_leaf=lambda x: isinstance(x, P)):
            if isinstance(s, P):
                for entry in s:
                    if entry is None:
                        continue
                    if isinstance(entry, (tuple, list)):
                        axes.update(entry)
                    else:
                        axes.add(entry)
        if pipe_axis_size(self.mesh) > 1:
            axes.add(PIPE_AXIS)
        return axes & set(self.mesh.axis_names) | {DATA_AXIS}

    def describe(self) -> Dict[str, Any]:
        """Artifact-ready summary (bench mesh_sweep embeds it)."""
        n_sharded = 0
        n_total = 0
        for s in jax.tree_util.tree_leaves(
                self.spec_tree, is_leaf=lambda x: isinstance(x, P)):
            if isinstance(s, P):
                n_total += 1
                if s != P():
                    n_sharded += 1
        return {
            "model": self.name,
            "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
            "declared_axes": sorted(self.declared_axes),
            "leaves": n_total,
            "sharded_leaves": n_sharded,
        }


def _network_specs(net):
    """TP spec tree for either network class, via tensor_parallel's
    Megatron layer rules. MLN's layers come indexed off the list conf;
    the graph's come named, walked in topological order so the
    column/row dense alternation follows dataflow."""
    from deeplearning4j_tpu.parallel.tensor_parallel import (
        param_specs_for_layers,
        param_specs_for_network,
    )

    conf = net.conf
    layers = getattr(conf, "layers", None)
    if isinstance(layers, dict):  # ComputationGraph: {name: LayerConf}
        order = [n for n in conf.topological_order if n in layers]
        order += [n for n in layers if n not in order]
        return param_specs_for_layers([(n, layers[n]) for n in order])
    return param_specs_for_network(conf)
