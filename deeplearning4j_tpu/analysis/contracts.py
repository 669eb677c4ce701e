"""Program-contract checker: assert invariants of the cached fused programs.

dl4j-lint (analysis/rules.py) checks the SOURCE; this module checks the
PROGRAMS — the jaxpr and lowered StableHLO of every entry in a network's
``_epoch_steps`` cache, at test time, against the contract the whole
fused pipeline (PRs 3–6) silently relies on:

1. **No host callbacks.** ``pure_callback`` / ``io_callback`` /
   ``debug_callback`` primitives anywhere in the program would serialize
   E*N fused steps behind host round-trips (and break donation). The
   jaxpr must be free of them, recursively through scan/cond/pjit.
2. **Donation actually applied.** ``donate_argnums=(0, 1, 2)`` is a
   request, not a guarantee — XLA drops aliasing it cannot pair. Every
   params/updater/net-state leaf must carry an input-output alias
   (``tf.aliasing_output`` / ``jax.buffer_donor``) in the lowered module,
   or chunk k+1 doubles the training state's HBM footprint.
3. **Collectives stay on declared mesh axes.** Any ``psum``/
   ``all_gather``/... over an axis outside the declared set means the
   program grew a dependency on topology the caller never declared
   (single-device programs must contain none at all).
4. **Outputs match the program key.** The trip history is present iff
   the sentinel is compiled in; the ``[E, N, 4]`` metrics history iff
   telemetry is; shapes/dtypes as documented in
   ``nn/train_step.py``'s ``epoch_run_fn``.

``check_network_contracts(net, cache)`` runs all four against every
cached program; tier-1 wires it over FF/RNN/graph x {plain, accum,
guard, telemetry} in tests/test_analysis.py. Checks trace/lower with
``jax.ShapeDtypeStruct`` specs — no device execution, no donation of
real buffers.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "ContractViolation",
    "CALLBACK_PRIMITIVES",
    "COLLECTIVE_PRIMITIVES",
    "callback_primitives",
    "collective_axes",
    "donated_arg_indices",
    "fused_program_specs",
    "check_fused_program",
    "check_network_contracts",
    "embedding_program_specs",
    "check_embedding_contracts",
]


class ContractViolation(AssertionError):
    """One or more fused-program contract checks failed."""

    def __init__(self, violations: Sequence[str]):
        self.violations = list(violations)
        super().__init__(
            "fused-program contract violated:\n  - "
            + "\n  - ".join(self.violations))


CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback",
})
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
    "pgather", "psum_scatter",
})


# ---------------------------------------------------------------------------
# jaxpr traversal
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    """Every equation in ``jaxpr``, recursing through call/control-flow
    sub-jaxprs (scan bodies, cond branches, pjit calls, shard_map...)."""
    from jax.extend import core as jcore

    seen = set()
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        if isinstance(jx, jcore.ClosedJaxpr):
            jx = jx.jaxpr
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn
            for val in eqn.params.values():
                stack.extend(_sub_jaxprs(val))


def _sub_jaxprs(val):
    from jax.extend import core as jcore

    if isinstance(val, (jcore.Jaxpr, jcore.ClosedJaxpr)):
        return [val]
    if isinstance(val, (list, tuple)):
        out = []
        for v in val:
            out.extend(_sub_jaxprs(v))
        return out
    return []


def callback_primitives(jaxpr) -> List[str]:
    """Names of host-callback primitives present in the program."""
    return sorted({eqn.primitive.name for eqn in _iter_eqns(jaxpr)
                   if eqn.primitive.name in CALLBACK_PRIMITIVES})


def collective_axes(jaxpr) -> Dict[str, List[str]]:
    """axis name -> sorted list of collective primitives using it."""
    out: Dict[str, set] = {}
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
            continue
        axes: List[str] = []
        for key in ("axes", "axis_name", "axis"):
            val = eqn.params.get(key)
            if val is None:
                continue
            if isinstance(val, (tuple, list)):
                axes.extend(str(a) for a in val)
            else:
                axes.append(str(val))
        for ax in axes or ["<unnamed>"]:
            out.setdefault(ax, set()).add(eqn.primitive.name)
    return {ax: sorted(prims) for ax, prims in out.items()}


# ---------------------------------------------------------------------------
# lowered-module inspection (donation)
# ---------------------------------------------------------------------------

_ARG_HEAD_RE = re.compile(r"%arg(\d+):")
_DONOR_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def donated_arg_indices(lowered_text: str) -> List[int]:
    """Flat argument indices carrying an input-output alias / donor mark
    in the lowered StableHLO's ``@main`` signature."""
    m = re.search(r"func\.func(?: public)? @main\((?P<sig>.*?)\)\s*->",
                  lowered_text, re.DOTALL)
    sig = m.group("sig") if m else lowered_text
    # Everything between one "%argN:" and the next belongs to argN —
    # including its attr dict. Scanning per-chunk (not regexing the attr
    # braces) survives nested/quoted braces like
    # ``mhlo.sharding = "{devices=[8,1]<=[8]}"`` on sharded programs.
    heads = list(_ARG_HEAD_RE.finditer(sig))
    out = []
    for i, am in enumerate(heads):
        end = heads[i + 1].start() if i + 1 < len(heads) else len(sig)
        chunk = sig[am.end():end]
        if any(marker in chunk for marker in _DONOR_MARKERS):
            out.append(int(am.group(1)))
    return sorted(set(out))


# ---------------------------------------------------------------------------
# spec construction + the checks
# ---------------------------------------------------------------------------


def _specs_of(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                       jnp.result_type(a)), tree)


def fused_program_specs(net, cache, epochs: int = 2):
    """``jax.ShapeDtypeStruct`` argument specs matching the fused chunk
    program's signature ``(params, updater, net_state, iteration0,
    lr_scale_host, xs, ys, fms, lms, epoch_keys)`` for ``epochs``
    epochs over ``cache``."""
    import jax
    import jax.numpy as jnp

    xs, ys, fms, lms = cache.stacks
    rng = net._rng
    key_spec = jax.ShapeDtypeStruct((epochs,) + tuple(jnp.shape(rng)),
                                    jnp.result_type(rng))
    return (
        _specs_of(net.params),
        _specs_of(net.updater_state),
        _specs_of(net.net_state),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        _specs_of(xs),
        _specs_of(ys),
        None if fms is None else _specs_of(fms),
        _specs_of(lms),
        key_spec,
    )


def _trace_jaxpr(fn, specs):
    """ClosedJaxpr of a (possibly jitted) callable on spec args."""
    import jax

    trace = getattr(fn, "trace", None)
    if trace is not None:
        try:
            return trace(*specs).jaxpr
        except (AttributeError, TypeError):
            pass
    return jax.make_jaxpr(fn)(*specs)


def check_fused_program(fn, specs, *, guard: bool, stride: int,
                        epochs: int, n_batches: int,
                        n_state_leaves: Optional[int] = None,
                        allowed_axes: Sequence[str] = (),
                        expect_donation: bool = True) -> List[str]:
    """All contract checks against one fused program; returns violation
    strings (empty = contract holds)."""
    import jax

    violations: List[str] = []
    jaxpr = _trace_jaxpr(fn, specs)

    # 1. no host callbacks inside the program
    cbs = callback_primitives(jaxpr)
    if cbs:
        violations.append(
            f"host callback primitive(s) {cbs} inside the fused program "
            "— each fused step would round-trip to the host")

    # 2. collectives only over declared axes
    allowed = set(allowed_axes)
    for ax, prims in sorted(collective_axes(jaxpr).items()):
        if ax not in allowed:
            violations.append(
                f"collective(s) {prims} over undeclared mesh axis "
                f"'{ax}' (declared: {sorted(allowed) or 'none'})")

    # 3. donation applied to every params/updater/net-state leaf
    if expect_donation:
        if n_state_leaves is None:
            n_state_leaves = len(jax.tree_util.tree_leaves(specs[:3]))
        try:
            text = fn.lower(*specs).as_text()
        except Exception as exc:  # lowering failed — report, don't crash
            violations.append(f"could not lower program for donation "
                              f"check: {exc!r}")
        else:
            donated = set(donated_arg_indices(text))
            missing = [i for i in range(n_state_leaves)
                       if i not in donated]
            if missing:
                violations.append(
                    f"{len(missing)}/{n_state_leaves} training-state "
                    f"leaves lack an input-output alias (flat arg "
                    f"indices {missing[:8]}{'...' if len(missing) > 8 else ''}) "
                    "— donate_argnums was dropped and chunk k+1 doubles "
                    "the state footprint")

    # 4. outputs match the program key (trips iff guard, metrics iff
    #    stride, documented shapes)
    try:
        out = jax.eval_shape(fn, *specs)
    except Exception as exc:
        violations.append(f"could not eval_shape program: {exc!r}")
        return violations
    expected_len = 4 + (1 if guard else 0) + (1 if stride else 0)
    if not isinstance(out, tuple) or len(out) != expected_len:
        violations.append(
            f"program returns {len(out) if isinstance(out, tuple) else type(out).__name__} "
            f"outputs, contract says {expected_len} "
            f"(guard={guard}, metrics_stride={stride})")
        return violations
    hist = out[3]
    if tuple(hist.shape) != (epochs, n_batches):
        violations.append(
            f"loss history shape {tuple(hist.shape)} != "
            f"({epochs}, {n_batches})")
    if guard:
        trips = out[4]
        if tuple(trips.shape) != (epochs, n_batches):
            violations.append(
                f"sentinel trip history shape {tuple(trips.shape)} != "
                f"({epochs}, {n_batches})")
        if trips.dtype != jax.numpy.bool_:
            violations.append(
                f"sentinel trip history dtype {trips.dtype} != bool")
    if stride:
        mets = out[-1]
        if (len(mets.shape) != 3
                or tuple(mets.shape[:2]) != (epochs, n_batches)
                or mets.shape[2] != 4):
            violations.append(
                f"metrics history shape {tuple(mets.shape)} != "
                f"({epochs}, {n_batches}, 4)")
    # state pytrees must round-trip (donor pairing relies on it)
    in_def = jax.tree_util.tree_structure(specs[:3])
    out_def = jax.tree_util.tree_structure(out[:3])
    if in_def != out_def:
        violations.append(
            "params/updater/net-state output pytree structure differs "
            "from the input structure — donation cannot pair buffers")
    return violations


def embedding_program_specs(w2v, cache, epochs: int = 2):
    """``jax.ShapeDtypeStruct`` argument specs for the fused skip-gram
    chunk program (``nlp/epoch_kernels.make_skipgram_chunk``):
    ``(syn0, syn1neg, it0, lr0, min_lr, planned, tokens, mask,
    keep_prob, table, epoch_keys[E])``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    key_spec = jax.ShapeDtypeStruct((epochs,) + tuple(jnp.shape(key)),
                                    jnp.result_type(key))
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    return (
        _specs_of(w2v.syn0),
        _specs_of(w2v.syn1neg),
        scalar, scalar, scalar, scalar,
        _specs_of(cache.tokens),
        _specs_of(cache.mask),
        _specs_of(cache.keep_prob),
        _specs_of(cache.table),
        key_spec,
    )


def check_embedding_contracts(w2v, cache, *, epochs: int = 2,
                              allowed_axes: Optional[Sequence[str]] = None,
                              raise_on_violation: bool = True
                              ) -> Dict[Tuple, List[str]]:
    """Contract-check every cached fused skip-gram program on a
    ``Word2Vec``/``DistributedWord2Vec`` (``_epoch_steps``, populated by
    ``fit_epochs``): no host callbacks, collectives only over axes the
    table registry declared (or the cache mesh's axes when the tables
    were never registered; none at all single-device), donation applied
    to both tables, outputs ``(syn0, syn1neg, hist[E, n_batches])``.
    Empty ``_epoch_steps`` raises ValueError — a vacuous pass must never
    look like a checked one."""
    import jax

    programs = getattr(w2v, "_epoch_steps", None) or {}
    if not programs:
        raise ValueError(
            "no cached fused skip-gram programs on %r (_epoch_steps is "
            "empty) — run fit_epochs first" % type(w2v).__name__)
    if allowed_axes is None:
        registry = getattr(w2v, "_sharding_registry", None)
        if registry is not None:
            allowed_axes = tuple(sorted(registry.declared_axes))
        elif getattr(cache, "mesh", None) is not None:
            allowed_axes = tuple(cache.mesh.axis_names)
        else:
            allowed_axes = ()
    specs = embedding_program_specs(w2v, cache, epochs)
    results: Dict[Tuple, List[str]] = {}
    for key, fn in sorted(programs.items(), key=repr):
        violations: List[str] = []
        jaxpr = _trace_jaxpr(fn, specs)
        cbs = callback_primitives(jaxpr)
        if cbs:
            violations.append(
                f"host callback primitive(s) {cbs} inside the fused "
                "skip-gram program")
        allowed = set(allowed_axes)
        for ax, prims in sorted(collective_axes(jaxpr).items()):
            if ax not in allowed:
                violations.append(
                    f"collective(s) {prims} over undeclared mesh axis "
                    f"'{ax}' (declared: {sorted(allowed) or 'none'})")
        try:
            text = fn.lower(*specs).as_text()
        except Exception as exc:
            violations.append(
                f"could not lower program for donation check: {exc!r}")
        else:
            donated = set(donated_arg_indices(text))
            missing = [i for i in (0, 1) if i not in donated]
            if missing:
                violations.append(
                    f"table arg(s) {missing} lack an input-output alias "
                    "— donation was dropped and each chunk doubles the "
                    "tables' HBM footprint")
        try:
            out = jax.eval_shape(fn, *specs)
        except Exception as exc:
            violations.append(f"could not eval_shape program: {exc!r}")
            out = None
        if out is not None:
            if not isinstance(out, tuple) or len(out) != 3:
                violations.append(
                    "program must return (syn0, syn1neg, hist), got "
                    f"{len(out) if isinstance(out, tuple) else type(out).__name__}")
            else:
                for i, (o, ref) in enumerate(zip(out[:2],
                                                 (w2v.syn0, w2v.syn1neg))):
                    if tuple(o.shape) != tuple(ref.shape):
                        violations.append(
                            f"output {i} shape {tuple(o.shape)} != table "
                            f"shape {tuple(ref.shape)}")
                hist = out[2]
                if tuple(hist.shape) != (epochs, cache.n_batches):
                    violations.append(
                        f"loss history shape {tuple(hist.shape)} != "
                        f"({epochs}, {cache.n_batches})")
        results[key] = [f"program {key}: {v}" for v in violations]
    flat = [v for vs in results.values() for v in vs]
    if flat and raise_on_violation:
        raise ContractViolation(flat)
    return results


def check_network_contracts(net, cache, *, epochs: int = 2,
                            allowed_axes: Optional[Sequence[str]] = None,
                            expect_donation: bool = True,
                            raise_on_violation: bool = True,
                            require_programs: bool = True,
                            registry=None
                            ) -> Dict[Tuple, List[str]]:
    """Contract-check EVERY cached fused program on ``net`` (a network or
    a ``ParallelWrapper`` — the wrapper's SPMD programs cache on the
    wrapper itself, keyed identically ``(shuffle, K, guard, stride)``).
    Returns {program key: violations}; raises :class:`ContractViolation`
    listing every violation unless ``raise_on_violation=False``. An empty
    or missing ``_epoch_steps`` cache raises :class:`ValueError` unless
    ``require_programs=False`` — a vacuous pass must never look like a
    checked one.

    The declared-axes set for check 3 resolves, in order: explicit
    ``allowed_axes=``; ``registry=`` (a ``ShardingRegistry``); the
    registry the last registry-driven placement stamped on the network
    (``net._sharding_registry`` — TP/PP programs may then ONLY reduce
    over axes the registry actually mapped something to, a strictly
    tighter set than the mesh's axis names); finally every axis of the
    net/cache mesh."""
    network = getattr(net, "network", net)
    programs = getattr(net, "_epoch_steps", None) or {}
    if not programs and require_programs:
        raise ValueError(
            "no cached fused programs on %r (_epoch_steps is empty or "
            "missing) — run fit_epochs first, or pass "
            "require_programs=False to accept an empty check"
            % type(net).__name__)
    if allowed_axes is None:
        if registry is None:
            registry = (getattr(net, "_registry", None)
                        or getattr(network, "_sharding_registry", None))
        if registry is not None:
            allowed_axes = tuple(sorted(registry.declared_axes))
        else:
            mesh = (getattr(net, "mesh", None)
                    or getattr(cache, "mesh", None))
            allowed_axes = tuple(mesh.axis_names) if mesh is not None else ()
    specs = fused_program_specs(network, cache, epochs) if programs else None
    results: Dict[Tuple, List[str]] = {}
    for key, fn in sorted(programs.items(), key=repr):
        shuffle, accum, guard, stride = key
        results[key] = [
            f"program {key}: {v}" for v in check_fused_program(
                fn, specs, guard=bool(guard), stride=int(stride),
                epochs=epochs, n_batches=cache.n_batches,
                allowed_axes=allowed_axes,
                expect_donation=expect_donation)]
    flat = [v for vs in results.values() for v in vs]
    if flat and raise_on_violation:
        raise ContractViolation(flat)
    return results
