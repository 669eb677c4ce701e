"""Slot handoff: move one request's KV state between replicas.

The prefill/decode split (the stretch of ROADMAP item 3's fleet phase,
after the DistBelief/TensorFlow serving-split lineage) separates the two
phases with opposite hardware profiles: prefill is one big compute-bound
forward over the whole prompt, decode is a long memory-bound stream of
single-token steps. A ``prefill`` replica computes the prompt's K/V into
a scratch slot, exports the slot as a host-resident
:class:`SlotHandoff` — ``(kv_slab, cursor, rng_key)`` plus the first
sampled token — and a ``decode`` replica installs it into a free slot of
its own pool and streams the rest.

Device programs: ``_slot_export_impl`` / ``_slot_import_impl`` are
``@traced`` hot roots (``HOT_PATH_REGISTRY``) compiled once per engine
through the engine's bounded program cache — the export's host readback
(the slab leaves the device by definition of a handoff) happens OUTSIDE
the traced bodies, in :func:`export_slot`, where dl4j-lint's host-sync
rule can see it is not on the per-token path: handoffs happen once per
request, prefill-side, never inside the decode loop.

Numerics: the installed slab is bit-identical to what a local prefill of
the same prompt would have written (same program, same math; the export/
import round trip is a pure gather/scatter), so a handed-off greedy
stream is token-identical to a locally-served one — asserted in
tests/test_serving_fleet.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

from deeplearning4j_tpu.analysis.annotations import traced

__all__ = ["SlotHandoff", "export_slot", "export_live_slot",
           "install_slot", "make_install"]


@traced
def _slot_export_impl(state, slot):
    """Gather one slot's K/V out of the pool: ``[L, S, T, Hkv, Dh]``
    pools yield ``[L, T, Hkv, Dh]`` slabs. ``slot`` is traced — one
    compiled program per engine, any slot."""
    import jax.numpy as jnp

    return {name: jnp.take(pool, slot, axis=1)
            for name, pool in state.items()}


@traced
def _slot_import_impl(state, slabs, slot):
    """Scatter a handed-off slab back into pool slot ``slot`` (the
    inverse of ``_slot_export_impl``); every other slot's K/V carries
    unchanged (the pool buffers are donated)."""
    from jax import lax

    out = {}
    for name, pool in state.items():
        slab = slabs[name][:, None]          # re-insert the slot axis
        start = (0, slot) + (0,) * (pool.ndim - 2)
        out[name] = lax.dynamic_update_slice(
            pool, slab.astype(pool.dtype), start)
    return out


@dataclass
class SlotHandoff:
    """One prefilled request's portable decode state: the host-side
    ``(kv_slab, cursor, rng_key)`` package a prefill replica ships to a
    decode replica's free slot, plus the first token (sampled at
    prefill, so TTFT is stamped prefill-side) and the compatibility
    fields the install validates against the target pool."""

    slabs: Dict[str, np.ndarray]   # k/v [L, T, Hkv, Dh]
    # next write position: prompt_len for a prefill handoff,
    # prompt_len + emitted for a drain-time mid-stream migration
    cursor: int
    key: np.ndarray                # per-slot RNG key, mid-chain
    # the last token fed back into decode: the prefill's first sampled
    # token, or — mid-stream — the newest token the source emitted
    first_token: int
    kv_dtype: str
    max_len: int

    @property
    def nbytes(self) -> int:
        return int(sum(s.nbytes for s in self.slabs.values()))


def _kv_only(engine) -> None:
    """A slab is K/V rows: the hand-off does not carry
    a 'kda' layer's recurrent state or an 'mla' layer's latent rows."""
    if engine.model.hybrid:
        raise ValueError(
            "the fleet hand-off carries K/V slabs only: a model with 'kda', "
            "'gdn', 'ret' or 'mla' layers keeps recurrent state and latent "
            "rows that export_slot / install_slot do not move")


def export_slot(engine, slot: int) -> Dict[str, np.ndarray]:
    """Pull one slot's pool state to host numpy (the handoff's wire
    format). The readback is sanctioned here — once per request at the
    prefill/decode boundary, never per token."""
    import jax
    import jax.numpy as jnp

    _kv_only(engine)
    run = engine._program(
        ("handoff_export", engine.slots),
        lambda: jax.jit(_slot_export_impl))
    device = run(engine.cache.state, jnp.asarray(slot, jnp.int32))
    return {name: np.asarray(v) for name, v in device.items()}


def install_slot(engine, slot: int, handoff: SlotHandoff):
    """Land a handoff's slab into ``slot`` of ``engine``'s pool; returns
    the device RNG key to continue the stream with (the slot's loop
    state — cursor, last token, tokens owed, key — is the admitting
    server's to write: ``DecodeEngine.admit_slot``). Validates pool
    compatibility — a silent dtype or capacity mismatch would decode
    garbage with no error."""
    import jax
    import jax.numpy as jnp

    _kv_only(engine)
    if handoff.kv_dtype != engine.kv_dtype:
        raise ValueError(
            f"handoff kv_dtype={handoff.kv_dtype!r} != target pool "
            f"{engine.kv_dtype!r}")
    if handoff.max_len != engine.max_len:
        raise ValueError(
            f"handoff max_len={handoff.max_len} != target pool "
            f"max_len={engine.max_len}")
    run = engine._program(
        ("handoff_import", engine.slots),
        lambda: jax.jit(_slot_import_impl, donate_argnums=(0,)))
    state = run(engine.cache.state,
                {k: jnp.asarray(v) for k, v in handoff.slabs.items()},
                jnp.asarray(slot, jnp.int32))
    engine.cache.install(state)
    return jnp.asarray(handoff.key)


def export_live_slot(server, slot: int) -> SlotHandoff:
    """Package a RUNNING slot's full decode state for migration — the
    graceful-drain counterpart of the prefill-side handoff. The slab
    covers every token decoded so far (cursor = prompt_len + emitted),
    the RNG key is the slot's mid-chain key, and ``first_token`` is the
    newest emitted token — installing this on a survivor continues the
    stream with ZERO recompute and zero lost tokens, where failover
    would re-prefill prompt + emitted from scratch. The server's unread
    token block is read first (``flush``): the device's loop state is
    one dispatch ahead of the request's tokens until then."""
    server.flush()
    engine = server.engine
    cursor, tok, key = engine.slot_state(slot)
    return SlotHandoff(
        slabs=export_slot(engine, slot), cursor=cursor, key=key,
        first_token=tok, kv_dtype=engine.kv_dtype, max_len=engine.max_len)


def make_install(handoff: SlotHandoff):
    """The ``install(engine, slot) -> (last_token, cursor, key)``
    callable ``DecodeServer.admit_external`` runs at the step boundary
    that claims a free slot."""

    def install(engine, slot):
        key = install_slot(engine, slot, handoff)
        return handoff.first_token, handoff.cursor, key

    return install
