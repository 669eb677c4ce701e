"""Slot-based batched KV cache: the device-resident state of the server.

The single-request decoder (``TransformerLM.make_generate``) builds a
fresh ``prompt_len + max_new`` cache per call — right for one stream,
wrong for a server: S concurrent requests would run S separate programs
with S dispatches per emitted token. The slot pool turns that inside
out: ONE ``[L, S, T_max, Hkv, Dh]`` pair of K/V arrays lives in HBM for
the server's lifetime, each of the S slots holds one in-flight request
at its own decode position, and a single jitted step advances all of
them (``serving/engine.py``).

Slot lifecycle (the scheduler in ``serving/server.py`` drives it):

- **free** — garbage contents, cursor frozen. Safe by construction: the
  decode mask admits only keys ``<= cursor`` of slots whose rows anyone
  reads, and a freed slot's rows are never read.
- **prefill** — an admitted request's bucket-padded prompt runs one
  batched forward; its per-layer K/V land in ``[slot, 0:P_bucket)`` and
  the cursor starts at ``prompt_len`` (the pad tail ``[prompt_len,
  P_bucket)`` sits beyond the mask until generated tokens overwrite it).
- **decoding** — each step writes the consumed token's K/V at ``cursor``
  (one row per layer scattered into the pool itself:
  ``write_pool_rows``) then attends keys ``<= cursor``; the cursor
  advances by one.
- **retired** — the request finished; the slot returns to free with its
  stale contents in place (the next prefill overwrites them, and the
  mask keeps them unreachable meanwhile).

The decode loop's per-slot state lives beside the pool as DEVICE
``[S]`` arrays (``SlotKVCache.loop``): the cursor, the last token (the
next step's input), the tokens still owed (``remaining``) and the RNG
key. Every decode-family program takes them and returns them advanced —
a slot with ``remaining > 0`` is live, consumes its token, moves its
cursor on by one and owes one token fewer; a slot at zero freezes
itself — so between decode steps the host sends nothing and reads only
the token block. The scheduler's admission decisions come from its own
slot table (which request occupies which slot), not from these values;
the host writes them only where a request enters or leaves a slot
(``slot_admit``: after a prefill or a hand-off, and with nothing owed on
a deadline or a cancel).

State by layer kind (``TransformerLM(mixers=...)``): what a position or a
request leaves behind depends on the layer's mixer, and one slot holds all
of it side by side:

- ``attn`` layers: the K/V pools above, ``[L_attn, S, T_max, Hkv, Dh]``
  (heads wider than one lane tile: stored ``[L_attn, S, T_max Hkv, Dh]``,
  the same rows in the same order; ``pool_shape``);
- ``attn`` layers of a model that gives a window a layer
  (``TransformerLM(attn={"windows": ...})``): the layers with a window keep a
  **ring** of ``R`` rows a slot, ``[L_win, S, R, Hkv, Dh]`` (``kw``, ``vw``;
  ``ring_rows``: the window in whole kernel blocks), beside the ``T_max`` rows
  of the layers that attend their whole prefix, ``[L_full, S, T_max, Hkv,
  Dh]``. Position ``t`` lies at ring row ``t mod R``; at cursor ``c`` row
  ``r`` holds position ``c - ((c - r) mod R)`` (``ring_positions``), which
  the mask admits when it is not negative and within the window. A prefill
  writes the whole ring (the prompt's last ``min(P, R)`` rows where they
  belong); a frozen slot's step writes at its frozen cursor's row, which the
  next prefill rewrites with the rest;
- ``mla`` layers: latent rows, one ``[S, T_max, r + dr]`` array a layer
  (``latent``; each row padded with zeros to whole 128-lane tiles,
  ``latent_row_width``), written at the cursor and masked like keys;
- ``mla`` layers with a lightning indexer (``TransformerLM(indexers=)``'s
  ``"full"`` layers, ``models/dsa.py``): beside their latent rows, index
  keys, one ``[S, T_max, dI]`` array a layer (``index``), written and
  masked as the latent rows are; a ``"shared"`` layer keeps none. With
  pooled index keys (``dsa["pool"]`` = n) the array is ``[S, T_max / n,
  dI]``, one mean key a complete pool of n positions, and beside it the
  open pool's running sum in float32, ``[S, dI]`` a layer (``index_open``):
  a decode step adds its key to the sum and writes ``sum / n`` at the open
  pool's row, which no query scores before the pool's last position has
  made it the mean;
- ``kda`` and ``gdn`` layers (the delta-rule mixers): a recurrent matrix
  in float32 (``kda``: ``[S, H, dk, dk]``, a ``gdn`` layer's ``[S, Hv, dk,
  dk]``) and a convolution tail (``conv``: ``[S, K - 1, 3 H dk]``, a
  ``gdn`` layer's ``[S, K - 1, 2 Hk dk + Hv dk]``) a layer, in the layers'
  own order. They have no time axis: a prefill writes them **as of the
  prompt's length** (pad rows move no state, ``models/kda.py``), a decode
  step replaces a live slot's and leaves a frozen slot's as they are, and
  the next prefill into the slot overwrites them whole;
- ``ret`` layers (power retention, ``models/ret.py``): the state ``[S, Hkv,
  D, dh]`` in float32 among the recurrent matrices above (``D`` = 8,704 rows
  at heads of 128: 35.7 MB a slot and layer) and the normaliser ``[S, Hkv,
  dh, dh]`` in float32 (``norm``), written, stepped and overwritten as the
  delta-rule state is. A model whose every layer is one has **no array with
  a time axis**: no K/V pool, no ring, no latent rows; ``max_len`` then
  bounds positions (RoPE, admission), not memory.

The new kinds are lists of per-layer arrays (no layer axis to slice a slab
out of). ``pool_layout`` is the one description of all of it: the arrays
are built from it and ``kv_pool_nbytes`` sums it.

Store dtype (``kv_dtype=``): ``float32`` or ``bfloat16``, by default the
model's compute dtype. The pool is the dominant HBM term at high slot
counts; ``max_slots_in_budget`` prices a slot at either width.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from deeplearning4j_tpu.analysis.annotations import traced

__all__ = [
    "SlotKVCache",
    "resolve_kv_dtype",
    "kv_pool_nbytes",
    "pool_layout",
    "pool_shape",
    "ring_rows",
    "ring_positions",
    "attn_places",
    "max_slots_in_budget",
    "write_pool_rows",
    "advance_loop",
    "slot_admit",
]

_KV_DTYPES = ("float32", "bfloat16")
# the per-layer lists of a pool's state beside the K/V pools and rings
_LISTS = ("latent", "index", "index_open", "kda", "conv", "norm")
_ALIASES = {"f32": "float32", "bf16": "bfloat16"}


def resolve_kv_dtype(kv_dtype: Optional[str], model) -> str:
    """Canonical store-dtype name for the pool: an explicit ``kv_dtype``,
    else the model's compute dtype."""
    if kv_dtype is None:
        import jax.numpy as jnp

        return str(jnp.dtype(model.policy.compute_dtype))
    name = _ALIASES.get(str(kv_dtype).lower(), str(kv_dtype).lower())
    if name not in _KV_DTYPES:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} must be one of {_KV_DTYPES}")
    return name


def _elem_bytes(name: str) -> int:
    return {"float32": 4, "bfloat16": 2}.get(name, 4)


def ring_rows(model, max_len: int, kv_dtype: str) -> Optional[int]:
    """Positions ``R`` a slot's ring holds for each 'attn' layer that has a
    window, or None where the model keeps no ring: it gives no window a
    layer (``attn['windows']``; a model with the one ``attn_window`` keeps
    ``T_max`` rows a layer), or the ring would be no shorter than ``T_max``.
    ``R`` is the widest window, rounded up to whole key blocks of the decode
    kernel where it is longer than one (``pallas/decode_attention.py``: 512
    KiB of K), so that the kernel reads a ring as it reads a pool."""
    windows = [w for w in model.windows if w is not None]
    if not windows or not (model.attn or {}).get("windows"):
        return None
    from deeplearning4j_tpu.pallas.decode_attention import _BLOCK_BYTES

    block = max(_BLOCK_BYTES // (model.head_dim * _elem_bytes(kv_dtype))
                // model.num_kv_heads, 1)
    rows = max(windows)
    if rows > block:
        rows = -(-rows // block) * block
    return rows if rows < max_len else None


def ring_positions(cursors, rows: int):
    """``[..., R]``: the position each ring row holds once the token at
    ``cursors [...]`` is written, ``c - ((c - r) mod R)``; negative where the
    row has never been written. A numpy or a jax integer array."""
    c = cursors[..., None]
    return c - (c - np.arange(rows)) % rows


def attn_places(model, ring: bool):
    """Where each 'attn' layer's rows lie, in the layers' order: ``(name,
    place)`` with ``name`` ``"ring"`` (``kw``, ``vw``) for a layer with a
    window of a model that keeps a ring, else ``"kv"`` (``k``, ``v``), and
    ``place`` its index among that pool's layers."""
    seen = {"kv": 0, "ring": 0}
    out = []
    for i in model.layers_of("attn"):
        name = "ring" if ring and model.windows[i] is not None else "kv"
        out.append((name, seen[name]))
        seen[name] += 1
    return out


def _pool_dims(model, slots: int, max_len: int, ring: Optional[int] = None):
    """The logical axes of the K (or V) pool of ``T_max`` rows a layer and,
    where ``ring`` (``ring_rows``) says the model keeps one, of the ring:
    ``((L, S, T_max, Hkv, Dh), (L_win, S, R, Hkv, Dh) or None)``."""
    places = [name for name, _ in attn_places(model, ring is not None)]
    tail = (model.num_kv_heads, model.head_dim)
    return ((places.count("kv"), slots, max_len) + tail,
            (places.count("ring"), slots, ring) + tail if ring else None)


def pool_shape(dims, sharded: bool = False):
    """The shape a K (or V) pool of the logical ``dims`` = ``(L, S, T_max,
    Hkv, Dh)`` is stored in. XLA:TPU tiles the two minor axes ``[Hkv, Dh]``
    by ``(Hkv, 128)`` when there are few kv heads; with ``Dh`` of one lane
    tile that is the byte order of the ``[T_max Hkv, Dh]`` rows the decode
    kernel reads (``pallas/decode_attention.py``: a free reshape), with a
    wider head it is another order and the reshape copies the pool (2 x 2
    GiB a decode step at 64 slots x 32,768 x 2 heads of 256; PERF.md section
    6, PR 39). So a pool of wider heads is stored as those rows, ``(L, S,
    T_max Hkv, Dh)``: row ``t Hkv + h`` is position ``t`` of kv head ``h``.
    A mesh's head split keeps the five axes it is written for (it does not
    read through the kernel)."""
    n, s, t, hkv, dh = dims
    if dh > 128 and dh % 128 == 0 and not sharded:
        return (n, s, t * hkv, dh)
    return tuple(dims)


def _recurrent_dims(model, kind: str):
    """``(heads, dk, tail width)`` of a ``kda`` or ``gdn`` layer's state."""
    if kind == "kda":
        h, dk = model.num_heads, model.kda["head_dim"]
        return h, dk, 3 * h * dk
    from deeplearning4j_tpu.models.gdn import gdn_widths

    ck, cv = gdn_widths(model.gdn)
    return model.gdn["value_heads"], model.gdn["head_dim"], 2 * ck + cv


def pool_layout(model, slots: int, max_len: int, kv_dtype: str,
                sharded: bool = False, ring: bool = True) -> dict:
    """``{kind: [(shape, dtype name), ...]}`` of every array a slot pool
    of this model holds, by what it is: ``kv`` (the K and the V pool),
    ``ring`` (the K and the V ring of the layers with a window, where the
    model keeps one: ``ring_rows``; ``ring=False``: those layers keep
    ``T_max`` rows like the others), ``latent`` (one array an ``mla`` layer),
    ``index`` (one array an ``mla`` layer with a ``"full"`` indexer, and
    with pooled keys one more, the open pool's running sum: the keys of all
    the layers first, then the sums),
    ``recurrent`` (one a ``kda``, ``gdn`` or ``ret`` layer, in the layers'
    order), ``conv`` (one a ``kda`` or ``gdn`` layer) and ``normaliser``
    (one a ``ret`` layer). The last three are float32 or follow ``kv_dtype``
    as their layers' docstrings say: a recurrent state is float32 whatever
    the pool's rows are. A kind the model has no layer of is an empty list.
    ``sharded``: the pool lies over a mesh (``pool_shape``)."""
    out = {"kv": [], "ring": [], "latent": [], "index": [], "recurrent": [],
           "conv": [], "normaliser": []}
    dims, ring_dims = _pool_dims(
        model, slots, max_len,
        ring_rows(model, max_len, kv_dtype) if ring else None)
    if dims[0]:
        out["kv"] += [(pool_shape(dims, sharded), kv_dtype)] * 2
    if ring_dims:
        out["ring"] += [(pool_shape(ring_dims, sharded), kv_dtype)] * 2
    if model.mla:
        # a multi-token-prediction module's block keeps one more layer of
        # rows, the last of the list (``TransformerLM.n_layers``)
        out["latent"] = [((slots, max_len, latent_row_width(model)),
                          kv_dtype)] * model.n_layers("mla")
    if model.dsa:
        pool, full = model.dsa.get("pool", 1), model.indexers.count("full")
        out["index"] = [((slots, -(-max_len // pool), model.dsa["head_dim"]),
                         kv_dtype)] * full
        if pool > 1:
            out["index"] += [((slots, model.dsa["head_dim"]),
                              "float32")] * full
    for kind in model.mixers:
        if kind in ("kda", "gdn"):
            h, dk, width = _recurrent_dims(model, kind)
            taps = (model.kda if kind == "kda" else model.gdn)["conv"]
            out["recurrent"].append(((slots, h, dk, dk), "float32"))
            out["conv"].append(((slots, taps - 1, width), kv_dtype))
        elif kind == "ret":
            from deeplearning4j_tpu.models.ret import state_rows

            h, dh = model.num_kv_heads, model.head_dim
            out["recurrent"].append(
                ((slots, h, state_rows(dh), dh), "float32"))
            out["normaliser"].append(((slots, h, dh, dh), "float32"))
    return out


def latent_row_width(model) -> int:
    """Lanes a cached latent row takes: its ``r + dr`` numbers, then zeros
    up to the next multiple of 128. With rows of 576 XLA:TPU copies the
    whole array twice a decode step (once before the scatter, once into
    the layout its dot wants: 4.8 ms of a 26 ms step at 64 slots x 10,240;
    PERF.md section 6, PR 29); with rows of 640 it does neither."""
    width = model.mla["kv_lora_rank"] + model.mla["qk_rope_head_dim"]
    return -(-width // 128) * 128


def _layout_nbytes(arrays) -> int:
    return sum(math.prod(shape) * _elem_bytes(dtype)
               for shape, dtype in arrays)


def kv_pool_nbytes(model, slots: int, max_len: Optional[int] = None,
                   kv_dtype: Optional[str] = None, ring: bool = True) -> int:
    """Analytic device footprint of a slot pool: the K/V pool pair, the
    ring pair of a model that keeps one (``ring=False``: it does not), the
    latent rows, an indexer's keys and the recurrent state with
    its convolution tails or normalisers, whichever the model's layers keep — the
    serving term of the HBM budget model. Matches ``SlotKVCache.nbytes``
    exactly (asserted in tests)."""
    name = resolve_kv_dtype(kv_dtype, model)
    layout = pool_layout(model, slots, int(max_len or model.max_len), name,
                         ring=ring)
    return sum(_layout_nbytes(arrays) for arrays in layout.values())


def max_slots_in_budget(model, max_len: int, budget_bytes: int,
                        kv_dtype: Optional[str] = None) -> int:
    """How many concurrent slots an HBM budget can hold at ``max_len``
    context — the capacity planning answer (bfloat16 fits twice the slots
    of float32)."""
    per_slot = kv_pool_nbytes(model, 1, max_len, kv_dtype)
    return max(0, int(budget_bytes) // per_slot)


# ---------------------------------------------------------------------------
# the one write the decode-family programs make
# ---------------------------------------------------------------------------
@traced
def write_pool_rows(pool, layer, values, rows, positions):
    """Write ``values [S, q, Hkv, Dh]`` at ``(rows [S], positions
    [S, q])`` of layer ``layer`` (a Python int) into the whole
    ``[L, S, T, Hkv, Dh]`` pool (or its ``[L, S, T Hkv, Dh]`` rows:
    ``pool_shape``); returns the pool.

    It scatters into the pool itself — never into a copy of the layer's
    slab — so a program whose pool argument is donated updates that
    buffer in place and the only pool-sized value it produces is the
    buffer it was given: a plain scatter in the store dtype.
    Out-of-range scatter positions (frozen slots riding along near
    ``T_max``) are dropped by XLA's scatter semantics, never written."""
    import jax.numpy as jnp

    if pool.ndim == 4:      # rows of wide heads (``pool_shape``)
        hkv = values.shape[2]
        at = (layer, rows[:, None, None],
              positions[:, :, None] * hkv + jnp.arange(hkv))
    else:
        at = (layer, rows[:, None], positions)
    return pool.at[at].set(values.astype(pool.dtype))


# ---------------------------------------------------------------------------
# the decode loop's per-slot state: what a decode step and the host do to it
# ---------------------------------------------------------------------------
@traced
def advance_loop(loop, ntok, nkeys):
    """One decode step's effect on the loop state: a live slot
    (``remaining > 0``) takes its sampled token and split key, moves its
    cursor on by one and owes one token fewer; a slot at zero carries
    everything unchanged (its row computed garbage no one reads, written
    at its frozen cursor: a position beyond its mask that the next
    prefill rewrites)."""
    import jax.numpy as jnp

    act = loop["remaining"] > 0
    return {**loop,
            "cursors": jnp.where(act, loop["cursors"] + 1, loop["cursors"]),
            "tok": jnp.where(act, ntok, loop["tok"]),
            "remaining": jnp.where(act, loop["remaining"] - 1,
                                   loop["remaining"]),
            "keys": jnp.where(act[:, None], nkeys, loop["keys"])}


@traced
def slot_admit(loop, at, tok, key, draft=None):
    """A request enters a slot: ``at`` = ``[slot, cursor, remaining]``
    (int32, one transfer), ``tok`` its last emitted token (the next
    step's input), ``key`` its RNG stream, ``draft`` (a model that drafts
    from its own multi-token-prediction module: the loop state has
    ``draft``) the token proposed for the position after ``tok``'s. A
    request that leaves before its last token (deadline, cancel) is the
    same write with nothing owed: the slot freezes."""
    slot = at[0]
    new = {"cursors": loop["cursors"].at[slot].set(at[1]),
           "tok": loop["tok"].at[slot].set(tok),
           "remaining": loop["remaining"].at[slot].set(at[2]),
           "keys": loop["keys"].at[slot].set(key)}
    if "draft" in loop:
        new["draft"] = loop["draft"].at[slot].set(
            0 if draft is None else draft)
    return new


class SlotKVCache:
    """``[L, S, T_max, Hkv, Dh]`` K/V pools, the window layers' ``[L_win,
    S, R, Hkv, Dh]`` rings, the other layer kinds' state
    (latent rows, index keys, recurrent matrices, convolution tails,
    normalisers: ``pool_layout``)
    + the decode loop's device per-slot state (cursors, last tokens,
    tokens owed, RNG keys)."""

    # validate_cache_budget (monitor/memory.py) prices any cache as
    # nbytes/n_shard vs measured per-device bytes; the slot pool is
    # single-replica device state
    n_shard = 1

    def __init__(self, model, slots: int, max_len: Optional[int] = None,
                 kv_dtype: Optional[str] = None, registry=None,
                 ring: bool = True):
        """``registry=`` (a ``ShardingRegistry``) shards the pool over the
        mesh ``model`` axis with the SAME head split the attention params
        use — each TP shard holds ``Hkv/tp`` heads of every slot, so the
        pool budget (``nbytes / n_shard``) becomes per-shard.
        ``ring=False``: a model that gives a window a layer keeps ``T_max``
        rows for every layer and no ring (what the ring is compared
        with)."""
        import jax.numpy as jnp

        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        self.slots = int(slots)
        self.max_len = int(max_len or model.max_len)
        if self.max_len < 2:
            raise ValueError(f"max_len={self.max_len} must be >= 2")
        if (model.pos_encoding == "learned"
                and self.max_len > model.max_len):
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's learned "
                f"position table ({model.max_len}); use "
                "pos_encoding='rope' to serve past it")
        self.kv_dtype = resolve_kv_dtype(kv_dtype, model)
        if model.hybrid and registry is not None:
            raise ValueError(
                "a model with 'kda', 'gdn', 'ret' or 'mla' layers is served "
                "on one chip: the mesh's head split is written for a model "
                "whose every layer keeps K/V rows, not for latent rows, an "
                "indexer's keys or recurrent state beside or instead of them")
        # positions a window layer's ring holds; None: the model keeps none
        self.ring = ring_rows(model, self.max_len, self.kv_dtype) if (
            ring) else None
        if self.ring and registry is not None:
            raise ValueError(
                "a model that gives a window a layer (attn['windows']) is "
                "served on one chip: the mesh's head split is written for "
                "one K/V pool, not for a ring of rows beside it")
        layout = pool_layout(model, self.slots, self.max_len, self.kv_dtype,
                             sharded=registry is not None, ring=ring)
        self.latent, self.index, self.kda, self.conv, self.norm = (
            [jnp.zeros(shape, jnp.dtype(dt)) for shape, dt in layout[kind]]
            for kind in ("latent", "index", "recurrent", "conv",
                         "normaliser"))
        # pooled index keys: the open pools' running sums are a list of
        # their own in a program's state (``index_open``)
        full = model.indexers.count("full")
        self.index, self.index_open = self.index[:full], self.index[full:]
        # the pools' logical axes (L, S, T_max, Hkv, Dh), whichever shape
        # they are stored in
        self.pool_dims, self.ring_dims = _pool_dims(
            model, self.slots, self.max_len, self.ring)
        self.k = self.v = None      # no layer keeps keys and values
        if layout["kv"]:
            self.k, self.v = (jnp.zeros(shape, jnp.dtype(dt))
                              for shape, dt in layout["kv"])
        self.kw = self.vw = None    # no layer keeps a ring
        if layout["ring"]:
            self.kw, self.vw = (jnp.zeros(shape, jnp.dtype(dt))
                                for shape, dt in layout["ring"])
        # the decode loop's per-slot state, DEVICE arrays the decode
        # programs take and return advanced (not donated: the token block
        # a program returns is its ``tok``, which the host reads one step
        # later). cursors: the position the NEXT consumed token's K/V
        # lands at (== the absolute position of the last emitted,
        # not-yet-consumed token); tok: that token; remaining: tokens
        # still owed (> 0 = live); keys: the slot's RNG stream. The host
        # writes them through ``slot_admit`` only.
        import jax

        key = jax.random.PRNGKey(0)
        self.loop = {
            "cursors": jnp.zeros(self.slots, jnp.int32),
            "tok": jnp.zeros(self.slots, jnp.int32),
            "remaining": jnp.zeros(self.slots, jnp.int32),
            "keys": jnp.zeros((self.slots,) + key.shape, key.dtype)}
        if model.mtp:
            # the module's proposal for position cursor + 1, made at the end
            # of the round (or the prefill) before: what a round verifies
            self.loop["draft"] = jnp.zeros(self.slots, jnp.int32)
        self.registry = registry
        if registry is not None:
            from jax.sharding import PartitionSpec as P

            from deeplearning4j_tpu.parallel.sharding_registry import (
                model_axis_size, named, replicated_sharding)

            pool_spec = registry.kv_pool_spec(model.num_kv_heads)
            pool = named(registry.mesh, pool_spec)
            self.k = jax.device_put(self.k, pool)
            self.v = jax.device_put(self.v, pool)
            self.loop = jax.device_put(
                self.loop, replicated_sharding(registry.mesh))
            if pool_spec != P():
                # instance attr shadows the class default 1:
                # validate_cache_budget prices nbytes/n_shard per device
                self.n_shard = model_axis_size(registry.mesh)

    @property
    def state(self) -> dict:
        """The pool pytree a jitted program consumes (and is donated):
        ``{k, v}``, a ring's ``{kw, vw}`` and the other layer kinds' lists. The
        engine's programs write into these buffers and hand them back
        (``install``); once donated, the arrays returned here are dead."""
        st = {} if self.k is None else {"k": self.k, "v": self.v}
        if self.kw is not None:
            st.update(kw=self.kw, vw=self.vw)
        for name in _LISTS:
            if getattr(self, name):
                st[name] = list(getattr(self, name))
        return st

    def install(self, state: dict) -> None:
        """Install the pool state a jitted program returned: the
        buffers ``state`` donated to it, updated in place — the same
        device memory, not a copy of it."""
        self.k, self.v = state.get("k"), state.get("v")
        self.kw, self.vw = state.get("kw"), state.get("vw")
        for name in _LISTS:
            setattr(self, name, list(state.get(name, ())))

    @property
    def nbytes(self) -> int:
        """Device footprint of the pool state (capacity planning: the
        serving analogue of the epoch cache's HBM budget)."""
        return sum(self.nbytes_by_kind.values())

    @property
    def nbytes_by_kind(self) -> dict:
        """``nbytes`` apart: ``kv`` (the K/V pools), ``ring`` (the window
        layers' rings), ``latent``, ``index``, ``recurrent``, ``conv``,
        ``normaliser`` (``pool_layout``'s kinds)."""
        kv = [a for a in (self.k, self.v) if a is not None]
        kinds = [("kv", kv), ("latent", self.latent),
                 ("recurrent", self.kda), ("conv", self.conv)]
        if self.index:      # only a model with an indexer names the kind
            kinds.insert(2, ("index", self.index + self.index_open))
        if self.kw is not None:     # and only one with a ring this one
            kinds.insert(1, ("ring", [self.kw, self.vw]))
        if self.norm:               # and only one with 'ret' layers this one
            kinds.append(("normaliser", self.norm))
        return {kind: sum(int(a.nbytes) for a in arrays)
                for kind, arrays in kinds}

    @property
    def per_slot_nbytes(self) -> int:
        """The pool bytes one concurrent request costs."""
        return self.nbytes // self.slots
