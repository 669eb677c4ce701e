"""The Pallas decode-attention kernel that reads the serving KV pool in
place (``pallas/decode_attention.py``), and the engine's choice of read.

On the CPU the kernel runs through the Pallas interpreter; what Mosaic
makes of it at the benchmark's widths is compiled for a described v5e
without a chip (one file for such compiles: only one xdist worker may
load the TPU's library).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.ops.attention import grouped_query_attention
from deeplearning4j_tpu.pallas.decode_attention import (
    _work_list, key_block_span, pool_block_rows, pool_decode_attention)
from deeplearning4j_tpu.serving import SlotKVCache
from deeplearning4j_tpu.serving import engine as eng


def _mask(positions, t_max, window):
    live = jnp.arange(t_max)[None, None, :] <= positions[:, :, None]
    if window is not None:
        live &= (jnp.arange(t_max)[None, None, :]
                 > positions[:, :, None] - window)
    return live


class TestPoolDecodeAttention:
    # (layers, slots, t_max, kv heads, query heads, queries, window)
    SHAPES = [
        (2, 3, 64, 2, 4, 1, None),      # GQA decode, several key blocks
        (2, 3, 64, 2, 6, 1, 24),        # window: leading blocks skipped
        (2, 4, 64, 1, 4, 3, None),      # MQA verify, ragged offsets
        (3, 2, 96, 2, 4, 2, 40),        # verify under a window
        (2, 2, 64, 4, 4, 1, None),      # MHA: one query head a kv head
        (2, 2, 48, 3, 6, 1, None),      # kv heads not a power of two
        (2, 3, 32, 16, 16, 1, None),    # 16 kv heads, full causal (OLMoE)
        (2, 3, 32, 16, 16, 2, None),    # ... under a verify
    ]

    @pytest.mark.parametrize("live", ["all", "some"])
    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_the_xla_op_over_the_slab(self, rng, shape, dtype, tol,
                                              live):
        """The kernel over layer ``li`` of the pool is
        ``grouped_query_attention`` over ``pool[li]`` under the same
        mask: slot 0 sits at position 0 (one key), the last slot at the
        pool's end, the rest anywhere. ``live`` = some: the middle slots
        hold no request — their keys, NaN here, are neither fetched nor
        multiplied and their rows are exact zeros, while the live rows
        are bit for bit what the kernel gives with every slot live."""
        n_layers, s_, t_max, hkv, h, nq, window = shape
        dh = 128
        pool_k, pool_v = (
            jnp.asarray(rng.normal(size=(n_layers, s_, t_max, hkv, dh)),
                        dtype) for _ in range(2))
        q = jnp.asarray(rng.normal(size=(s_, nq, h, dh)), dtype)
        first = rng.integers(0, t_max - nq, size=s_)
        first[0], first[-1] = 0, t_max - nq
        positions = jnp.asarray(first[:, None] + np.arange(nq)[None, :],
                                jnp.int32)
        li = n_layers - 1
        want = grouped_query_attention(
            q, pool_k[li], pool_v[li], mask=_mask(positions, t_max, window))
        kernel = functools.partial(
            pool_decode_attention, window=window, block_rows=16,
            interpret=True)
        got = kernel(q, pool_k, pool_v, li, positions)
        if live == "some":
            dead = np.arange(s_)[1:-1]
            every, got = got, kernel(
                q, pool_k.at[:, dead].set(jnp.nan),
                pool_v.at[:, dead].set(jnp.nan), li, positions,
                live=jnp.asarray(np.isin(np.arange(s_), dead, invert=True)))
            assert not np.asarray(got[dead], np.float32).any()
            keep = np.setdiff1d(np.arange(s_), dead)
            assert np.array_equal(np.asarray(got[keep], np.float32),
                                  np.asarray(every[keep], np.float32))
            want = want.at[dead].set(0)
        assert got.shape == want.shape and got.dtype == want.dtype
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(want, np.float32)).max()
        assert err < tol, err

    # (kv heads, window): StarCoder2's and OLMoE's; 16 rows a block
    HEADS = [(2, 24), (16, None)]

    @pytest.mark.parametrize("hkv,window", HEADS)
    def test_no_slot_live_and_every_slot_live(self, rng, hkv, window):
        """No live slot: nothing is copied (the pool is NaN), every row
        is zero. ``live`` all true is ``live=None`` bit for bit."""
        s_, t_max, h = 3, 64, 2 * hkv if hkv == 2 else hkv
        pool_k, pool_v = (
            jnp.asarray(rng.normal(size=(2, s_, t_max, hkv, 128)),
                        jnp.float32) for _ in range(2))
        q = jnp.asarray(rng.normal(size=(s_, 1, h, 128)), jnp.float32)
        positions = jnp.asarray([[5], [40], [63]], jnp.int32)
        kernel = functools.partial(
            pool_decode_attention, window=window, block_rows=16,
            interpret=True)
        none = kernel(q, pool_k * jnp.nan, pool_v * jnp.nan, 1, positions,
                      live=jnp.zeros(s_, bool))
        assert none.shape == q.shape and not np.asarray(none).any()
        assert np.array_equal(
            np.asarray(kernel(q, pool_k, pool_v, 1, positions)),
            np.asarray(kernel(q, pool_k, pool_v, 1, positions,
                              live=jnp.ones(s_, bool))))

    @pytest.mark.parametrize("hkv,window", HEADS)
    @pytest.mark.parametrize("cursors", [
        (16, 31, 63),       # a block's first and last position; T_max - 1
        (0, 15, 32),        # one key; a full first block; one key of a third
        (63, 63, 0),
    ])
    def test_cursors_on_block_edges(self, rng, hkv, window, cursors):
        """Live rows against the XLA op with the cursors on the edges of
        a 16-position block (16 rows a block at one kv head's worth of
        rows: ``block_rows = 16 * hkv``), the slot between them frozen —
        dead, its keys NaN — at a cursor beyond the window."""
        t_max, h = 64, 2 * hkv if hkv == 2 else hkv
        cursors = (cursors[0], 50, cursors[1], cursors[2])
        s_ = len(cursors)
        live = np.asarray([True, False, True, True])
        pool_k, pool_v = (
            jnp.asarray(rng.normal(size=(2, s_, t_max, hkv, 128)),
                        jnp.float32).at[:, 1].set(jnp.nan)
            for _ in range(2))
        q = jnp.asarray(rng.normal(size=(s_, 1, h, 128)), jnp.float32)
        positions = jnp.asarray(cursors, jnp.int32)[:, None]
        got = pool_decode_attention(
            q, pool_k, pool_v, 0, positions, window=window,
            block_rows=16 * hkv, interpret=True, live=jnp.asarray(live))
        want = grouped_query_attention(
            q[live], pool_k[0][live], pool_v[0][live],
            mask=_mask(positions[live], t_max, window))
        assert not np.asarray(got[1]).any()
        np.testing.assert_allclose(np.asarray(got[live]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("hkv,window", HEADS + [(3, None), (2, None)])
    def test_work_list_is_the_live_slots_spans(self, rng, hkv, window):
        """The span function the host counts with (numpy) and the
        ``lo``/``hi`` the kernel prefetches (jax) agree, and the work
        list is every live slot's blocks ``lo..hi`` once, in order."""
        s_, t_max, block = 9, 96, 16
        cursors = rng.integers(0, t_max + 8, size=s_)    # some past the end
        cursors[:3] = 0, t_max - 1, 16
        live = rng.random(s_) < 0.6
        live[:3] = True
        kw = dict(block=block, hkv=hkv, window=window, t_max=t_max)
        lo, hi = key_block_span(cursors, cursors, **kw)
        assert isinstance(lo, np.ndarray) and (0 <= lo).all()
        assert (lo <= hi).all() and (hi < t_max * hkv // block).all()
        n, slot_of, klo, khi, first = (np.asarray(a) for a in _work_list(
            jnp.asarray(cursors, jnp.int32)[:, None], jnp.asarray(live),
            **kw))
        assert np.array_equal(klo, lo) and np.array_equal(khi, hi)
        items = [(s, b) for s in range(s_) if live[s]
                 for b in range(lo[s], hi[s] + 1)]
        assert n[0] == len(items) == (hi - lo + 1)[live].sum()
        w = np.arange(n[0])
        assert [(s, lo[s] + i - first[s])
                for i, s in zip(w, slot_of[:n[0]])] == items
        # a verify's span runs from its oldest query's window to its newest
        vlo, vhi = key_block_span(cursors + 3, cursors, **kw)
        assert np.array_equal(vlo, lo) and (vhi >= hi).all()

    def test_served_trace_reads_no_more_than_the_pool(self, rng):
        """A server's ``kv_blocks`` (the slots dispatched) against
        ``kv_blocks_pool`` (every slot's cursor, frozen ones included):
        equal while every slot holds a request, less once one has left
        (a slot that never held one counts its block at cursor 0)."""
        from deeplearning4j_tpu.serving import DecodeServer

        srv = DecodeServer(_lm128(), slots=2, max_len=96)
        for n, new in ((40, 3), (9, 12)):
            srv.submit(rng.integers(1, 61, n).astype(np.int32), new)
        srv.step()
        assert srv.kv_blocks == srv.kv_blocks_pool == 2   # one block a slot
        srv.drain()
        st = srv.stats()
        assert 0 < st["kv_blocks"] < st["kv_blocks_pool"]
        assert st["kv_blocks_share"] < 1

    def test_pool_stored_in_another_dtype(self, rng):
        """A float32 pool under bf16 queries: blocks are cast in VMEM,
        like ``dequant_slab`` casts the slab."""
        pool_k, pool_v = (
            jnp.asarray(rng.normal(size=(1, 2, 32, 2, 128)), jnp.float32)
            for _ in range(2))
        q = jnp.asarray(rng.normal(size=(2, 1, 4, 128)), jnp.bfloat16)
        positions = jnp.asarray([[7], [31]], jnp.int32)
        want = grouped_query_attention(
            q, pool_k[0].astype(q.dtype), pool_v[0].astype(q.dtype),
            mask=_mask(positions, 32, None))
        got = pool_decode_attention(q, pool_k, pool_v, 0, positions,
                                    block_rows=32, interpret=True)
        assert got.dtype == jnp.bfloat16
        assert np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)).max() < 2e-2

    def test_block_rows(self):
        # the benchmark's pools: 512 KiB blocks of 2,048 rows — 1,024
        # positions at StarCoder2's 2 kv heads, 128 at OLMoE's 16
        assert pool_block_rows((4, 64, 16384, 2, 128), jnp.bfloat16) == 2048
        assert pool_block_rows((4, 32, 4096, 16, 128), jnp.bfloat16) == 2048
        assert pool_block_rows((4, 64, 16384, 2, 128), jnp.float32) == 1024
        # a small pool is one block; a head size off the lanes has no kernel
        assert pool_block_rows((2, 4, 96, 1, 128), jnp.float32) == 96
        assert pool_block_rows((2, 4, 96, 2, 64), jnp.float32) is None
        assert pool_block_rows((2, 4, 100, 1, 128), jnp.float32) is None
        with pytest.raises(ValueError):
            pool_decode_attention(
                jnp.zeros((4, 1, 2, 64)), jnp.zeros((2, 4, 96, 2, 64)),
                jnp.zeros((2, 4, 96, 2, 64)), 0,
                jnp.zeros((4, 1), jnp.int32), interpret=True)


def _lm128(**kw):
    cfg = dict(vocab_size=61, d_model=256, num_heads=2, num_kv_heads=1,
               num_layers=2, max_len=96, seed=3, pos_encoding="rope")
    cfg.update(kw)
    return TransformerLM(**cfg).init()


class TestEngineRead:
    @pytest.mark.parametrize("kind", ["decode", "verify"])
    def test_kernel_read_agrees_with_the_xla_read(self, rng, kind):
        """The same step with the pool read by the kernel (interpreted
        here) and by the XLA op: the same first-layer rows bit for bit
        (they do not depend on the read), the rest of the pool and the
        logits to float32 rounding."""
        lm = _lm128(attn_window=32)
        slots, max_len = 3, 96
        kv = {name: jnp.asarray(rng.normal(size=a.shape), a.dtype)
              for name, a in SlotKVCache(lm, slots, max_len).state.items()}
        nq = 1 if kind == "decode" else 3
        positions = jnp.asarray([[4], [60], [max_len - nq]]) + jnp.arange(nq)
        toks = jnp.asarray(rng.integers(1, 61, (slots, nq)), jnp.int32)
        out = {}
        for pool_kernel in (False, True):
            if kind == "decode":
                out[pool_kernel] = eng._decode_step_body(
                    lm, lm.params, kv, toks[:, 0], positions[:, 0],
                    pool_kernel=pool_kernel)
            else:
                out[pool_kernel] = eng._serve_verify_impl(
                    lm, lm.params, kv, toks, positions,
                    pool_kernel=pool_kernel)
        (want, want_kv), (got, got_kv) = out[False], out[True]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        for name in kv:
            a, b = np.asarray(got_kv[name]), np.asarray(want_kv[name])
            assert np.array_equal(a[0], b[0])
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_which_pools_the_kernel_reads(self, monkeypatch):
        """The kernel is the read where a TPU is attached, for a
        pool whose head size fills the lanes;
        other head sizes and (bound by the engine) pools sharded over a
        mesh keep the XLA read."""
        calls = []
        monkeypatch.setattr(
            "deeplearning4j_tpu.ops.attention.grouped_query_attention",
            lambda q, *a, **k: calls.append("xla") or q)
        monkeypatch.setattr(
            "deeplearning4j_tpu.pallas.decode_attention"
            ".pool_decode_attention",
            lambda q, *a, **k: calls.append("kernel") or q)

        def reads(lm, kv_dtype, attached, **kw):
            calls.clear()
            monkeypatch.setattr(eng, "flash_default_interpret",
                                lambda: not attached)
            kv = SlotKVCache(lm, 2, 96, kv_dtype).state
            z = jnp.zeros(2, jnp.int32)
            jax.eval_shape(functools.partial(
                eng._decode_step_body, lm, **kw), lm.params, kv, z, z)
            return sorted(set(calls))

        lm = _lm128()
        assert reads(lm, "float32", attached=True) == ["kernel"]
        assert reads(lm, "bfloat16", attached=True) == ["kernel"]
        assert reads(lm, "float32", attached=False) == ["xla"]
        assert reads(lm, "float32", attached=True,
                     pool_kernel=False) == ["xla"]
        small = _lm128(d_model=32, num_heads=4, num_kv_heads=2)
        assert reads(small, "float32", attached=True) == ["xla"]

    def test_mesh_engine_keeps_the_xla_read(self):
        from deeplearning4j_tpu.parallel import MeshSpec, build_mesh
        from deeplearning4j_tpu.serving.engine import DecodeEngine

        lm = _lm128(d_model=32, num_heads=4, num_kv_heads=2)
        one = DecodeEngine(lm, 2, max_len=96)._decode_jit(
            eng._serve_decode_impl, lm, None)
        tp = DecodeEngine(
            _lm128(d_model=32, num_heads=4, num_kv_heads=2), 2, max_len=96,
            mesh=build_mesh(MeshSpec(data=1, model=2),
                            devices=jax.devices()[:2]))._decode_jit(
            eng._serve_decode_impl, lm, None)
        assert one.__wrapped__.keywords == {}
        assert tp.__wrapped__.keywords == {"pool_kernel": False}


# ---------------------------------------------------------------------------
# compiled for a described v5e, without the chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class TestCompiledForV5e:
    def test_decode_program_reads_the_pool_in_place(self, one_chip,
                                                    monkeypatch):
        """The one-step decode program at the serving cells' pool widths
        (64 slots x 16,384 positions, 2 kv heads of 128, bf16; two
        layers and a narrow MLP to keep the compile short): Mosaic takes
        the kernel, the donated pool is the output pool, and no
        temporary comes near one layer's 512 MiB slab."""
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.setattr(eng, "flash_default_interpret", lambda: False)
        cfg = dict(vocab_size=512, d_model=3072, num_heads=24,
                   num_kv_heads=2, num_layers=2, d_ff=256, max_len=16384,
                   pos_encoding="rope", attn_window=4096,
                   dtype_policy="bf16")
        lm = TransformerLM(**cfg)
        slots = 64

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=one_chip), tree)

        params = abstract(jax.eval_shape(
            lambda: TransformerLM(**cfg).init().params))
        pool = jax.ShapeDtypeStruct((2, slots, 16384, 2, 128), jnp.bfloat16,
                                    sharding=one_chip)
        vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
        keys = jax.ShapeDtypeStruct((slots, 2), jnp.uint32,
                                    sharding=one_chip)
        fn = jax.jit(functools.partial(
            eng._serve_decode_impl, lm, eng._row_sampler(0.0, None)),
            donate_argnums=(1,))
        # a compile for a described chip is written to the persistent
        # cache and cannot be read back without one
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            compiled = fn.lower(params, {"k": pool, "v": pool}, vec, vec,
                                keys).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        assert compiled.as_text().count("tpu_custom_call") >= 2
        mem = compiled.memory_analysis()
        slab = slots * 16384 * 2 * 128 * 2
        assert mem.alias_size_in_bytes == 2 * 2 * slab
        assert mem.temp_size_in_bytes < slab // 8, mem.temp_size_in_bytes

    def test_routed_experts_fetch_the_reached_ones_as_stored(self, one_chip,
                                                             monkeypatch):
        """``routed_ffn`` at ``ling-serve-reason``'s decode shapes (64 rows,
        8 of 512 experts a row, 64 held, hidden 2,560, width 768, float32
        storage under bf16 compute): Mosaic takes the reached-experts
        kernel (``pallas/reached_experts.py``), and no temporary comes
        near one stacked matrix, let alone a bf16 copy of the three."""
        from jax.experimental.compilation_cache import compilation_cache

        from deeplearning4j_tpu.models import routed_experts

        monkeypatch.setattr(routed_experts, "_kernel_backend",
                            lambda: "mosaic")
        n, d, f, e, held = 64, 2560, 768, 512, 64

        def arg(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        p = {"router": arg((d, e)), "bias": arg((e,)),
             "w_gate": arg((held, d, f)), "w_up": arg((held, d, f)),
             "w_down": arg((held, f, d))}
        fn = jax.jit(lambda x, p, live: routed_experts.routed_ffn(
            x, p, experts_per_token=8, norm_topk_prob=True, live=live,
            cast=lambda w: w.astype(jnp.bfloat16), groups=(8, 4, 2.5)))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            compiled = fn.lower(arg((n, d), jnp.bfloat16), p,
                                arg((n,), jnp.bool_)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        assert compiled.as_text().count("tpu_custom_call") == 1
        matrix = held * d * f * 2           # one stacked matrix in bf16
        assert compiled.memory_analysis().temp_size_in_bytes < matrix // 16

    def test_retention_step_moves_the_state_in_place(self, one_chip,
                                                     monkeypatch):
        """``pallas/retention_step.py`` at ``brumby-serve-continue``'s
        sizes (32 slots, 8 kv heads of 128 serving 5 queries each, a state
        of 8,704 rows a head): Mosaic takes the kernel (dynamic trip counts,
        VMEM scratch, a 4.46 MB block a head), the donated state is the
        output state, and nothing is copied beside it."""
        from jax.experimental.compilation_cache import compilation_cache

        from deeplearning4j_tpu.models import ret
        from deeplearning4j_tpu.pallas import retention_step as step

        b, hkv, rep, d = 32, 8, 5, 128
        rows = ret.state_rows(d)
        assert rows == 8704

        def arg(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        fn = jax.jit(lambda *a: step.retention_step(
            *a, eps=ret.EPS, interpret=False), donate_argnums=(4, 5))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            compiled = fn.lower(
                arg((b, hkv * rep, d)), arg((b, hkv, d)), arg((b, hkv, d)),
                arg((b, hkv)), arg((b, hkv, rows, d)), arg((b, hkv, d, d)),
                arg((b,), jnp.bool_)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        assert compiled.as_text().count("tpu_custom_call") == 1
        mem = compiled.memory_analysis()
        state = b * hkv * (rows * d + d * d) * 4
        assert mem.alias_size_in_bytes == state
        assert mem.temp_size_in_bytes < state // 256, mem.temp_size_in_bytes
