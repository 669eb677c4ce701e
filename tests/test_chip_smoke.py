"""chip_smoke.py's phases, tiny, on the CPU.

The script itself only ever runs full-size on a TPU (tier-1 cannot); these
tests call the SAME phase functions with small sizes so a refactor that
breaks a phase shows up here first. Interpret mode for the Pallas kernels
is chosen here, by the test — never discovered by the script.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

GRAPH = dict(batch=8, image=8, n_batches=2, epochs=2)
LM = dict(vocab_size=128, d_model=64, num_heads=4, num_layers=2, max_len=64)
SERVE = dict(lm_kwargs=LM, slots=2, max_len=64, prompt_lens=(5, 17, 30),
             new_tokens=(4, 6, 5))
# head_dim 64 so the flash kernel's lane axis is what the chip sees
TRAIN_LM = dict(vocab_size=128, d_model=128, num_heads=2, num_layers=2,
                max_len=128)


def test_device_phase_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.device_phase()
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert "platform=cpu" in out.out
    assert '"ok"' not in out.out  # no result line without an accelerator


def test_main_refuses_cpu_before_any_phase(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "running" not in capsys.readouterr().out


def test_train_lm_tiny():
    r = chip_smoke.train_lm_phase(
        lm_kwargs=TRAIN_LM, batch=2, steps=2, fused_k=2, attn_impl="flash",
        expect_mosaic=False)
    assert r["attn_impl"] == "flash"
    assert r["loss_last"] < r["loss_first"]


def test_train_lm_demands_flash():
    # "auto" off a TPU resolves to the XLA path: the phase must refuse it
    with pytest.raises(AssertionError, match="resolved to 'xla'"):
        chip_smoke.train_lm_phase(lm_kwargs=TRAIN_LM, batch=2, steps=1,
                                  fused_k=1)


def test_kernels_tiny():
    r = chip_smoke.kernels_phase(
        batch=1, heads=2, head_dim=64, cases=((128, None), (256, 128)),
        decode=(3, 64, 24), moe=(64, 32, 8, 2, (8, 1032)),
        reached=(384, 128, 8, 2, 16, 3), delta=(3, 8, 16), interpret=True)
    assert set(r["rel_err"]) == {
        f"{case}_{k}" for case in ("t128", "t256_w128")
        for k in ("fwd", "dq", "dk", "dv")} | {
            "decode_t64_kv2_w24", "decode_t64_kv16", "moe_n8", "moe_n1032",
            "moe_share_n1032", "reached_n16_b384x128", "delta_head",
            "delta_channel"}


def test_kernels_tolerance_is_enforced():
    with pytest.raises(AssertionError, match="beyond"):
        chip_smoke.kernels_phase(batch=1, heads=1, head_dim=64,
                                 cases=((128, None),), decode=(2, 32, 32),
                                 decode_heads=((8, 2),),
                                 moe=(64, 32, 8, 2, (8,)),
                                 reached=(128, 128, 8, 2, 16, 3),
                                 delta=(2, 2, 8),
                                 interpret=True, tol=0.0)


@pytest.fixture(scope="module")
def graph_result():
    return chip_smoke.train_graph_phase(**GRAPH)


@pytest.fixture(scope="module")
def serve_result():
    return chip_smoke.serve_phase(**SERVE)


def test_train_graph_tiny(graph_result):
    assert graph_result["history"].shape == (2, 2)


def test_serve_tiny(serve_result):
    assert serve_result["requests"] == 6
    assert serve_result["near_tie"] is None  # f32-exact on the CPU
    assert [len(o) for o in serve_result["outputs"]] == [9, 23, 35]


def test_same_greedy_rejects_a_real_difference(serve_result):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    lm = TransformerLM(seed=0, dtype_policy="bf16", **LM).init()
    want = np.asarray(serve_result["outputs"][0])
    got = want.copy()
    # the least likely token at the first generated position: no near-tie
    logits = np.asarray(lm.forward(lm.params, want[None, :5])[0, -1])
    got[5] = int(np.argmin(logits))
    with pytest.raises(AssertionError, match="no near-tie"):
        chip_smoke._assert_same_greedy(lm, got, want, "test")


def test_mesh_tiny(graph_result, serve_result):
    r = chip_smoke.mesh_phase(
        graph_sizes=GRAPH, serve_sizes=SERVE,
        graph_history=graph_result["history"],
        serve_outputs=serve_result["outputs"])
    assert r["devices"] == 4 and r["kv_shards"] == 2
