"""``lib/reference_resnet.py``'s training steps against the program's own
float32 training on the same batches in the same order: forward with batch
statistics, backward and Adam are the same arithmetic, so the losses agree to
float32 rounding (and what it grows to over three steps). (The cell compares a bf16 four-chip run with this reference;
this test is what says the reference itself computes the program's step.)"""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks.drivers.graph_train_dp import make_data
from benchmarks.lib import reference_resnet

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_first_losses_are_the_programs_float32_training():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import resnet18

    with open(os.path.join(ROOT, "benchmarks/configs/resnet18-cifar10.json")) as f:
        cfg = dict(json.load(f), train_samples=96, image_size=8)
    x, y = make_data(cfg, 0.5, 3)
    net = resnet18(num_classes=cfg["num_classes"], seed=3,
                   lr=cfg["learning_rate"], dtype_policy="float32").init()
    params0 = jax.device_get(net.params)
    hist = net.fit_epochs(ListDataSetIterator(DataSet(x, y), 32), 1,
                          shuffle=False)
    batches = [(x[i:i + 32], y[i:i + 32]) for i in (0, 32, 64)]
    ref = reference_resnet.first_losses(params0, batches, cfg, micro=32,
                                        lr=cfg["learning_rate"])
    # Adam's first steps are near sign(g) * lr, so rounding in a small
    # gradient grows from step to step: tight on two steps, loose on the third
    assert np.asarray(hist)[0, :2] == pytest.approx(ref[:2], rel=2e-4)
    assert np.asarray(hist)[0, 2] == pytest.approx(ref[2], rel=1e-2)
    # micro-batches change only batch norm's statistics: close, not equal
    ghost = reference_resnet.first_losses(params0, batches[:1], cfg, micro=16,
                                          lr=cfg["learning_rate"])
    assert ghost[0] != ref[0] and ghost[0] == pytest.approx(ref[0], rel=0.2)
