"""The control: the reference computed in float8 (the step below the
configuration's bfloat16) in the program's place reads above the
limits that the program passes."""
import json
import os

import numpy as np

from bench.harness import check as C
from bench.harness import weights as W
from bench.tests.tiny import FIX


def _load(kind, name):
    with open(os.path.join(FIX, kind, name + ".json")) as f:
        return json.load(f)


def test_training_control_fails_a_limit():
    cfg = _load("configs", "tiny")
    cell = _load("workloads", "tiny.train")
    mix = _load("traffic", "tiny_train")
    seed = 2 ** 35 + 3
    ref = C.train_reference(cfg, mix, cell["optimizer"], seed, 3, "f32")
    ctl = C.train_reference(cfg, mix, cell["optimizer"], seed, 3, "fp8")
    names = [str(i) for i in range(len(ref["grad_norms"]))]
    got = C.compare_train(ctl, ref, names)
    lim = cell["limits"]
    assert any(got[k] > lim[k] for k in lim), (got, lim)


def test_serving_control_fails_the_limit():
    cfg = _load("configs", "tiny")
    cell = _load("workloads", "tiny.chat")
    params = W.make_params(cfg, 99)
    rng = np.random.default_rng(0)
    V = cfg["model"]["vocab_size"]
    samples = [(i, rng.integers(0, V, 20).astype(np.int32),
                list(rng.integers(0, V, 12))) for i in range(24)]
    got = C.served_gaps(params, cfg, samples, 32, 16, 32, control=True)
    assert got["control_gap"] > cell["limits"]["served_gap"]
