"""The benchmark's operation counts against hand counts."""

import json
import os

import pytest

import chipbench_tiny as tb

import counts

CONFIGS = os.path.join(tb.BENCH_DIR, "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        c = json.load(f)
    c["name"] = name
    return c


def test_smollm_train_flops_per_token():
    c = config("smollm-135m")
    # Per layer: q 576x576, k and v 576x192, o 576x576, SwiGLU 3x576x1536;
    # head 576x49152; attention 2 x 2 x 576 x (2049 / 2) per layer.
    layer = 576 * 576 * 2 + 2 * 576 * 192 + 3 * 576 * 1536
    fwd = 2 * (30 * layer + 576 * 49152) + 30 * 4 * 576 * 2049 / 2
    assert counts.train_flops_per_token(c, 2048) == pytest.approx(3 * fwd)
    assert counts.train_flops_per_token(c, 2048) == pytest.approx(1.02e9,
                                                                  rel=0.01)


def test_granite_train_flops_per_token():
    c = config("granite-3.0-3b-a800m-4L")
    # Top-8 experts of 3 x 1536 x 512 each and a 1536 x 40 router; head
    # over the padded 51,200 rows.
    layer = 1536 * 1536 * 2 + 2 * 1536 * 512 + 1536 * 40 + 8 * 3 * 1536 * 512
    fwd = 2 * (4 * layer + 1536 * 51200) + 4 * 4 * 1536 * 2049 / 2
    assert counts.train_flops_per_token(c, 2048) == pytest.approx(3 * fwd)
    assert counts.train_flops_per_token(c, 2048) == pytest.approx(1.15e9,
                                                                  rel=0.01)
