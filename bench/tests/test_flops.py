"""FLOPs per token of each family against the program's analytic
parameter count (``ModelConfig.params_count``)."""
import json
import os

import pytest

from flops import dense
from harness import spec, sim_train


def _config(name):
    with open(os.path.join(spec.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_dense_matmul_params_match_params_count():
    c = _config("gpt2-xl-paper.12L")
    n = sim_train.model_config(c).params_count()
    d, L = c["d_model"], c["num_layers"]
    assert dense.matmul_params(c) == n - (2 * d * L + d)   # norm gains
    assert dense.matmul_params(c) == 449_051_200
    assert dense.flops_per_token(c, 1024) == pytest.approx(2.9302368e9)
