"""FLOPs per token of the ``ssm`` family: its matmul parameters are the
matrices of the reference's weights, and the count reproduces the
arithmetic of `flops.ssm`'s docstring."""
import json
import math
import os

import jax
import pytest

from flops import ssm
from harness import spec
from reference import ssm as ref


def test_ssm_flops_per_token():
    with open(os.path.join(spec.BENCH, "configs",
                           "mamba2-1.3b.12L.json")) as f:
        c = json.load(f)
    shapes = jax.eval_shape(lambda: ref.init_params(
        c, jax.random.PRNGKey(0)))
    m = shapes["layers"]["mamba"]
    matrices = sum(math.prod(a.shape) for a in
                   (shapes["embed"], m["in_proj"], m["out_proj"]))
    assert ssm.matmul_params(c) == matrices == 412_827_648
    assert ssm.flops_per_token(c, 1024) == pytest.approx(2.630320128e9)
