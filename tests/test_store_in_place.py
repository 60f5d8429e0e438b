"""The simulated train step donates its state: the compiled step writes
the new parameters, moments and AQ-SGD message store into the buffers
it was handed, and copies no store whole.  ``simulated.train`` copies
the caller's ``initial_params`` once, so that they outlive the run.
Smoke widths on the CPU."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.config import CommConfig
from repro.configs.base import get_config
from repro.data.pipeline import Dataset, DatasetConfig
from repro.optim.adamw import AdamWConfig
from repro.training import simulated as sim

SAMPLES, SEQ, BATCH = 16, 32, 4
COPY = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")


def _configs(buffer_bits: int):
    # 4 layers for 4 stages: 3 boundaries, as the benchmark's cells
    cfg = get_config("gpt2-xl-paper", smoke=True).with_(num_layers=4)
    comm = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                 "bw": {"bits": 8},
                                 "zbuf": {"bits": buffer_bits}})
    tcfg = sim.SimTrainConfig(num_stages=4, comm=comm, remat=True,
                              optimizer=AdamWConfig(lr=1e-3,
                                                    warmup_steps=1,
                                                    total_steps=8))
    return cfg, tcfg


@pytest.mark.parametrize("buffer_bits", [0, 8], ids=["m", "codes"])
def test_step_aliases_its_state_and_copies_no_store(buffer_bits):
    cfg, tcfg = _configs(buffer_bits)
    state = jax.eval_shape(lambda: sim.init_train_state(
        cfg, tcfg, SAMPLES, SEQ, jax.random.PRNGKey(0)))
    store = {k: v for k, v in state["buffers"].items() if k != "seen"}
    assert set(store) == ({"codes", "scale"} if buffer_bits else {"m"})
    i32 = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    batch = {"tokens": i32, "targets": i32,
             "mask": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.float32),
             "sample_ids": jax.ShapeDtypeStruct((BATCH,), jnp.int32)}
    compiled = sim.train_step.lower(
        state, batch, jax.random.PRNGKey(1), mcfg=cfg,
        tcfg=tcfg).compile()

    donated = sum(x.size * x.dtype.itemsize for k in
                  ("buffers", "params", "opt")
                  for x in jax.tree.leaves(state[k]))
    assert compiled.memory_analysis().alias_size_in_bytes >= donated
    shapes = {",".join(map(str, x.shape)) for x in store.values()}
    copied = [s for s in COPY.findall(compiled.as_text()) if s in shapes]
    assert copied == [], copied


def test_train_leaves_initial_params_alive():
    cfg, tcfg = _configs(0)
    dc = DatasetConfig(num_samples=SAMPLES, seq_len=SEQ,
                       vocab_size=cfg.vocab_size)
    p = sim.init_train_state(cfg, tcfg, SAMPLES, SEQ,
                             jax.random.PRNGKey(3))["params"]
    before = [np.asarray(x) for x in jax.tree.leaves(p)]
    # a fresh dataset each: its batches come from a stateful order
    runs = [sim.train(cfg, tcfg, Dataset(dc), num_steps=3,
                      batch_size=BATCH, initial_params=p)[1]
            for _ in range(2)]
    assert not any(x.is_deleted() for x in jax.tree.leaves(p))
    for a, b in zip(before, jax.tree.leaves(p)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert runs[0] == runs[1]
