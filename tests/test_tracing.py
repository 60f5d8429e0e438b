"""What the program names in a profile (`repro.tracing`): the layer
scopes of the compiled train step, and the launcher loop's host spans
under a `jax.profiler` trace.  Smoke sizes on the CPU."""
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import tracing
from repro.comm.config import CommConfig
from repro.configs.base import get_config
from repro.data.pipeline import Dataset, DatasetConfig
from repro.launch import hlo_cost, runner
from repro.optim.adamw import AdamWConfig
from repro.training import simulated as sim

# the benchmark's reader of the scopes, so that what is checked here is
# what the per-layer metrics see
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
from harness.scopes import classify  # noqa: E402

SAMPLES, SEQ, BATCH = 16, 32, 4
OP_NAME = re.compile(r'op_name="([^"]*)"')
MODEL_SCOPES = (tracing.ATTN, tracing.FFN, tracing.LM_HEAD)


def _configs(remat: bool = True):
    cfg = get_config("gpt2-xl-paper", smoke=True)
    comm = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                 "bw": {"bits": 8}})
    tcfg = sim.SimTrainConfig(num_stages=2, comm=comm, remat=remat,
                              optimizer=AdamWConfig(lr=1e-3,
                                                    warmup_steps=1,
                                                    total_steps=8))
    return cfg, tcfg


@pytest.fixture(scope="module")
def step_hlo():
    cfg, tcfg = _configs()
    state = jax.eval_shape(lambda: sim.init_train_state(
        cfg, tcfg, SAMPLES, SEQ, jax.random.PRNGKey(0)))
    i32 = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    batch = {"tokens": i32, "targets": i32,
             "mask": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.float32),
             "sample_ids": jax.ShapeDtypeStruct((BATCH,), jnp.int32)}
    return sim.train_step.lower(
        state, batch, jax.random.PRNGKey(1), mcfg=cfg,
        tcfg=tcfg).compile().as_text()


def test_step_carries_every_scope_in_its_phases(step_hlo):
    seen = {classify(m) for m in OP_NAME.findall(step_hlo)}
    assert set(tracing.SCOPES) <= {s for s, _ in seen}
    for scope in (tracing.ATTN, tracing.FFN):
        for phase in ("forward", "backward", "recompute"):
            assert (scope, phase) in seen, (scope, phase)
    for phase in ("forward", "backward"):
        assert (tracing.BOUNDARY, phase) in seen, phase


def test_stage_slices_are_timed_with_their_block(step_hlo):
    """Each stage's slice of the stacked layer weights runs under the
    scope of the block that reads it, not outside every scope."""
    paths = set(OP_NAME.findall(step_hlo))
    for scope in (tracing.ATTN, tracing.FFN):
        assert f"jit(train_step)/jvp({scope})/slice" in paths, scope
    assert "jit(train_step)/jvp()/slice" not in paths


def _outside_dots_dropped(text: str) -> str:
    """The HLO text with every dot outside the model scopes turned into
    a copy, which `hlo_cost` counts no FLOPs for."""
    out = []
    for line in text.splitlines():
        m = OP_NAME.search(line)
        if " dot(" in line and (m is None
                                or classify(m.group(1))[0]
                                not in MODEL_SCOPES):
            line = line.replace(" dot(", " copy(", 1)
        out.append(line)
    return "\n".join(out)


def test_matmul_flops_fall_under_the_model_scopes(step_hlo):
    """At least 95% of the step's dot FLOPs (while loops multiplied
    through their trip counts) lie under attn, ffn or lm_head."""
    total = hlo_cost.hlo_cost(step_hlo).flops
    model = hlo_cost.hlo_cost(_outside_dots_dropped(step_hlo)).flops
    assert total > 0 and model >= 0.95 * total, (model, total)


def _spans(log_dir: str) -> list:
    """(name, start, end, args) of every ``repro.*`` host span of the
    trace under ``log_dir``, in start order."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1, path
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.SPAN_PREFIX):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _train(tmp_path, **kw):
    cfg, tcfg = _configs(remat=False)
    ds = Dataset(DatasetConfig(num_samples=SAMPLES, seq_len=SEQ,
                               vocab_size=cfg.vocab_size))
    lines = []
    _, losses = runner.run_sim_training(
        cfg, tcfg, ds, num_steps=3, batch_size=BATCH, log_every=1,
        profile=tracing.StepProfile(str(tmp_path / "prof"), 0, 3),
        print_fn=lines.append, **kw)
    return _spans(str(tmp_path / "prof")), losses, lines


def test_runner_steps_hold_feed_dispatch_sync_in_order(tmp_path):
    spans, losses, lines = _train(tmp_path)
    assert len(losses) == 3 and len(lines) == 3
    steps = [s for s in spans if s[0] == "repro.step"]
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2]
    for name, start, end, args in steps:
        inner = [s for s in spans if s[0] != "repro.step"
                 and start <= s[1] and s[2] <= end]
        names = [s[0] for s in inner]
        assert names == ["repro.feed", "repro.dispatch", "repro.sync",
                         "repro.guard"], names
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    # the counts stand as each step ends: steps after the first reuse
    # the compiled step
    counts = [(s[3]["compiles"], s[3]["cache_hits"]) for s in steps]
    assert counts[0] <= counts[1] == counts[2]


def test_compile_counts_count_a_compile():
    before = tracing.compile_counts()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    after = tracing.compile_counts()
    assert after["compiles"] > before["compiles"]
    assert after["cache_hits"] == before["cache_hits"]


def test_a_cache_load_is_not_a_compile(tmp_path):
    """An executable loaded from the persistent compilation cache counts
    as a cache hit, not as a compile."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    fn = lambda x: x * 5 + 2                          # noqa: E731
    x = jnp.ones(9)
    try:
        for n, v in zip(names, (str(tmp_path), 0, 0)):
            jax.config.update(n, v)
        compilation_cache.reset_cache()
        jax.jit(fn)(x).block_until_ready()
        stored = tracing.compile_counts()
        jax.clear_caches()            # the next call finds only the file
        jax.jit(fn)(x).block_until_ready()
        loaded = tracing.compile_counts()
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
    assert loaded["cache_hits"] == stored["cache_hits"] + 1
    assert loaded["compiles"] == stored["compiles"]


def test_runner_checkpoint_span_carries_its_bytes(tmp_path):
    spans, _, _ = _train(tmp_path, ckpt_dir=str(tmp_path / "ckpt"),
                         save_every=2)
    saves = [s for s in spans if s[0] == "repro.ckpt.save"]
    assert saves, [s[0] for s in spans]
    # the state holds the parameters, both moments and the store
    cfg, _ = _configs()
    assert saves[0][3]["bytes"] > 3 * 4 * cfg.vocab_size * cfg.d_model


def test_step_profile_traces_only_its_steps(tmp_path):
    prof = tracing.StepProfile(str(tmp_path), 1, 3)
    prof.at(0)
    assert not prof.active
    prof.at(1)
    assert prof.active
    prof.at(2)
    assert prof.active
    prof.at(3)
    assert not prof.active and prof.taken
    prof.at(1)                      # a replayed step starts no second one
    assert not prof.active
    prof.close()
