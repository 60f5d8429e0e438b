"""The DP gradient wire: bucketed error-feedback compression contract.

Mirrors tests/test_boundary_parity.py for the gradient path: the
reference and Pallas backends of the bucketed codec
(`core.grad_compress` + the shared-scale ops in `core.boundary`) must
produce IDENTICAL bits under jit — packed payloads, int32 code sums,
mean gradients, and carried error states.  On top of the parity
contract, the error-feedback algebra itself is pinned:

* telescoping — over T steps, the emitted quantized gradients plus the
  final carried error reconstruct the exact gradient sum (QuantizedAdam
  / Tang et al. 2021's defining invariant: compression error never
  accumulates, it is *deferred*);
* unbiasedness — stochastic rounding through the fused codec is
  mean-zero over many trials (Thm 3.1's requirement on Q);
* bucketing — leaves with small trailing dims are grouped along the
  flattened bucket, never per-row with degenerate scale groups (the
  pre-bucketing `compress_gradients` reshaping bug).

The convergence regression at the bottom (slow tier, nightly) pins the
Fig. 5a claim: AQ-SGD fw3/bw6 + 4-bit error-feedback gradient
compression tracks FP32 where DirectQ + the same gradient wire drifts.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boundary as B
from repro.core import grad_compress as GC

BITS = [2, 4, 8]
KEY = jax.random.PRNGKey(0)
GROUP = 128


def _tree(seed=0, scale=1.0):
    """A gradient-tree stand-in with awkward shapes: a small-last-dim
    leaf (the old per-row-degenerate case), a vector, a bf16 leaf."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "wide": jax.random.normal(ks[0], (4096, 2)) * scale,
        "bias": jax.random.normal(ks[1], (11,)) * scale,
        "emb": (jax.random.normal(ks[2], (13, 17)) * scale
                ).astype(jnp.bfloat16),
        "blk": jax.random.normal(ks[3], (3, 5, 7)) * scale,
    }


# ---------------------------------------------------------------------------
# bucket layout
# ---------------------------------------------------------------------------

def test_flatten_unflatten_roundtrip_bit_exact():
    tree = _tree()
    lay = GC.bucket_layout(tree, GROUP)
    total = sum(int(np.prod(v.shape)) for v in tree.values())
    assert lay.total == total
    assert lay.rows * lay.group_d == total + lay.pad
    v = GC.flatten_bucket(tree, lay)
    assert v.shape == (lay.rows, GROUP) and v.dtype == jnp.float32
    # padded tail is zeros (padded lanes are dead weight on the wire,
    # but must never perturb scales beyond the real data's absmax)
    flat = np.asarray(v).reshape(-1)
    assert not lay.pad or np.all(flat[total:] == 0)
    back = GC.unflatten_bucket(v, lay, tree)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(
            np.asarray(tree[k].astype(jnp.float32)),
            np.asarray(back[k].astype(jnp.float32)))


def test_small_last_dim_leaf_groups_along_bucket():
    """Regression for the pre-bucketing reshaping bug: a (4096, 2) leaf
    used to quantize per-row — 4096 degenerate 2-element scale groups,
    one f32 scale per 2 codes (scale bytes 4x the 4-bit payload).  The
    bucketed layout groups along the flattened vector instead."""
    tree = {"w": jnp.zeros((4096, 2))}
    lay = GC.bucket_layout(tree, 512)
    assert lay.rows == 16                       # 8192 / 512, not 4096 rows
    wire = GC.grad_wire_bytes(tree, 4)
    payload = 8192 // 2                         # 4-bit packed
    old_scale_bytes = 4096 * 4                  # per-row scales (the bug)
    new_scale_bytes = wire - payload
    assert new_scale_bytes < old_scale_bytes / 100
    assert new_scale_bytes < payload / 4        # scales amortized away


# ---------------------------------------------------------------------------
# error-feedback invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("stochastic", [False, True])
def test_error_feedback_telescopes(bits, stochastic):
    """v_t = g_t + e_{t-1}, q_t = v_t - e_t  =>  Σ q_t + e_T = Σ g_t:
    the carried error telescopes, so nothing is ever lost — only
    deferred.  Checked through the full bucketed fused codec."""
    tree = _tree(seed=1)
    lay = GC.bucket_layout(tree, GROUP)
    err = GC.init_error_state(tree, GROUP)
    q_sum = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    g_sum = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    key = jax.random.PRNGKey(2)
    for t in range(5):
        g = _tree(seed=10 + t)
        q, err = GC.compress_gradients(g, err, bits,
                                       jax.random.fold_in(key, t),
                                       stochastic=stochastic, layout=lay)
        q_sum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             q_sum, q)
        g_sum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             g_sum, g)
    recon = jax.tree.map(jnp.add, q_sum,
                         GC.unflatten_bucket(err, lay, g_sum))
    for k in tree:
        # bf16 leaves round-trip through their storage dtype each step,
        # so the telescope holds to bf16 resolution there
        tol = 0.1 if tree[k].dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(recon[k]),
                                   np.asarray(g_sum[k]),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [2, 4])
def test_stochastic_qdq_unbiased_10k_trials(bits):
    """E[Q(x)] = x for stochastic rounding on the shared-scale grid,
    estimated over 10k independent draws through the fused codec."""
    n_trials = 10_000
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 64))
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-12)

    @jax.jit
    @jax.vmap
    def one(key):
        packed = B.encode_with_scale(x, scale, bits=bits, stochastic=True,
                                     key=key, backend="reference")
        return B.decode(packed, scale, bits=bits, d=x.shape[-1])

    qs = one(jax.random.split(jax.random.PRNGKey(6), n_trials))
    est = np.mean(np.asarray(qs), axis=0)
    cell = 2.0 * np.asarray(scale) / ((1 << bits) - 1)
    # per-element stderr of the mean is <= cell / sqrt(4 * n_trials);
    # 5 sigma over 256 elements keeps the false-positive rate ~1e-4
    bound = 5.0 * cell / (2.0 * np.sqrt(n_trials))
    err = np.abs(est - np.asarray(x))
    assert np.max(err / bound) < 1.0, float(np.max(err / bound))


# ---------------------------------------------------------------------------
# reference <-> pallas bit-identity (the backend contract, under jit)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bits", "stoch", "backend"))
def _codec(v, s, key, *, bits, stoch, backend):
    packed = B.encode_with_scale(v, s, bits=bits, stochastic=stoch,
                                 key=key, backend=backend)
    codes = B.decode_codes(packed, bits=bits, d=v.shape[-1],
                           backend=backend)
    mean = B.decode_sum_mean(codes * 3, s, bits=bits, n=3, backend=backend)
    return packed, codes, mean


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
def test_bucketed_codec_bit_identical(bits, stoch):
    """Shared-scale sender, code-domain accumulator, and sum->mean
    receiver: all bit-equal across backends — including an all-zero row
    (raw zero scale), which both backends must clamp identically."""
    v = jax.random.normal(jax.random.PRNGKey(7), (37, 256))
    v = v.at[5].set(0.0)
    s = 1.17 * jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    ref = _codec(v, s, KEY, bits=bits, stoch=stoch, backend="reference")
    pal = _codec(v, s, KEY, bits=bits, stoch=stoch, backend="pallas")
    for name, a, b in zip(("packed", "codes", "mean"), ref, pal):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@functools.partial(jax.jit, static_argnames=("bits", "stoch", "backend"))
def _ring_codec(v, s, key, *, bits, stoch, backend):
    """The ring wire's op chain: fused pack+codes encode, fused
    unpack-accumulate, code-sum pack/unpack, sum->mean."""
    packed, codes = B.encode_codes_with_scale(
        v, s, bits=bits, stochastic=stoch, key=key, pack=True,
        backend=backend)
    acc = B.accumulate_codes(packed, codes * 2, bits=bits, backend=backend)
    ps = B.pack_sums(acc, bits=bits, n=3, backend=backend)
    total = B.unpack_sums(ps, bits=bits, n=3, d=v.shape[-1],
                          backend=backend)
    mean = B.decode_sum_mean(total, s, bits=bits, n=3, backend=backend)
    return packed, codes, acc, ps, total, mean


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
def test_ring_codec_bit_identical(bits, stoch):
    """The ring's whole op chain — codes-only encode (with packed
    payload), unpack-accumulate, code-sum pack/unpack, sum->mean — is
    bit-equal across backends under jit, including an all-zero row."""
    v = jax.random.normal(jax.random.PRNGKey(9), (37, 256))
    v = v.at[5].set(0.0)
    s = 1.17 * jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    r = _ring_codec(v, s, KEY, bits=bits, stoch=stoch,
                    backend="reference")
    p = _ring_codec(v, s, KEY, bits=bits, stoch=stoch, backend="pallas")
    names = ("packed", "codes", "acc", "packed_sums", "total", "mean")
    for name, a, b in zip(names, r, p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # the accumulate path reproduces the exact code sum: acc == 3*codes
    np.testing.assert_array_equal(np.asarray(r[2]), 3 * np.asarray(r[1]))
    np.testing.assert_array_equal(np.asarray(r[4]), np.asarray(r[2]))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
def test_compress_allreduce_bit_identical_across_backends(bits, stoch):
    """The full n-worker bucketed allreduce — mean tree AND carried
    errors — is backend-independent bit-for-bit."""
    trees = [_tree(seed=20 + i) for i in range(3)]
    lay = GC.bucket_layout(trees[0], GROUP)
    err0 = jnp.stack([GC.init_error_state(trees[0], GROUP)] * 3)

    @functools.partial(jax.jit, static_argnames=("backend",))
    def run(err, key, *, backend):
        return GC.compress_allreduce(trees, err, bits, key,
                                     stochastic=stoch, backend=backend,
                                     layout=lay)
    m_r, e_r = run(err0, KEY, backend="reference")
    m_p, e_p = run(err0, KEY, backend="pallas")
    np.testing.assert_array_equal(np.asarray(e_r), np.asarray(e_p))
    for k in m_r:
        np.testing.assert_array_equal(
            np.asarray(m_r[k].astype(jnp.float32)),
            np.asarray(m_p[k].astype(jnp.float32)), err_msg=k)


@pytest.mark.parametrize("n", [2, 3, 5])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_compress_reduce_scatter_matches_allreduce(n, backend):
    """The ZeRO-sharded sim extension: `compress_reduce_scatter`'s
    owned segments must be BIT-EQUAL to the corresponding rows of
    `compress_allreduce`'s full mean (same codes, same int32 segment
    sums), its error states identical, and the zero-scale pad rows of
    a ragged last segment must decode to (sign-preserving) zeros.
    n=3/5 exercise ragged segments.  (All-f32 trees: the allreduce
    returns a TREE, so its bf16 leaves would round before this
    comparison re-flattens them, while the sharded form returns the
    raw f32 bucket — the bf16 round-trip is covered by the backend
    parity tests above.)"""
    bits = 4
    trees = [jax.tree.map(lambda a: a.astype(jnp.float32),
                          _tree(seed=40 + i)) for i in range(n)]
    lay = GC.bucket_layout(trees[0], GROUP)
    err0 = jnp.stack([GC.init_error_state(trees[0], GROUP)] * n)

    @functools.partial(jax.jit, static_argnames=())
    def run(err, key):
        full = GC.compress_allreduce(trees, err, bits, key,
                                     stochastic=True, backend=backend,
                                     layout=lay)
        shrd = GC.compress_reduce_scatter(trees, err, bits, key,
                                          stochastic=True,
                                          backend=backend, layout=lay)
        return full, shrd
    (mean, e_full), (segs, e_shrd) = run(err0, KEY)
    np.testing.assert_array_equal(np.asarray(e_full),
                                  np.asarray(e_shrd))
    seg = segs.shape[1]
    assert seg == -(-lay.rows // n)
    # live region only: the bucket's zero-pad TAIL (beyond lay.total)
    # holds harmless nonzero dequant values on the sharded bucket —
    # quantize(0) != 0 under a shared scale — which the allreduce tree
    # round-trip already dropped; both drop it before parameters.
    flat_live = np.asarray(GC.flatten_bucket(mean, lay)
                           ).reshape(-1)[:lay.total]
    sg_live = np.asarray(segs).reshape(-1)[:lay.total]
    np.testing.assert_array_equal(sg_live, flat_live)
    pad = seg * n - lay.rows
    if pad:
        # fully-padded rows (beyond lay.rows) decode against a ZERO
        # scale: sign-preserving zeros
        np.testing.assert_array_equal(
            np.abs(np.asarray(segs)[-1, seg - pad:]),
            np.zeros((pad, lay.group_d)))


def test_sim_zero_sharded_training_parity():
    """The simulated trainer's ZeRO mode (``dp_sharded=True``:
    `compress_reduce_scatter` + segment-owner `apply_bucket_updates` +
    parameter reassembly) tracks the allreduce + per-leaf AdamW path on
    DISTINCT per-worker gradients: bit-identical losses while the
    trajectories coincide, ulp-level tracking after (the two jitted
    programs fuse the model backward differently — the documented
    cross-program drift class of core/boundary.py, not codec or
    optimizer divergence: `apply_bucket_updates` is pinned elementwise
    bit-identical to `apply_updates` below)."""
    from repro.comm import CommConfig
    from repro.configs.base import get_config
    from repro.core.aqsgd import CompressionConfig
    from repro.data.pipeline import Dataset, DatasetConfig
    from repro.training import simulated as sim
    from repro.optim.adamw import AdamWConfig

    cfg = get_config("gpt2-xl-paper", smoke=True).with_(num_layers=2)
    dc = DatasetConfig(num_samples=8, seq_len=16,
                       vocab_size=cfg.vocab_size, kind="synthetic-lm")
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    out = {}
    for sh in (False, True):
        tcfg = sim.SimTrainConfig(
            num_stages=2,
            comm=CommConfig.from_legacy(
                CompressionConfig(mode="aqsgd", fw_bits=4, bw_bits=8),
                dp_grad_bits=4,
                dp_wire="ring-sharded" if sh else ""),
            optimizer=opt, dp_workers=2)
        _, losses = sim.train(cfg, tcfg, Dataset(dc), num_steps=4,
                              batch_size=4, key=jax.random.PRNGKey(0))
        out[sh] = losses
    assert out[True][:2] == out[False][:2], out
    np.testing.assert_allclose(out[True], out[False], rtol=2e-3)


def test_bucket_adamw_bit_identical_to_leaf_adamw():
    """`adamw.apply_bucket_updates` (the segment-owner update of the
    ring-sharded wire) is ELEMENTWISE bit-identical to the per-leaf
    `apply_updates` over chained steps — the anchor that lets the
    sharded pipeline reproduce the replicated optimizer bit-for-bit on
    the same gradient stream."""
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig
    cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    tree = _tree(seed=50)
    tree = jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    grads = jax.tree.map(lambda a: a * 0.01, tree)
    lay = GC.bucket_layout(tree, GROUP)
    w = 2
    seg = -(-lay.rows // w)
    pad = seg * w - lay.rows

    @jax.jit
    def leaf_steps(params, grads):
        st = adamw.init_opt_state(params)
        for _ in range(3):
            params, st = adamw.apply_updates(cfg, params, grads, st)
        return params

    @jax.jit
    def bucket_steps(params, grads):
        st = adamw.init_bucket_opt_state(w, seg, lay.group_d)
        gb = GC.flatten_bucket(grads, lay)
        if pad:
            gb = jnp.pad(gb, ((0, pad), (0, 0)))
        gb = gb.reshape(w, seg, lay.group_d)
        for _ in range(3):
            pb = GC.flatten_bucket(params, lay)
            if pad:
                pb = jnp.pad(pb, ((0, pad), (0, 0)))
            new_pb, st = adamw.apply_bucket_updates(
                cfg, pb.reshape(w, seg, lay.group_d), gb, st)
            params = GC.unflatten_bucket(
                new_pb.reshape(w * seg, lay.group_d)[:lay.rows], lay,
                params)
        return params

    a, b = leaf_steps(tree, grads), bucket_steps(tree, grads)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


@pytest.mark.parametrize("n_ranks,daxes", [
    (2, ("data",)), (3, ("data",)), (5, ("data",)), (8, ("data",)),
    (4, ("pod", "data")), (6, ("pod", "data")),
])
def test_dp_error_layout_matches_train_step(n_ranks, daxes):
    """Layout-drift gate for the sharded DP carries: on every mesh
    shape the workers exercise, `init_dp_error` (what launchers
    allocate) and `make_state_structs` (what `make_train_step` traces
    against) must agree on the dp_error shape, and `init_sharded_opt`
    must produce exactly one `ring_segment_rows` segment per DP rank —
    so the sharded carry cannot silently desync from the wire's
    segment schedule."""
    from types import SimpleNamespace
    from repro.configs.base import get_config
    from repro.core import collectives as C
    from repro.models import model as Mo
    from repro.training import pipeline as PL

    from repro.comm import CommConfig

    cfg = get_config("gpt2-xl-paper", smoke=True).with_(num_layers=2)
    pcfg = PL.PipelineConfig(comm=CommConfig.from_legacy(
        None, dp_grad_bits=4, dp_wire="ring-sharded"))
    params_shape = jax.eval_shape(
        lambda: PL.to_pipeline_params(
            cfg, Mo.init_params(cfg, jax.random.PRNGKey(0)), 2))
    lay = GC.bucket_layout(params_shape, pcfg.comm.dp_group_d)

    err = jax.eval_shape(
        lambda: PL.init_dp_error(pcfg, params_shape, n_ranks))
    assert err.shape == (n_ranks, lay.rows, lay.group_d), err

    # make_state_structs must derive the identical struct (it calls
    # eval_shape of the same init functions — pinned here so a future
    # re-derivation cannot drift)
    shape = {"model": 2}
    if daxes == ("data",):
        shape["data"] = n_ranks
        names = ("data", "model")
    else:
        shape["pod"], shape["data"] = 2, n_ranks // 2
        names = ("pod", "data", "model")
    mesh = SimpleNamespace(axis_names=names, shape=shape)
    meta = {"params_shape": params_shape, "m": 2, "trunk_seq": 16,
            "buffer_samples": 2}
    state, _, _ = PL.make_state_structs(
        cfg, pcfg, meta, mesh, global_batch=2 * n_ranks, seq_len=16)
    assert state["dp_error"].shape == err.shape
    assert state["dp_error"].dtype == jnp.float32

    seg = C.ring_segment_rows(lay.rows, n_ranks)
    opt = jax.eval_shape(
        lambda: PL.init_sharded_opt(pcfg, params_shape, n_ranks))
    assert opt["mu"].shape == (n_ranks, seg, lay.group_d), opt["mu"]
    assert state["opt"]["mu"].shape == opt["mu"].shape
    # ceil-division minimality: covers the bucket, one fewer row per
    # segment would not
    assert seg * n_ranks >= lay.rows
    assert (seg - 1) * n_ranks < lay.rows


@pytest.mark.parametrize("bits", [4, 8])
def test_compress_allreduce_tracks_true_mean(bits):
    """Deterministic sanity: the compressed mean is within one
    quantization cell (of the shared scale) of the exact mean."""
    trees = [_tree(seed=30 + i, scale=0.5 + 0.2 * i) for i in range(4)]
    lay = GC.bucket_layout(trees[0], GROUP)
    err0 = jnp.stack([GC.init_error_state(trees[0], GROUP)] * 4)
    mean, _ = GC.compress_allreduce(trees, err0, bits, KEY,
                                    stochastic=False, layout=lay)
    v = jnp.stack([GC.flatten_bucket(t, lay) for t in trees])
    true = jnp.mean(v, axis=0)
    got = GC.flatten_bucket(mean, lay)
    cell = 2.0 * np.asarray(jnp.max(jnp.abs(v), axis=(0, -1)),
                            np.float32) / ((1 << bits) - 1)
    assert np.max(np.abs(np.asarray(got - true)), axis=None) \
        <= np.max(cell) * 0.5 + 1e-6


# ---------------------------------------------------------------------------
# opt-in on-core PRNG (REPRO_ONCORE_PRNG=1): statistical contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4])
def test_oncore_prng_unbiased_10k_trials(bits, monkeypatch):
    """The on-core PRNG encode path (pltpu.prng_random_bits instead of
    an HBM noise tensor) relaxes ref↔pallas parity to a STATISTICAL
    contract; this 10k-trial unbiasedness gate (the same harness as the
    noise-tensor test above) is what lets it ship.  TPU-only: interpret
    mode has no CPU lowering for prng_seed, so this skips on CPU."""
    from repro.kernels import ops as K

    if not K.oncore_prng_supported():
        pytest.skip("on-core PRNG has no lowering on this backend "
                    "(CPU interpret mode)")
    monkeypatch.setenv("REPRO_ONCORE_PRNG", "1")
    n_trials = 10_000
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 64))
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-12)
    # one fused call over the tiled batch: every row draws iid on-core
    # noise (blocks seed with the key words + grid position)
    xt = jnp.tile(x, (n_trials, 1))
    st = jnp.tile(scale, (n_trials, 1))
    codes = B.encode_codes_with_scale(xt, st, bits=bits, stochastic=True,
                                      key=jax.random.PRNGKey(6),
                                      backend="pallas")
    q = B.decode_sum_mean(codes, st, bits=bits, n=1, backend="reference")
    est = np.asarray(q).reshape(n_trials, 4, 64).mean(axis=0)
    cell = 2.0 * np.asarray(scale) / ((1 << bits) - 1)
    bound = 5.0 * cell / (2.0 * np.sqrt(n_trials))
    err = np.abs(est - np.asarray(x))
    assert np.max(err / bound) < 1.0, float(np.max(err / bound))
    # and the stream is deterministic given the key
    codes2 = B.encode_codes_with_scale(xt, st, bits=bits, stochastic=True,
                                       key=jax.random.PRNGKey(6),
                                       backend="pallas")
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes2))


def test_oncore_prng_gate_refuses_without_support(monkeypatch):
    """REPRO_ONCORE_PRNG=1 on a backend that cannot lower prng_seed must
    fail loudly at the boundary layer, not crash inside lowering."""
    from repro.kernels import ops as K

    if K.oncore_prng_supported():
        pytest.skip("on-core PRNG supported here; gate cannot trip")
    monkeypatch.setenv("REPRO_ONCORE_PRNG", "1")
    v = jax.random.normal(jax.random.PRNGKey(11), (8, 64))
    s = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    with pytest.raises(ValueError, match="REPRO_ONCORE_PRNG"):
        B.encode_codes_with_scale(v, s, bits=4, stochastic=True, key=KEY,
                                  backend="pallas")


# ---------------------------------------------------------------------------
# chunked encoder determinism (the double-buffered ring's sender)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
@pytest.mark.parametrize("n,chunks", [(2, 2), (3, 2), (4, 3), (4, 4)])
def test_chunk_encoder_bit_identical_to_monolithic(bits, stoch, n,
                                                   chunks, monkeypatch):
    """`collectives.make_chunk_encoder` — the double-buffered ring's
    per-chunk sender — reassembles to the BIT-IDENTICAL packed payload,
    codes, and error carry `grad_compress.ef_encode` produces for the
    same key, for every chunk count including ragged ones.  With the
    on-core PRNG opt-in OFF, the chunked path's once-drawn row-sliced
    noise is exactly the boundary `_noise` draw, so stochastic rounding
    is chunking-invariant too (the on-core stream is grid-position-
    dependent, which is why the encoder pins noise explicitly)."""
    from repro.core import collectives as C

    monkeypatch.delenv("REPRO_ONCORE_PRNG", raising=False)
    rows, d = 79, 128
    v = jax.random.normal(jax.random.PRNGKey(21), (rows, d)) * 0.7
    v = v.at[3].set(0.0)
    s = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    packed_m, codes_m, err_m = GC.ef_encode(v, s, bits, KEY,
                                            stochastic=stoch,
                                            backend="reference",
                                            pack=True)
    seg = C.ring_segment_rows(rows, n)
    bounds = C.ring_chunk_bounds(seg, chunks)
    enc = C.make_chunk_encoder(v, s, bits, KEY, n, bounds,
                               stochastic=stoch, backend="reference")
    packed_c = jnp.concatenate([enc(ci)[0] for ci in
                                range(len(bounds))], axis=1)
    codes_c = jnp.concatenate([enc(ci)[1] for ci in
                               range(len(bounds))], axis=1)
    live_p = packed_c.reshape(n * seg, -1)[:rows]
    live_c = codes_c.reshape(n * seg, d)[:rows]
    np.testing.assert_array_equal(np.asarray(live_p),
                                  np.asarray(packed_m))
    np.testing.assert_array_equal(np.asarray(live_c),
                                  np.asarray(codes_m))
    # pad rows (ragged last segment) are zeroed in code space
    pad_c = np.asarray(codes_c.reshape(n * seg, d)[rows:])
    assert pad_c.size == 0 or not pad_c.any()
    # the error carry recomputed from the reassembled codes matches
    q = B.decode_sum_mean(live_c, s, bits=bits, n=1,
                          backend="reference")
    np.testing.assert_array_equal(np.asarray(v - q), np.asarray(err_m))


# ---------------------------------------------------------------------------
# the gradient path is fused end-to-end (no unfused quantize calls)
# ---------------------------------------------------------------------------

def test_gradient_path_has_no_unfused_quantize_calls():
    """Every quantize/pack/unpack on the gradient path must route
    through core.boundary's fused backend-selectable ops — never the
    per-leaf `Q.qdq` loop this wire replaced, nor any other unfused
    `Q.*` chain (same gate PR 1 established for the activation path).
    The assertion lives in the `no-unfused-quantize` lint rule
    (repro.analysis), which covers grad_compress, collectives,
    simulated and pipeline alias-proof; this is its one-line test
    invocation."""
    from repro.analysis import run_rule

    assert run_rule("no-unfused-quantize") == []


@pytest.mark.parametrize("wire", ["ring_ef_reduce_mean_bucket",
                                  "ring_ef_reduce_scatter_bucket"])
def test_ring_segments_are_row_slices(wire):
    """The ring cuts its (n*seg, .) bucket into segments by row slices,
    never by a reshape to or from (n, seg, .): on a TPU that reshape is
    a relayout when seg is not a multiple of the row tile, and compiles
    in time linear in the rows — minutes for a 1.5B-parameter bucket."""
    from repro.core import collectives as C

    n, seg, d = 2, 37, 128
    rows = n * seg - 1                       # a ragged last segment
    fn = functools.partial(getattr(C, wire), axis_name="data", bits=4,
                           backend="reference")
    jaxpr = jax.make_jaxpr(
        lambda v, e, k: fn(v, e, key=k), axis_env=[("data", n)])(
        jnp.zeros((rows, d)), jnp.zeros((rows, d)), jax.random.PRNGKey(0))
    split = [(tuple(e.invars[0].aval.shape), tuple(o.aval.shape))
             for e in jaxpr.jaxpr.eqns if e.primitive.name == "reshape"
             for o in e.outvars]
    assert not [s for s in split
                if (n, seg) in (s[0][:2], s[1][:2])], split


# ---------------------------------------------------------------------------
# Fig. 5a convergence regression (slow tier -> nightly CI)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fig5a_aqsgd_grad4_tracks_fp32():
    """End-to-end communication compression (Fig. 5a): AQ-SGD fw3/bw6
    plus 4-bit error-feedback gradient compression fine-tunes to within
    tolerance of FP32, and beats DirectQ under the same gradient wire —
    so a quality regression in the compressed wire fails CI nightly
    instead of silently shipping."""
    from benchmarks.common import finetune, tail_loss

    steps = 50
    l_fp, _ = finetune("fp32", steps=steps)
    l_aq, _ = finetune("aqsgd", 3, 6, steps=steps, dp_grad_bits=4,
                       dp_workers=2)
    l_dq, _ = finetune("directq", 3, 6, steps=steps, dp_grad_bits=4,
                       dp_workers=2)
    fp, aq, dq = tail_loss(l_fp), tail_loss(l_aq), tail_loss(l_dq)
    assert np.isfinite([fp, aq, dq]).all(), (fp, aq, dq)
    assert aq < dq, f"AQ-SGD {aq:.4f} must beat DirectQ {dq:.4f}"
    # "tracks FP32": the AQ-SGD gap stays well under half the DirectQ
    # gap AND under an absolute drift cap (reference run: fp 3.01,
    # aq 3.20, dq 3.71 — gaps 0.20 vs 0.70)
    assert abs(aq - fp) < 0.5 * abs(dq - fp) + 1e-6, (fp, aq, dq)
    assert abs(aq - fp) < 0.35, \
        f"AQ-SGD+grad4 tail {aq:.4f} drifted from FP32 {fp:.4f}"
