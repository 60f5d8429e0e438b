"""Pallas flash-attention kernels (interpret mode) against the model's
scan path `layers.flash_attention`: outputs over shapes, dtypes, GQA,
windows and softcap; the forward/backward pair's values and gradients;
the chip's bf16 operand rounding against the scan's arithmetic rounded
at the same points; and the kernels run per shard of rows under
`layers.rows_over`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.kernels import flash_attention as FA
from repro.models import layers as L


def _setup(b, h, hk, s, hd, dtype, seed=0):
    """q (B, S, H, hd), k, v (B, S, Hk, hd) and positions 0..S-1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hk, hd), jnp.float32).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return q, k, v, pos


@pytest.mark.parametrize("b,h,hk,s,hd,bq,bk", [
    (1, 2, 2, 64, 32, 16, 16),
    (2, 4, 2, 128, 64, 32, 64),     # GQA groups=2
    (1, 8, 1, 64, 128, 64, 16),     # MQA
    (1, 2, 2, 96, 32, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_shapes_dtypes(b, h, hk, s, hd, bq, bk, dtype):
    q, k, v, pos = _setup(b, h, hk, s, hd, dtype)
    o = FA.flash_attention(q, k, v, pos, pos, 10 ** 9, block_q=bq,
                           block_k=bk)
    ref = L.flash_attention(q, k, v, q_pos=pos, k_pos=pos, window=10 ** 9,
                            block_k=bk)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,cap,causal", [
    (9, 0.0, True), (10 ** 9, 30.0, True), (17, 4.0, True),
    (10 ** 9, 0.0, False),
])
def test_flash_kernel_masks(window, cap, causal):
    q, k, v, pos = _setup(1, 2, 2, 64, 32, jnp.float32, seed=5)
    o = FA.flash_attention(q, k, v, pos, pos, window, causal=causal,
                           softcap=cap, block_q=16, block_k=16)
    ref = L.flash_attention(q, k, v, q_pos=pos, k_pos=pos, window=window,
                            causal=causal, attn_softcap=cap, block_k=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_matches_model_layer_path():
    """Kernel == the JAX-level flash used by the model trunk."""
    b, s, h, hd = 1, 64, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32)
    jax_flash = L.flash_attention(q, k, v, q_pos=pos, k_pos=pos,
                                  window=11, block_k=16)
    kernel = FA.flash_attention(q, k, v, pos, pos, 11, block_q=16,
                                block_k=16)
    np.testing.assert_allclose(np.asarray(jax_flash), np.asarray(kernel),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The kernel pair (forward, dK/dV, dQ) against the model's scan path
# ---------------------------------------------------------------------------

BIG = 10 ** 9
BLOCK_K = 128
# interpret mode computes as the host's f32 dot, as the scan does here.
# With the chip's bf16 rounding against `_rounded_scan`'s, only the f32
# sums' order differs, and the few bf16 roundings it flips: at most
# 2.0e-4 of the largest value over CASES; the unrounded kernel reads
# 2.1e-3 and more, at least twice the tolerance.
TOL = {"host": 2e-5, "bf16": 5e-4}


def _bshd(b, sq, sk, h, hk, hd, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, sq, h, hd)),
            jax.random.normal(ks[1], (b, sk, hk, hd)),
            jax.random.normal(ks[2], (b, sk, hk, hd)),
            jax.random.normal(ks[3], (b, sq, h, hd)))


def _kernel_path(q, k, v, q_pos, k_pos, window, causal, cap):
    return FA.flash_attention(q, k, v, q_pos, k_pos, window, causal=causal,
                              softcap=cap, block_k=BLOCK_K)


def _scan_path(q, k, v, q_pos, k_pos, window, causal, cap):
    return L.flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                             window=window, causal=causal,
                             attn_softcap=cap, block_k=BLOCK_K)


def _rounded_scan(q, k, v, q_pos, k_pos, window, causal, cap, do):
    """The scan's forward (online softmax over BLOCK_K keys) and flash
    backward with each matmul operand rounded to bf16 where the chip's
    one-pass dot rounds it: (o, (dq, dk, dv)) for output cotangent do."""
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    mm = lambda eq, a, b: jnp.einsum(eq, bf(a), bf(b),
                                     precision="highest")
    g = q.shape[2] // k.shape[2]
    scale = 1.0 / np.sqrt(q.shape[3])
    q, do = q.transpose(0, 2, 1, 3), do.transpose(0, 2, 1, 3)
    k, v = (jnp.repeat(x, g, 2).transpose(0, 2, 1, 3) for x in (k, v))
    vis = k_pos[:, None, None, :] > q_pos[:, None, :, None] - window
    if causal:
        vis &= k_pos[:, None, None, :] <= q_pos[:, None, :, None]

    def scores(u):
        s = cap * jnp.tanh(u / cap) if cap else u
        return s, (1.0 - jnp.square(s / cap) if cap else 1.0)

    s_fwd = jnp.where(vis, scores(mm("bhqd,bhkd->bhqk", q * scale, k))[0],
                      L.NEG_INF)
    m = jnp.full(q.shape[:3] + (1,), L.NEG_INF)
    l = jnp.zeros_like(m)
    acc = jnp.zeros_like(q)
    for j in range(0, k.shape[2], BLOCK_K):
        s = s_fwd[..., j:j + BLOCK_K]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p, corr = jnp.exp(s - m_new), jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        acc = acc * corr + mm("bhqk,bhkd->bhqd", p, v[:, :, j:j + BLOCK_K])
        m = m_new
    o = acc / l
    s, dsdu = scores(mm("bhqd,bhkd->bhqk", q, k) * scale)
    p = jnp.exp(jnp.where(vis, s, L.NEG_INF) - (m + jnp.log(l)))
    dp = mm("bhqd,bhkd->bhqk", do, v)
    ds = jnp.where(vis, p * (dp - jnp.sum(do * o, -1, keepdims=True))
                   * dsdu, 0.0)
    dq = mm("bhqk,bhkd->bhqd", ds, k) * scale
    dk = mm("bhqk,bhqd->bhkd", ds, q) * scale
    dv = mm("bhqk,bhqd->bhkd", p, do)
    heads_last = lambda x: x.transpose(0, 2, 1, 3)
    kv_heads = lambda x: heads_last(x.reshape(x.shape[0], -1, g,
                                              *x.shape[2:]).sum(2))
    return heads_last(o), (heads_last(dq), kv_heads(dk), kv_heads(dv))


def _gap(a, r):
    """max |a - r| over max |r|."""
    return float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))


# (b, sq, sk, h, hk, hd, window, cap, causal, k_offset)
CASES = {
    # Sq = 2 blocks: tile (q 0, k 1) is hidden by the causal mask
    "causal_skip": (1, 256, 256, 2, 2, 64, BIG, 0.0, True, 0),
    "window": (1, 384, 384, 2, 2, 64, 100, 0.0, True, 0),
    "softcap": (2, 256, 256, 2, 2, 64, BIG, 30.0, True, 0),
    "gqa": (1, 256, 256, 4, 2, 128, BIG, 0.0, True, 0),
    "mqa_window_softcap": (1, 256, 256, 4, 1, 64, 77, 5.0, True, 0),
    # whisper cross-attention: keys not a block multiple, padded at -1e9
    "noncausal_padded": (2, 128, 200, 2, 2, 64, BIG, 0.0, False, 0),
    # prefill into a cache: queries at positions 100.. over 384 slots
    "cache_offset": (1, 256, 384, 2, 2, 64, BIG, 0.0, True, 100),
}


@pytest.mark.parametrize("mxu", ["host", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pair_matches_scan_path(case, mxu, monkeypatch, request):
    """Output and jax.grad (dq, dk, dv) of the kernels against the scan:
    with the host's f32 dot ('host'), and with the MXU operands rounded
    to bf16 as a TPU compiles them ('bf16') against `_rounded_scan`,
    closely enough that the unrounded kernel fails the same check."""
    b, sq, sk, h, hk, hd, window, cap, causal, off = CASES[case]
    q, k, v, ct = _bshd(b, sq, sk, h, hk, hd, seed=len(case))
    q_pos = jnp.broadcast_to(off + jnp.arange(sq, dtype=jnp.int32),
                             (b, sq))
    k_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    args = (q_pos, k_pos, window, causal, cap)

    def kernel():
        o = _kernel_path(q, k, v, *args)
        grads = jax.grad(lambda q, k, v: jnp.sum(
            _kernel_path(q, k, v, *args) * ct), (0, 1, 2))(q, k, v)
        return o, grads

    if mxu == "host":
        o_ref = _scan_path(q, k, v, *args)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            _scan_path(q, k, v, *args) * ct), (0, 1, 2))(q, k, v)
    else:
        o_ref, g_ref = _rounded_scan(q, k, v, *args, ct)
        unrounded = kernel()
        assert min(_gap(a, r) for a, r in zip(
            (unrounded[0], *unrounded[1]), (o_ref, *g_ref))) \
            > 2 * TOL[mxu]
        monkeypatch.setattr(FA, "_mxu", lambda interpret: jnp.bfloat16)
        FA._make.cache_clear()
        request.addfinalizer(FA._make.cache_clear)
    o, grads = kernel()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=TOL[mxu], atol=TOL[mxu])
    for a, r, name in zip(grads, g_ref, "qkv"):
        assert _gap(a, r) < TOL[mxu], (f"d{name}", _gap(a, r))


def test_kernel_pair_traced_window_in_scan():
    """window as a traced per-layer scalar inside lax.scan, as the
    layer stack passes `window_vector`: values and gradients."""
    q, k, v, _ = _bshd(1, 384, 384, 2, 2, 64, seed=3)
    pos = jnp.broadcast_to(jnp.arange(384, dtype=jnp.int32), (1, 384))
    windows = jnp.array([100, BIG, 200], jnp.int32)

    def stack(path):
        def f(q):
            def body(c, w):
                return path(c, k, v, pos, pos, w, True, 0.0), None
            return jnp.sum(jnp.sin(jax.lax.scan(body, q, windows)[0]))
        return f

    np.testing.assert_allclose(float(stack(_kernel_path)(q)),
                               float(stack(_scan_path)(q)), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jax.grad(stack(_kernel_path))(q)),
        np.asarray(jax.grad(stack(_scan_path))(q)), rtol=2e-5, atol=2e-5)


def test_hidden_tiles_are_skipped():
    """The scalar-prefetch bounds mark exactly the hidden tiles dead, and
    the tiles whose every key is visible full (they skip the mask)."""
    pos = jnp.broadcast_to(jnp.arange(384, dtype=jnp.int32), (1, 384))
    dead, part, full = (False, False), (True, False), (True, True)

    def tiles(window):
        bounds = FA._bounds(pos, pos, jnp.int32(window), 128, 128)
        return [[tuple(bool(x) for x in FA._tile(bounds, 0, qi, ki, 3, 3,
                                                  True))
                 for ki in range(3)] for qi in range(3)]

    # causal hides k > q; below the diagonal every key is visible
    assert tiles(BIG) == [[part, dead, dead],
                          [full, part, dead],
                          [full, full, part]]
    # a window of 100 also hides (q 2, k 0) and cuts into (q 1, k 0)
    assert tiles(100) == [[part, dead, dead],
                          [part, part, dead],
                          [dead, part, part]]
    padded = jnp.pad(pos[:, :200], ((0, 0), (0, 56)),
                     constant_values=FA.PAD_POS)
    bounds = FA._bounds(pos[:, :128], padded, jnp.int32(BIG), 128, 128)
    assert [bool(x) for x in FA._tile(bounds, 0, 0, 1, 1, 2, False)] == \
        [True, False]
    bounds = FA._bounds(pos[:, :128], jnp.full((1, 128), FA.PAD_POS),
                        jnp.int32(BIG), 128, 128)
    assert not FA._tile(bounds, 0, 0, 0, 1, 1, False)[0]


@pytest.mark.parametrize("sq,block_k,hd,want", [
    (1024, 512, 64, 512),     # the gpt2-xl cell
    (384, 512, 128, 128),
    (4096, 512, 256, 512),
    (37, 512, 64, None),      # a ragged prefill: the scan
    (1024, 100, 64, None),    # a key block that is not a lane multiple
    (1024, 512, 80, None),    # a head dim that fills no whole pass
])
def test_block_q_for_routes_shapes(sq, block_k, hd, want):
    assert FA.block_q_for(sq, block_k, hd) == want


def test_rows_over_runs_the_kernels_per_shard():
    """Under `layers.rows_over` (a GSPMD section, where a Mosaic call
    cannot be partitioned) the kernels run inside a shard_map over the
    rows' axes, with the scan's values and gradients; a call already
    inside a shard_map runs them as they are."""
    q, k, v, ct = _bshd(2, 256, 256, 2, 2, 64, seed=7)
    pos = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rows = jax.sharding.PartitionSpec("data")

    def sharded(q, k, v):
        with L.rows_over(mesh, "data"):
            return L._kernel_attention(q, k, v, pos, pos, jnp.int32(BIG),
                                       causal=True, softcap=0.0,
                                       block_k=BLOCK_K)

    def n_shard_maps(fn):
        return str(jax.make_jaxpr(fn)(q, k, v)).count("shard_map[")

    assert n_shard_maps(sharded) == 1
    assert n_shard_maps(jax.shard_map(
        sharded, mesh=mesh, in_specs=rows, out_specs=rows,
        check_vma=False)) == 1
    args = (pos, pos, BIG, True, 0.0)
    np.testing.assert_allclose(np.asarray(jax.jit(sharded)(q, k, v)),
                               np.asarray(_scan_path(q, k, v, *args)),
                               rtol=TOL["host"], atol=TOL["host"])
    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(sharded(q, k, v) * ct),
                             (0, 1, 2)))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        _scan_path(q, k, v, *args) * ct), (0, 1, 2))(q, k, v)
    for a, r, name in zip(grads, g_ref, "qkv"):
        assert _gap(a, r) < TOL["host"], (f"d{name}", _gap(a, r))
