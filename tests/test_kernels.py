"""Pallas kernels vs pure-jnp oracles, swept over shapes and dtypes
(interpret mode on CPU — the kernel bodies execute for real)."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


def _mk(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("B,S,H,D", [(1, 128, 2, 64), (2, 256, 4, 128),
                                     (1, 512, 1, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, D, causal, window, dtype):
    q = _mk(0, (B, S, H, D), dtype)
    k = _mk(1, (B, S, H, D), dtype)
    v = _mk(2, (B, S, H, D), dtype)
    scale = D ** -0.5
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              scale=scale, block_q=128, block_k=128)
    qf = q.swapaxes(1, 2).reshape(B * H, S, D)
    kf = k.swapaxes(1, 2).reshape(B * H, S, D)
    vf = v.swapaxes(1, 2).reshape(B * H, S, D)
    exp = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window,
                                  scale=scale)
    exp = exp.reshape(B, H, S, D).swapaxes(1, 2)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                exp.astype(jnp.float32))))
    assert err < TOL[dtype], err


@pytest.mark.parametrize("B,H,Hkv,D,Skv", [(2, 8, 2, 64, 256),
                                           (1, 4, 4, 128, 512),
                                           (3, 16, 1, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, H, Hkv, D, Skv, dtype):
    q = _mk(0, (B, H, D), dtype)
    ck = _mk(1, (B, Skv, Hkv, D), dtype)
    cv = _mk(2, (B, Skv, Hkv, D), dtype)
    lengths = jnp.array([1 + 37 * i % Skv for i in range(B)], jnp.int32)
    lengths = jnp.maximum(lengths, 1)
    out = ops.flash_decode(q, ck, cv, lengths, scale=D ** -0.5,
                           block_k=128)
    exp = ref.flash_decode_ref(q, ck, cv, lengths, scale=D ** -0.5)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                exp.astype(jnp.float32))))
    assert err < TOL[dtype], err


def test_attend_decode_kernel_routing():
    """cfg.use_kernels routes single-token decode through the length-masked
    Pallas flash-decode; logits must match the XLA grouped-attention path."""
    from repro.configs import get_reduced_config
    from repro.models import build_model
    cfg_x = get_reduced_config("smollm2-1.7b")
    cfg_k = get_reduced_config("smollm2-1.7b", use_kernels=True)
    model_x, model_k = build_model(cfg_x), build_model(cfg_k)
    params = model_x.init(jax.random.PRNGKey(0))
    cache = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape,
                                    a.dtype) * 0.1,
        model_x.init_cache(2, 32, jnp.float32))
    toks = jnp.array([[5], [9]], jnp.int32)
    lengths = jnp.array([3, 17], jnp.int32)
    lx, _ = model_x.decode_step(params, toks, lengths, cache)
    lk, _ = model_k.decode_step(params, toks, lengths, cache)
    err = float(jnp.max(jnp.abs(lx - lk)))
    assert err < 2e-4, err


@pytest.mark.parametrize("Skv,want", [(600, 120), (5, 5), (131, None),
                                      (2 * 131, None)])
def test_flash_decode_blocks_divide_the_cache(Skv, want):
    """The contiguous-cache block is the largest divisor of the cache
    length that fits; a length with no divisor of at least 8 tokens is
    refused rather than run a grid step per token or two."""
    from repro.kernels.decode_attention import _block_tokens
    if want is None:
        with pytest.raises(ValueError, match="multiple of 8"):
            _block_tokens(Skv, 32, 512)
    else:
        assert _block_tokens(Skv, 32, 512) == want


def test_flash_decode_active_mask():
    """The megastep's per-slot mask: inactive slots' lengths are forced to
    0 so every KV block is skipped; active slots match the oracle."""
    B, H, Hkv, D, Skv = 4, 8, 2, 64, 256
    q = _mk(0, (B, H, D), jnp.float32)
    ck = _mk(1, (B, Skv, Hkv, D), jnp.float32)
    cv = _mk(2, (B, Skv, Hkv, D), jnp.float32)
    lengths = jnp.array([100, 7, 200, 256], jnp.int32)
    active = jnp.array([True, False, True, False])
    out = ops.flash_decode(q, ck, cv, lengths, scale=D ** -0.5,
                           block_k=128, active=active)
    exp = ref.flash_decode_ref(q, ck, cv, lengths, scale=D ** -0.5)
    for b in range(B):
        if bool(active[b]):
            err = float(jnp.max(jnp.abs(out[b] - exp[b])))
            assert err < TOL[jnp.float32], (b, err)
        else:
            assert float(jnp.max(jnp.abs(out[b]))) == 0.0, b


@pytest.mark.parametrize("B,S,H,N,P,chunk", [(1, 128, 2, 16, 32, 32),
                                             (2, 256, 1, 64, 64, 128),
                                             (1, 64, 4, 8, 16, 64)])
def test_ssd_scan_sweep(B, S, H, N, P, chunk):
    C = _mk(0, (B, S, H, N), jnp.float32)
    Bm = _mk(1, (B, S, H, N), jnp.float32)
    v = _mk(2, (B, S, H, P), jnp.float32)
    la = -jax.nn.softplus(_mk(3, (B, S, H), jnp.float32))
    y, st = ops.ssm_scan(C, Bm, v, la, chunk=chunk)
    qf = C.swapaxes(1, 2).reshape(B * H, S, N)
    kf = Bm.swapaxes(1, 2).reshape(B * H, S, N)
    vf = v.swapaxes(1, 2).reshape(B * H, S, P)
    laf = la.swapaxes(1, 2).reshape(B * H, S, 1)
    ye, ste = ref.ssd_scan_ref(qf, kf, vf, laf)
    ye = ye.reshape(B, H, S, P).swapaxes(1, 2)
    ste = ste.reshape(B, H, N, P)
    assert float(jnp.max(jnp.abs(y - ye))) < 2e-3
    assert float(jnp.max(jnp.abs(st - ste))) < 2e-3


@pytest.mark.parametrize("E,C,d,f", [(2, 128, 256, 128), (8, 256, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_sweep(E, C, d, f, dtype):
    x = _mk(0, (E, C, d), dtype)
    w = _mk(1, (E, d, f), dtype)
    out = ops.grouped_gemm(x, w)
    exp = ref.grouped_gemm_ref(x, w)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                exp.astype(jnp.float32))))
    # relative tolerance: contraction depth d
    assert err < (5e-3 if dtype == jnp.float32 else 1.0) * (d ** 0.5), err


def test_flash_attention_jit_grad_safe():
    """The kernel path is jit-compatible; grads flow via the jnp fallback
    in training (kernels are inference-path)."""
    q = _mk(0, (1, 128, 2, 64), jnp.float32)
    out = jax.jit(lambda a: ops.flash_attention(a, a, a, causal=True,
                                                scale=0.125))(q)
    assert out.shape == q.shape


# ------------------------------------------------------------ paged decode --
def _paged_setup(key, B, npages, num_pages, page, tail, dtype):
    """Random pool + per-slot page table: each slot owns ``npages`` distinct
    physical pages, drawn without overlap across slots; the trash page is
    index ``num_pages``."""
    import numpy as np
    rng = np.random.RandomState(key)
    pool = _mk(key, (num_pages + 1,) + (page,) + tail, dtype)
    ids = rng.permutation(num_pages)[:B * npages]
    pt = jnp.asarray(ids.reshape(B, npages).astype(np.int32))
    return pool, pt


@pytest.mark.parametrize("page,npages", [(8, 4), (16, 2), (32, 3), (7, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_sweep(page, npages, dtype):
    B, H, Hkv, D = 3, 8, 2, 64
    num_pages = 2 * B * npages
    kp, pt = _paged_setup(1, B, npages, num_pages, page, (Hkv, D), dtype)
    vp, _ = _paged_setup(2, B, npages, num_pages, page, (Hkv, D), dtype)
    q = _mk(0, (B, H, D), dtype)
    cap = npages * page
    # odd lengths: page-boundary, mid-page, single-token
    lengths = jnp.array([cap, (cap // 2) | 1, 1][:B], jnp.int32)
    out = ops.paged_flash_decode(q, kp, vp, pt, lengths, scale=D ** -0.5)
    exp = ref.paged_decode_ref(q, kp, vp, pt, lengths, scale=D ** -0.5)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                exp.astype(jnp.float32))))
    assert err < TOL[dtype], err


def test_paged_flash_decode_inactive_slot_is_finite():
    """lengths == 0 (free/finished slot): every page is skipped; the output
    row must be finite garbage the caller can discard — never NaN."""
    B, H, Hkv, D, page, npages = 2, 4, 2, 64, 8, 3
    num_pages = 2 * B * npages
    kp, pt = _paged_setup(3, B, npages, num_pages, page, (Hkv, D),
                          jnp.float32)
    vp, _ = _paged_setup(4, B, npages, num_pages, page, (Hkv, D),
                         jnp.float32)
    q = _mk(0, (B, H, D), jnp.float32)
    lengths = jnp.array([13, 0], jnp.int32)
    out = ops.paged_flash_decode(q, kp, vp, pt, lengths, scale=D ** -0.5)
    assert bool(jnp.all(jnp.isfinite(out)))
    exp = ref.paged_decode_ref(q, kp, vp, pt, lengths[:1], scale=D ** -0.5)
    err = float(jnp.max(jnp.abs(out[:1] - exp[:1])))
    assert err < TOL[jnp.float32], err


def test_paged_flash_decode_trash_columns_masked():
    """Columns past a slot's reservation point at the TRASH page; the
    length mask must keep whatever lives there out of the result."""
    B, H, Hkv, D, page, npages = 2, 4, 2, 64, 8, 4
    num_pages = 2 * B * npages
    kp, pt = _paged_setup(5, B, npages, num_pages, page, (Hkv, D),
                          jnp.float32)
    vp, _ = _paged_setup(6, B, npages, num_pages, page, (Hkv, D),
                         jnp.float32)
    q = _mk(0, (B, H, D), jnp.float32)
    lengths = jnp.array([11, 2 * page], jnp.int32)   # 2 resp. 2 pages live
    # redirect the dead tail columns to trash and poison the trash page
    pt_trash = pt.at[:, 2:].set(num_pages)
    kp = kp.at[num_pages].set(1e4)
    vp = vp.at[num_pages].set(1e4)
    out = ops.paged_flash_decode(q, kp, vp, pt_trash, lengths,
                                 scale=D ** -0.5)
    exp = ops.paged_flash_decode(q, kp, vp, pt, lengths, scale=D ** -0.5)
    err = float(jnp.max(jnp.abs(out - exp)))
    assert err < TOL[jnp.float32], err


@pytest.mark.parametrize("page,npages", [(8, 4), (16, 3), (7, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_decode_sweep(page, npages, dtype):
    B, H, R, Dr = 3, 8, 32, 16
    num_pages = 2 * B * npages
    ckv, pt = _paged_setup(7, B, npages, num_pages, page, (R,), dtype)
    kr, _ = _paged_setup(8, B, npages, num_pages, page, (Dr,), dtype)
    ql = _mk(0, (B, H, R), dtype)
    qr = _mk(1, (B, H, Dr), dtype)
    cap = npages * page
    lengths = jnp.array([cap, (cap // 2) | 1, 1][:B], jnp.int32)
    scale = (R + Dr) ** -0.5
    out = ops.paged_mla_decode(ql, qr, ckv, kr, pt, lengths, scale=scale)
    exp = ref.paged_mla_decode_ref(ql, qr, ckv, kr, pt, lengths,
                                   scale=scale)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                exp.astype(jnp.float32))))
    assert err < TOL[dtype], err


def test_paged_mla_decode_inactive_slot_is_finite():
    B, H, R, Dr, page, npages = 2, 4, 32, 16, 8, 3
    num_pages = 2 * B * npages
    ckv, pt = _paged_setup(9, B, npages, num_pages, page, (R,), jnp.float32)
    kr, _ = _paged_setup(10, B, npages, num_pages, page, (Dr,), jnp.float32)
    ql = _mk(0, (B, H, R), jnp.float32)
    qr = _mk(1, (B, H, Dr), jnp.float32)
    lengths = jnp.array([9, 0], jnp.int32)
    out = ops.paged_mla_decode(ql, qr, ckv, kr, pt, lengths,
                               scale=(R + Dr) ** -0.5)
    assert bool(jnp.all(jnp.isfinite(out)))
