"""Pallas kernels vs pure-jnp oracles (interpret mode), with
shape/dtype sweeps per the deliverable spec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis only powers the property-based sweep below; the directed
# corpus must still run (tier-1) when it isn't installed
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # pragma: no cover - env-dependent
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("variant", ops.COPY_VARIANTS)
@pytest.mark.parametrize("shape", [(17,), (300, 7), (1024, 129), (5, 3, 11)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_symm_copy(variant, shape, dtype):
    n = int(np.prod(shape))
    if dtype == jnp.int32:
        x = jnp.arange(n, dtype=dtype).reshape(shape)
    else:
        x = jax.random.normal(KEY, shape).astype(dtype)
    y = ops.symm_copy(x, variant)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref.copy_ref(x)))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 5000),
           variant=st.sampled_from(list(ops.COPY_VARIANTS)))
    def test_symm_copy_property(n, variant):
        x = jnp.arange(n, dtype=jnp.float32) * 0.5 - 100.0
        y = ops.symm_copy(x, variant)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


@pytest.mark.parametrize("op", ["sum", "max", "min", "prod"])
@pytest.mark.parametrize("variant", ops.COMBINE_VARIANTS)
def test_combine(op, variant):
    a = jax.random.normal(KEY, (333, 5))
    b = jax.random.normal(jax.random.PRNGKey(1), (333, 5))
    y = ops.combine(a, b, op, variant)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.combine_ref(a, b, op)),
                               rtol=1e-6)


def test_combine_shape_mismatch():
    with pytest.raises(ValueError):
        ops.combine(jnp.zeros((4,)), jnp.zeros((5,)))


@pytest.mark.parametrize(
    "b,h,hkv,t,s,d,causal,window",
    [(2, 4, 2, 128, 128, 64, True, None),
     (1, 8, 1, 100, 100, 32, True, None),     # MQA, ragged seq
     (2, 4, 4, 128, 128, 64, False, None),
     (1, 4, 2, 256, 256, 64, True, 96),       # sliding window
     (1, 2, 2, 64, 64, 128, True, None)])
def test_flash_attention_kernel(b, h, hkv, t, s, d, causal, window):
    q = jax.random.normal(KEY, (b, h, t, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, s, d), jnp.float32)
    y = ops.attention(q, k, v, causal=causal, window=window,
                      block_q=64, block_kv=64)
    yr = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(KEY, (1, 4, 64, 32)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 32)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 64, 32)).astype(dtype)
    y = ops.attention(q, k, v, block_q=32, block_kv=32)
    yr = ref.attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)


def test_model_flash_vs_ref_with_grads():
    """The jnp blocked attention (model-side) — fwd and custom-VJP bwd."""
    from repro.models.flash import blocked_attention
    b, h, hkv, t, d = 1, 4, 2, 96, 32
    q = jax.random.normal(KEY, (b, t, h, d))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, t, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, t, hkv, d))

    def f_blocked(q, k, v):
        return (blocked_attention(q, k, v, causal=True, block_q=32,
                                  block_kv=32) ** 2).sum()

    def f_ref(q, k, v):
        r = ref.attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                              jnp.moveaxis(v, 1, 2), causal=True)
        return (jnp.moveaxis(r, 1, 2) ** 2).sum()

    g1 = jax.grad(f_blocked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


# ======================================================================
# prefill-window kernel vs its jnp oracle (directed parity corpus)
# ======================================================================
from repro.kernels import paged_attention as pa  # noqa: E402


def _window_case(seed, B, C, H, Hkv, D, P, slots, dtype=jnp.float32,
                 start=None, n_tok=None):
    """A random paged window: every sequence gets its own live pages
    (null-padded table past them), `start` placed so the window fits
    inside the paged span."""
    rng = np.random.RandomState(seed)
    n_pages = B * slots + 1
    q = jnp.asarray(rng.randn(B, C, H, D)).astype(dtype)
    kp = jnp.asarray(rng.randn(n_pages, P, Hkv, D)).astype(dtype)
    vp = jnp.asarray(rng.randn(n_pages, P, Hkv, D)).astype(dtype)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(B, slots)
        .astype(np.int32))
    if start is None:
        start = rng.randint(0, max(P * slots - C, 0) + 1, B)
    if n_tok is None:
        n_tok = rng.randint(0, C + 1, B)
    pool = jnp.stack([kp, vp], axis=1)[:, :, None]   # one layer
    return (q, pool, bt, jnp.asarray(start, jnp.int32),
            jnp.asarray(n_tok, jnp.int32))


def _assert_window_parity(case, dtype=jnp.float32, block_q=None,
                          msg=""):
    q, pool, bt, start, n_tok = case
    out = pa.paged_prefill_attention(q, pool, 0, bt, start, n_tok,
                                     block_q=block_q, interpret=True)
    ref_out = pa.paged_prefill_attention_ref(q, pool, 0, bt, start, n_tok)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=tol, rtol=tol, err_msg=msg)
    # padded rows (j >= n_tok) are exactly zero, both impls
    mask = np.arange(q.shape[1])[None] >= np.asarray(n_tok)[:, None]
    assert np.all(np.asarray(out)[mask] == 0.0), msg
    return out


def test_prefill_window_kernel_midpage_starts():
    """Windows whose start sits mid-page (resumed chunked prefill):
    the causal frontier crosses a page interior, not a boundary."""
    for seed, start in ((10, [1, 5, 3]), (11, [7, 2, 6])):
        case = _window_case(seed, B=3, C=8, H=4, Hkv=2, D=16, P=8,
                            slots=3, start=start, n_tok=[8, 8, 5])
        _assert_window_parity(case, msg=f"seed={seed} start={start}")


def test_prefill_window_kernel_full_final_page():
    """Windows that END exactly on a page boundary — the final page
    completely full, no partial-page mask on the last kv block."""
    case = _window_case(20, B=2, C=8, H=4, Hkv=2, D=16, P=4, slots=4,
                        start=[0, 8], n_tok=[8, 8])   # ends at 8 and 16
    _assert_window_parity(case, msg="full final page")


def test_prefill_window_kernel_padded_and_inactive_rows():
    """Right-padded short chunks and fully-inactive (n_tok=0) slots:
    padded rows exact zero, live rows still match the oracle."""
    case = _window_case(30, B=4, C=8, H=4, Hkv=2, D=16, P=8, slots=2,
                        start=[0, 3, 5, 0], n_tok=[8, 4, 1, 0])
    _assert_window_parity(case, msg="padded rows")


def test_prefill_window_kernel_verify_shape():
    """The speculative-verify window: (B, spec_k+1) tiny windows at
    deep, unaligned positions — the shape make_verify hands the op."""
    for spec_k in (1, 3):
        case = _window_case(40 + spec_k, B=3, C=spec_k + 1, H=4, Hkv=1,
                            D=16, P=8, slots=4,
                            start=[13, 26, 7],
                            n_tok=[spec_k + 1] * 3)
        _assert_window_parity(case, msg=f"spec_k={spec_k}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_window_kernel_dtypes(dtype):
    case = _window_case(50, B=2, C=16, H=8, Hkv=2, D=16, P=4, slots=8,
                        dtype=dtype)
    _assert_window_parity(case, dtype=dtype, msg=str(dtype))


def test_prefill_window_kernel_block_not_dividing_window():
    """block_q that doesn't divide the window (C=7 with block 8, C=13
    with block 8): the padded q rows must not leak into the output."""
    for C, bq in ((7, 8), (13, 8), (5, 16)):
        case = _window_case(60 + C, B=2, C=C, H=4, Hkv=2, D=16, P=8,
                            slots=4)
        _assert_window_parity(case, block_q=bq, msg=f"C={C} bq={bq}")


def test_prefill_window_kernel_gqa_mqa_groups():
    for H, Hkv in ((4, 1), (6, 2), (4, 4)):
        case = _window_case(70 + H * 10 + Hkv, B=2, C=8, H=H, Hkv=Hkv,
                            D=16, P=8, slots=3)
        _assert_window_parity(case, msg=f"H={H} Hkv={Hkv}")


def test_prefill_window_choose_block_dispatch():
    """The §4.5.4 size/dtype ladder: sublane-aligned, never wider than
    the padded window, monotone in window length."""
    for w in (1, 3, 8, 16, 64, 256, 1024):
        blk = pa.choose_block(w, jnp.float32)
        assert blk % 8 == 0
        assert blk <= -(-w // 8) * 8
    assert pa.choose_block(4, jnp.float32) == 8      # verify window
    assert pa.choose_block(64, jnp.float32) == 16
    assert pa.choose_block(1024, jnp.float32) == 64
    assert pa.choose_block(3, jnp.bfloat16) == 16    # bf16 sublane 16
    # ladder choices all agree with the ref on a real case
    for bq in (8, 16, 32):
        case = _window_case(80, B=2, C=32, H=4, Hkv=2, D=16, P=8,
                            slots=4)
        _assert_window_parity(case, block_q=bq, msg=f"ladder bq={bq}")


def test_prefill_window_unknown_impl_raises():
    case = _window_case(90, B=1, C=4, H=4, Hkv=2, D=16, P=8, slots=2)
    q, pool, bt, start, n_tok = case
    with pytest.raises(ValueError, match="paged_prefill_attention"):
        ops.paged_prefill_attention(q, pool, 0, bt, start, n_tok,
                                    impl="nope")
    with pytest.raises(ValueError, match="paged_attention"):
        ops.paged_attention(q[:, 0], pool, 0, bt,
                            jnp.asarray([1], jnp.int32), impl="nope")
    assert "kernel" in ops.PAGED_PREFILL_IMPLS
    assert "ref" in ops.PAGED_PREFILL_IMPLS


def test_prefill_window_ops_kernel_route():
    """ops.paged_prefill_attention(impl='kernel') actually reaches the
    grid kernel and matches the ref route at 1e-5."""
    case = _window_case(91, B=3, C=8, H=4, Hkv=2, D=16, P=8, slots=3)
    q, pool, bt, start, n_tok = case
    k_out = ops.paged_prefill_attention(q, pool, 0, bt, start, n_tok,
                                        impl="kernel")
    r_out = ops.paged_prefill_attention(q, pool, 0, bt, start, n_tok,
                                        impl="ref")
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(r_out),
                               atol=1e-5, rtol=1e-5)
