"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import pairwise_l2 as pk
from repro.kernels import bucket_assign as ak
from repro.kernels import flash_attention as fk


RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,n,d", [(128, 128, 128), (256, 128, 128),
                                   (200, 150, 96), (64, 300, 33),
                                   (1, 1, 8), (130, 2, 130)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_pairwise_l2_matches_oracle(m, n, d, dtype):
    a = RNG.normal(size=(m, d)).astype(dtype)
    b = RNG.normal(size=(n, d)).astype(dtype)
    eps = 1.5
    d2r, mr = ops.pairwise_l2_threshold(a, b, eps, use_pallas=False)
    d2p, mp = ops.pairwise_l2_threshold(a, b, eps, use_pallas=True)
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r),
                               rtol=1e-4, atol=1e-3)
    # threshold disagreement only possible within float tolerance of eps²
    dis = np.asarray(mr) != np.asarray(mp)
    if dis.any():
        assert np.abs(np.asarray(d2r)[dis] - eps * eps).max() < 1e-2


@pytest.mark.parametrize("m,b,d", [(128, 128, 64), (100, 37, 96),
                                   (256, 130, 128), (5, 3, 16)])
def test_bucket_assign_matches_oracle(m, b, d):
    x = RNG.normal(size=(m, d)).astype(np.float32)
    c = RNG.normal(size=(b, d)).astype(np.float32)
    dr, ir = ops.bucket_assign(x, c, use_pallas=False)
    dp, ip = ops.bucket_assign(x, c, use_pallas=True)
    assert np.array_equal(np.asarray(ir), np.asarray(ip))
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("b,h,sq,skv,hd", [
    (1, 2, 128, 128, 64), (2, 4, 256, 256, 64),
    (1, 1, 128, 384, 32), (2, 2, 384, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(b, h, sq, skv, hd, causal):
    if causal and sq != skv:
        pytest.skip("kernel causal convention requires sq == skv "
                    "(ops falls back to ref for offset-causal)")
    q = RNG.normal(size=(b, h, sq, hd)).astype(np.float32)
    k = RNG.normal(size=(b, h, skv, hd)).astype(np.float32)
    v = RNG.normal(size=(b, h, skv, hd)).astype(np.float32)
    o_ref = ops.flash_attention(q, k, v, causal=causal, use_pallas=False)
    o_pal = ops.flash_attention(q, k, v, causal=causal, use_pallas=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)


def test_pairwise_raw_kernel_blockspec_alignment():
    """The raw kernel demands exact block divisibility — guard the contract.
    (Dims smaller than a block auto-shrink; non-divisible larger dims fail.)"""
    a = jnp.zeros((130, 128), jnp.float32)
    b = jnp.zeros((128, 128), jnp.float32)
    with pytest.raises(ValueError):
        pk.pairwise_l2_threshold(a, b, 1.0, interpret=True)


def test_flash_attention_kernel_raw_alignment():
    q = jnp.zeros((2, 130, 64), jnp.float32)
    with pytest.raises(ValueError):
        fk.flash_attention(q, q, q, interpret=True)


def test_bucket_assign_padding_never_wins():
    """Padded far-away centers must not be selected."""
    x = RNG.normal(size=(10, 8)).astype(np.float32)
    c = RNG.normal(size=(3, 8)).astype(np.float32)
    _, idx = ops.bucket_assign(x, c, use_pallas=True)
    assert int(np.asarray(idx).max()) < 3


def test_extract_pairs_upper_triangle():
    d2 = np.asarray([[0.0, 1.0], [1.0, 0.0]])
    mask = d2 <= 1.5
    ids = np.asarray([7, 9])
    pairs, dists = ops.extract_pairs(d2, mask, ids, ids, upper_triangle=True)
    assert pairs.tolist() == [[7, 9]]
    np.testing.assert_allclose(dists, [1.0])


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, expect):
    """Kernels compile on TPU, interpret on CPU, and refuse any other
    backend instead of silently running the interpreter there."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is expect
