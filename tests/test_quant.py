"""BitNet quantization + sub-byte packing (incl. hypothesis properties)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.quant import bitnet, packing

SETTINGS = dict(max_examples=25, deadline=None)


@settings(**SETTINGS)
@given(st.integers(0, 2**32 - 1), st.sampled_from([4, 8, 64, 256]))
def test_pack2_roundtrip(seed, k):
    rng = np.random.default_rng(seed)
    v = rng.integers(-1, 2, size=(3, k)).astype(np.int8)
    assert (np.asarray(packing.unpack_2bit(packing.pack_2bit(jnp.array(v))))
            == v).all()


@settings(**SETTINGS)
@given(st.integers(0, 2**32 - 1), st.sampled_from([2, 8, 64]))
def test_pack4_roundtrip(seed, k):
    rng = np.random.default_rng(seed)
    v = rng.integers(-8, 8, size=(2, k)).astype(np.int8)
    assert (np.asarray(packing.unpack_4bit(packing.pack_4bit(jnp.array(v))))
            == v).all()


@settings(**SETTINGS)
@given(st.integers(0, 2**32 - 1))
def test_pack_kmajor_roundtrip(seed):
    rng = np.random.default_rng(seed)
    v = rng.integers(-1, 2, size=(16, 8)).astype(np.int8)
    out = packing.unpack_2bit_kmajor(packing.pack_2bit_kmajor(jnp.array(v)))
    assert (np.asarray(out) == v).all()
    v4 = rng.integers(-8, 8, size=(16, 8)).astype(np.int8)
    out4 = packing.unpack_4bit_kmajor(packing.pack_4bit_kmajor(jnp.array(v4)))
    assert (np.asarray(out4) == v4).all()


def test_pack_requires_divisibility():
    with pytest.raises(ValueError):
        packing.pack_2bit(jnp.zeros((2, 7), jnp.int8))
    with pytest.raises(ValueError):
        packing.pack_2bit_kmajor(jnp.zeros((7, 2), jnp.int8))


@settings(**SETTINGS)
@given(st.integers(0, 2**32 - 1))
def test_ternary_values_and_scale(seed):
    rng = np.random.default_rng(seed)
    w = jnp.array(rng.standard_normal((16, 32)), jnp.float32)
    q, gamma = bitnet.quantize_weight_ternary(w)
    assert set(np.unique(np.asarray(q))) <= {-1, 0, 1}
    assert float(gamma) == pytest.approx(float(jnp.mean(jnp.abs(w))),
                                         abs=1e-4)


@settings(**SETTINGS)
@given(st.integers(0, 2**32 - 1))
def test_act_quant_bounds_and_error(seed):
    rng = np.random.default_rng(seed)
    x = jnp.array(rng.standard_normal((4, 64)) * 10, jnp.float32)
    q, s = bitnet.quantize_act_int8(x)
    assert int(jnp.max(q)) <= 127 and int(jnp.min(q)) >= -128
    err = jnp.abs(q.astype(jnp.float32) * s - x)
    assert float(err.max()) <= float(s.max()) * 0.51 + 1e-5


def test_ste_gradient_is_identity_shaped():
    w = jnp.ones((8, 8)) * 0.3
    g = jax.grad(lambda w: bitnet.fake_quant_weight(w).sum())(w)
    assert g.shape == w.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    ga = jax.grad(lambda x: bitnet.fake_quant_act(x).sum())(w)
    assert bool(jnp.all(jnp.isfinite(ga)))


def test_bit_linear_serve_matches_dequant(rng):
    x = jnp.array(rng.standard_normal((4, 32)), jnp.float32)
    w = jnp.array(rng.standard_normal((32, 16)), jnp.float32)
    qt = bitnet.pack_weight_ternary(w)
    out = bitnet.bit_linear_serve(x, qt, backend="reference")
    xq, xs = bitnet.quantize_act_int8(x)
    expect = (xq.astype(jnp.float32) * xs) @ qt.dequantize()
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_bit_linear_serve_pallas_matches_reference(rng):
    """The Pallas branch (interpret mode here, compiled on a TPU) repacks
    the N-packed weights K-major and agrees with the XLA branch."""
    x = jnp.array(rng.standard_normal((4, 32)), jnp.float32)
    w = jnp.array(rng.standard_normal((32, 16)), jnp.float32)
    qt = bitnet.pack_weight_ternary(w)
    np.testing.assert_allclose(
        np.asarray(bitnet.bit_linear_serve(x, qt, backend="pallas")),
        np.asarray(bitnet.bit_linear_serve(x, qt, backend="reference")),
        rtol=1e-5, atol=1e-5)


def test_packed_dequantize_roundtrip(rng):
    w = jnp.array(rng.standard_normal((8, 16)), jnp.float32)
    qt = bitnet.pack_weight_ternary(w)
    q, gamma = bitnet.quantize_weight_ternary(w)
    np.testing.assert_allclose(
        np.asarray(qt.dequantize()),
        np.asarray(q.astype(jnp.float32) * gamma), rtol=1e-6,
    )


@pytest.mark.parametrize("shape", [(3, 64, 48), (2, 4, 32, 16)],
                         ids=["stacked", "moe"])
def test_ternary_weight_matches_per_layer_quantization(shape):
    """Codes and gamma of the served form are exactly what
    ``quantize_weight_ternary`` gives on each bf16 layer slice (the
    fake-quant step's own numbers), gamma widened to f32."""
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.standard_normal(shape) / 8, jnp.bfloat16)
    tw = bitnet.ternary_weight(w)
    assert tw.q.dtype == jnp.int8 and tw.q.shape == shape
    assert tw.gamma.dtype == jnp.float32 and tw.gamma.shape == shape[:1]
    assert set(np.unique(np.asarray(tw.q))) <= {-1, 0, 1}
    for layer in range(shape[0]):
        q, gamma = bitnet.quantize_weight_ternary(w[layer])
        np.testing.assert_array_equal(np.asarray(tw.q[layer]), np.asarray(q))
        assert np.asarray(tw.gamma[layer]) == np.float32(gamma)
    # a leaf that is not stacked gets one gamma over the whole of it
    whole = bitnet.ternary_weight(w[0], stacked=False)
    np.testing.assert_array_equal(np.asarray(whole.q), np.asarray(tw.q[0]))
    assert whole.gamma.shape == ()


@pytest.mark.parametrize("x_shape,w_shape,spec", [
    ((16, 256), (256, 192), None),
    ((2, 5, 256), (256, 192), None),
    ((2, 4, 3, 64), (4, 64, 32), "becd,edf->becf"),
], ids=["tokens", "batch-seq", "experts"])
def test_ternary_dot_is_exact_bitlinear(x_shape, w_shape, spec):
    """The integer BitLinear on a served weight agrees with the bf16
    fake-quant path within bf16 rounding (unit roundoff u = 2**-8: two
    rounded operands and a rounded output, 3u of the magnitude sum), and
    lies closer than it to the f32 BitLinear (the same int8 x ternary codes
    and scales, summed at ``highest``)."""
    from repro.models.common import dense

    u = 2.0 ** -8
    rng = np.random.default_rng(11)
    for _ in range(4):
        x = jnp.asarray(rng.standard_normal(x_shape) * 2, jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal(w_shape) / 16, jnp.bfloat16)
        tw = bitnet.ternary_weight(w, stacked=False)
        if spec is None:
            got, fq = dense(x, tw), dense(x, w, quantize=True)
            spec_ = "...d,df->...f"
        else:
            got = bitnet.ternary_dot(x, tw, spec)
            fq = jnp.einsum(spec, bitnet.fake_quant_act(x),
                            bitnet.fake_quant_weight(w))
            spec_ = spec
        assert got.dtype == x.dtype
        xq, xs = bitnet.quantize_act_int8(x)
        q, gamma = bitnet.quantize_weight_ternary(w)
        a = xq.astype(jnp.float32) * xs.astype(jnp.float32)
        b = q.astype(jnp.float32) * gamma.astype(jnp.float32)
        ref = np.asarray(jnp.einsum(spec_, a, b, precision="highest"))
        mag = np.asarray(jnp.einsum(spec_, jnp.abs(a), jnp.abs(b),
                                    precision="highest"))
        got = np.asarray(got, np.float32)
        fq = np.asarray(fq, np.float32)
        assert (np.abs(got - fq) <= 3 * u * mag).all()
        # only the output's rounding to bf16 (plus f32 scaling) remains
        assert (np.abs(got - ref) <= u * np.abs(ref) + 2.0 ** -12 * mag).all()
        assert np.linalg.norm(got - ref) <= np.linalg.norm(fq - ref)
