"""QAT primitives: STE quantizers, per-layer activation selection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyp_compat import given, settings, st

from repro.core import quant as Q


def test_sign_ste_values_and_grad():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
    y = Q.sign_ste(x)
    assert set(np.asarray(y).tolist()) <= {-1.0, 1.0}
    g = jax.grad(lambda x: jnp.sum(Q.sign_ste(x)))(x)
    # clipped-identity STE: grad 1 inside [-1, 1], 0 outside
    np.testing.assert_allclose(np.asarray(g), [0, 1, 1, 1, 0])


def test_pact_clips_and_quantizes():
    x = jnp.linspace(-1, 8, 100)
    y = Q.pact(x, jnp.asarray(5.0), bits=2)
    lv = np.asarray(Q.pact_levels(5.0, 2))
    assert np.all(np.isin(np.round(np.asarray(y), 5), np.round(lv, 5)))
    assert float(y.max()) == pytest.approx(5.0)
    assert float(y.min()) == 0.0


def test_pact_alpha_gradient():
    # d/dalpha is 1 where x >= alpha (PACT paper), ~0 well below clip
    f = lambda a, x: jnp.sum(Q.pact(x, a, bits=4))
    g_hi = jax.grad(f)(jnp.asarray(2.0), jnp.asarray([5.0, 7.0]))
    assert float(g_hi) == pytest.approx(2.0, rel=0.2)


def test_signed_uniform_bits1_is_bipolar():
    x = jnp.asarray([-3.0, 0.2, 4.0])
    y = Q.signed_uniform(x, 1.5, bits=1)
    np.testing.assert_allclose(np.asarray(y), [-1.5, 1.5, 1.5])


def test_selection_rule():
    # the paper's rule: non-negative -> PACT; both signs -> sign/signed
    assert Q.select_activation(True, 4).kind == "pact"
    assert Q.select_activation(True, 1).kind == "binary"
    assert Q.select_activation(False, 1).kind == "sign"
    assert Q.select_activation(False, 3).kind == "signed"


@settings(max_examples=30, deadline=None)
@given(kind=st.sampled_from(["sign", "binary", "pact", "signed"]),
       bits=st.integers(1, 4), alpha=st.floats(0.5, 4.0))
def test_encode_decode_roundtrip(kind, bits, alpha):
    """Property: quantize -> encode -> decode is the identity on the
    quantized value set (the contract truth-table extraction relies on)."""
    if kind in ("sign", "binary") and bits != 1:
        bits = 1
    spec = Q.ActQuantSpec(kind, bits)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 2, 64), jnp.float32)
    q = Q.apply_act_quant(spec, x, jnp.asarray(alpha, jnp.float32))
    codes = Q.encode_levels(spec, q, alpha)
    assert int(codes.min()) >= 0 and int(codes.max()) < spec.n_levels
    decoded = Q.decode_levels(spec, codes, alpha)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(q),
                               rtol=1e-5, atol=1e-5)


def _host_twin_inputs(spec, alpha):
    """Random normals, every level boundary k*step/2 and its +-1-ulp
    neighbours, +-alpha, values far out of range, and NaN (float32)."""
    a = np.float32(alpha)
    if spec.kind in ("sign", "binary") or spec.bits == 1:
        n = 1
    elif spec.kind == "pact":
        n = (1 << spec.bits) - 1
    else:
        n = (1 << (spec.bits - 1)) - 1
    step = a / np.float32(n)
    k = np.arange(-2 * n - 2, 2 * n + 3, dtype=np.float32)
    edges = np.concatenate([k * step / np.float32(2), [a, -a, a / 2, 0.5]])
    edges = edges.astype(np.float32)
    far = np.array([4 * a, -4 * a, 1e6, -1e6, 3e38, -3e38, np.inf, -np.inf,
                    np.nan, -0.0], np.float32)
    rng = np.random.default_rng(spec.bits)
    return np.concatenate([
        rng.normal(0, 2 * alpha, 4096).astype(np.float32), edges,
        np.nextafter(edges, np.float32(np.inf)),
        np.nextafter(edges, np.float32(-np.inf)), far])


@pytest.mark.parametrize("alpha", [0.75, 1.0, 1.001, 1.2345])
@pytest.mark.parametrize("kind", ["sign", "binary", "pact", "signed"])
@pytest.mark.parametrize("bits", [1, 2, 3, 4])
def test_encode_inputs_host_matches_jax(kind, bits, alpha):
    """The numpy input quantizer gives the jax path's codes bit for bit."""
    spec = Q.ActQuantSpec(kind, bits)
    x = _host_twin_inputs(spec, alpha)
    q = Q.apply_act_quant(spec, jnp.asarray(x), jnp.asarray(alpha, jnp.float32))
    want = np.asarray(Q.encode_levels(spec, q, alpha))
    got = Q.encode_inputs_host(spec, x, alpha)
    assert got.dtype == np.int32 and got.shape == x.shape
    np.testing.assert_array_equal(got, want)


def test_encode_inputs_host_refuses_unquantized():
    with pytest.raises(ValueError):
        Q.encode_inputs_host(Q.ActQuantSpec("none", 1), np.zeros(4), 1.0)


def test_dorefa_weights():
    w = jnp.asarray(np.random.default_rng(1).normal(size=(8, 8)),
                    jnp.float32)
    w1 = Q.dorefa_weight(w, 1)
    scale = float(jnp.mean(jnp.abs(w)))
    assert np.allclose(np.abs(np.asarray(w1)), scale, rtol=1e-5)
    w2 = Q.dorefa_weight(w, 2)
    assert len(np.unique(np.round(np.asarray(w2), 5))) <= 4


def test_fold_bn_equivalence(rng):
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    gamma = jnp.asarray(rng.uniform(0.5, 2, 4), jnp.float32)
    beta = jnp.asarray(rng.normal(size=4), jnp.float32)
    mean = jnp.asarray(rng.normal(size=4), jnp.float32)
    var = jnp.asarray(rng.uniform(0.5, 2, 4), jnp.float32)
    x = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    y_bn = (x @ w.T + b - mean) / jnp.sqrt(var + 1e-5) * gamma + beta
    w2, b2 = Q.fold_bn(w, b, gamma, beta, mean, var)
    np.testing.assert_allclose(np.asarray(x @ w2.T + b2), np.asarray(y_bn),
                               rtol=1e-4, atol=1e-4)
