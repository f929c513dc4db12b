"""Quantization-aware training primitives (NullaNet Tiny §QAT).

The paper's key QAT idea: *per-layer activation function selection* —
use a signed quantizer (``sign`` / bipolar / symmetric multi-bit) when a
layer's inputs take both signs, and PACT (parameterized clipping
activation, Choi et al. 2018) when inputs are non-negative.

All quantizers use straight-through estimators (STE) implemented with
``jax.custom_vjp`` or the stop-gradient trick so they are differentiable
under ``jax.grad`` and safe inside ``pjit``/``shard_map``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Straight-through rounding / sign
# ---------------------------------------------------------------------------

def ste_round(x: Array) -> Array:
    """round(x) in the forward pass, identity gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def ste_floor(x: Array) -> Array:
    return x + jax.lax.stop_gradient(jnp.floor(x) - x)


@jax.custom_vjp
def sign_ste(x: Array) -> Array:
    """Bipolar sign: {-1, +1}; clipped-identity STE (|x| <= 1 passes grad).

    sign(0) is mapped to +1 so every input has a defined binary code.
    """
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _sign_fwd(x):
    return sign_ste(x), x


def _sign_bwd(x, g):
    # Hard-tanh STE (Hubara et al., Binarized Neural Networks).
    return (g * (jnp.abs(x) <= 1.0).astype(g.dtype),)


sign_ste.defvjp(_sign_fwd, _sign_bwd)


@jax.custom_vjp
def binary_ste(x: Array) -> Array:
    """Binary {0, 1} step with clipped-identity STE on [0, 1]."""
    return (x >= 0.5).astype(x.dtype)


def _bin_fwd(x):
    return binary_ste(x), x


def _bin_bwd(x, g):
    return (g * ((x >= 0.0) & (x <= 1.0)).astype(g.dtype),)


binary_ste.defvjp(_bin_fwd, _bin_bwd)


# ---------------------------------------------------------------------------
# PACT — parameterized clipping activation (for non-negative layers)
# ---------------------------------------------------------------------------

def pact(x: Array, alpha: Array, bits: int) -> Array:
    """PACT quantizer: y = clip(x, 0, alpha) quantized to ``bits`` levels.

    ``alpha`` is a learnable scalar (or per-channel vector). Gradient w.r.t.
    alpha flows through the clip boundary exactly as in the PACT paper:
    d y / d alpha = 1 where x >= alpha, else 0 (via the clip), plus the STE
    treats rounding as identity.
    """
    alpha = jnp.asarray(alpha, x.dtype)
    levels = (1 << bits) - 1
    y = jnp.clip(x, 0.0, alpha)  # grads: x in (0, alpha) -> x; x >= alpha -> alpha
    scale = levels / jnp.maximum(alpha, 1e-8)
    q = ste_round(y * scale) / scale
    return q


def pact_levels(alpha: float, bits: int) -> jnp.ndarray:
    """The discrete value set PACT can emit (used by truth-table enumeration)."""
    levels = (1 << bits) - 1
    return jnp.arange(levels + 1, dtype=jnp.float32) * (alpha / levels)


# ---------------------------------------------------------------------------
# Symmetric signed multi-bit quantizer (bipolar generalisation)
# ---------------------------------------------------------------------------

def signed_uniform(x: Array, alpha: Array, bits: int) -> Array:
    """Symmetric signed quantizer on [-alpha, alpha] with 2^bits - 1 levels.

    bits=1 degenerates to bipolar sign * alpha. Used for layers whose
    inputs take both signs (the paper's ``sign`` branch, generalised).
    """
    alpha = jnp.asarray(alpha, x.dtype)
    if bits == 1:
        return sign_ste(x) * alpha
    half = (1 << (bits - 1)) - 1  # e.g. bits=2 -> {-1,0,1}
    y = jnp.clip(x, -alpha, alpha)
    scale = half / jnp.maximum(alpha, 1e-8)
    return ste_round(y * scale) / scale


def signed_levels(alpha: float, bits: int) -> jnp.ndarray:
    if bits == 1:  # bipolar
        return jnp.array([-alpha, alpha], dtype=jnp.float32)
    half = (1 << (bits - 1)) - 1
    return jnp.arange(-half, half + 1, dtype=jnp.float32) * (alpha / half)


# ---------------------------------------------------------------------------
# DoReFa weight quantizer
# ---------------------------------------------------------------------------

def dorefa_weight(w: Array, bits: int) -> Array:
    """DoReFa-Net weight quantization (Zhou et al. 2016).

    bits=1: sign(w) * E[|w|] (XNOR-Net-style scaling).
    bits>1: tanh-normalised uniform quantization to [-1, 1].
    """
    if bits >= 32:
        return w
    if bits == 1:
        scale = jnp.mean(jnp.abs(w))
        return sign_ste(w) * jax.lax.stop_gradient(scale)
    t = jnp.tanh(w)
    t = t / jnp.maximum(jnp.max(jnp.abs(t)), 1e-8)  # [-1, 1]
    u = (t + 1.0) * 0.5
    levels = (1 << bits) - 1
    q = ste_round(u * levels) / levels
    return 2.0 * q - 1.0


# ---------------------------------------------------------------------------
# Activation-function selection (the paper's per-layer rule)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ActQuantSpec:
    """Per-layer activation quantizer choice.

    kind: 'sign' (bipolar), 'binary' ({0,1}), 'pact' (non-negative
          multi-bit), 'signed' (symmetric multi-bit), 'none'.
    bits: output bit-width (1 for sign/binary).
    """

    kind: str = "sign"
    bits: int = 1

    @property
    def n_levels(self) -> int:
        if self.kind == "none":
            raise ValueError("unquantized activation has no level set")
        if self.kind in ("sign", "binary"):
            return 2
        if self.kind == "pact":
            return 1 << self.bits
        if self.kind == "signed":
            if self.bits == 1:  # degenerates to bipolar sign
                return 2
            return 2 * ((1 << (self.bits - 1)) - 1) + 1
        raise ValueError(self.kind)

    @property
    def code_bits(self) -> int:
        """Bits needed to index the level set (for truth-table packing)."""
        n = self.n_levels
        return max(1, (n - 1).bit_length())

    def levels(self, alpha: float) -> jnp.ndarray:
        if self.kind == "sign":
            return jnp.array([-alpha, alpha], dtype=jnp.float32)
        if self.kind == "binary":
            return jnp.array([0.0, alpha], dtype=jnp.float32)
        if self.kind == "pact":
            return pact_levels(alpha, self.bits)
        if self.kind == "signed":
            return signed_levels(alpha, self.bits)
        raise ValueError(self.kind)


def select_activation(inputs_nonnegative: bool, bits: int = 1) -> ActQuantSpec:
    """The paper's per-layer selection rule: PACT for non-negative ranges,
    sign/signed for ranges spanning both signs."""
    if inputs_nonnegative:
        return ActQuantSpec("pact", max(bits, 1)) if bits > 1 else ActQuantSpec("binary", 1)
    return ActQuantSpec("signed", bits) if bits > 1 else ActQuantSpec("sign", 1)


def apply_act_quant(spec: ActQuantSpec, x: Array, alpha: Array) -> Array:
    if spec.kind == "none":
        return x
    if spec.kind == "sign":
        return sign_ste(x) * jnp.asarray(alpha, x.dtype)
    if spec.kind == "binary":
        return binary_ste(x) * jnp.asarray(alpha, x.dtype)
    if spec.kind == "pact":
        return pact(x, alpha, spec.bits)
    if spec.kind == "signed":
        return signed_uniform(x, alpha, spec.bits)
    raise ValueError(spec.kind)


def encode_levels(spec: ActQuantSpec, x: Array, alpha: Array) -> Array:
    """Map quantized activation values -> integer level codes [0, n_levels).

    Used when feeding a logic (truth-table) layer: logic layers consume
    codes, not real values.
    """
    alpha = jnp.asarray(alpha, jnp.float32)
    xf = x.astype(jnp.float32)
    if spec.kind == "sign":
        return (xf > 0).astype(jnp.int32)
    if spec.kind == "binary":
        return (xf > alpha * 0.5).astype(jnp.int32)
    if spec.kind == "pact":
        levels = (1 << spec.bits) - 1
        return jnp.clip(jnp.round(xf * levels / alpha), 0, levels).astype(jnp.int32)
    if spec.kind == "signed":
        if spec.bits == 1:  # bipolar
            return (xf > 0).astype(jnp.int32)
        half = (1 << (spec.bits - 1)) - 1
        return jnp.clip(jnp.round(xf * half / alpha) + half, 0, 2 * half).astype(jnp.int32)
    raise ValueError(spec.kind)


def encode_inputs_host(spec: ActQuantSpec, x, alpha) -> np.ndarray:
    """Numpy twin of ``encode_levels(spec, apply_act_quant(spec, x, alpha),
    alpha)``: real inputs -> int32 level codes, on the host.

    Every intermediate is float32 and in the jax path's order (clip,
    ``scale = n / max(alpha, 1e-8)``, round half to even, divide, then
    ``round(q * n / alpha)`` shifted and clipped to the codes), so the
    codes are bit-identical to it. Two places follow XLA where numpy
    differs: XLA reads subnormals as zero, so the sign test is
    ``x > -tiny`` (a negative subnormal is ``>= 0`` there), and XLA
    converts a NaN to code 0, so the last clip drops NaNs
    (``fmax``/``fmin``).
    """
    x = np.asarray(x, np.float32)
    a = np.float32(alpha)
    if spec.kind == "sign" or (spec.kind == "signed" and spec.bits == 1):
        nonneg = x > -np.finfo(np.float32).tiny
        q = np.where(nonneg, np.float32(1), np.float32(-1)) * a
        return (q > 0).astype(np.int32)
    if spec.kind == "binary":
        q = (x >= np.float32(0.5)).astype(np.float32) * a
        return (q > a * np.float32(0.5)).astype(np.int32)
    if spec.kind == "pact":
        n = (1 << spec.bits) - 1
        lo, shift, top = np.float32(0), 0, n
    elif spec.kind == "signed":
        n = (1 << (spec.bits - 1)) - 1
        lo, shift, top = -a, n, 2 * n
    else:
        raise ValueError(spec.kind)
    nf = np.float32(n)
    scale = nf / np.maximum(a, np.float32(1e-8))
    q = np.rint(np.clip(x, lo, a) * scale) / scale
    codes = np.rint(q * nf / a) + np.float32(shift)
    return np.fmin(np.fmax(codes, np.float32(0)),
                   np.float32(top)).astype(np.int32)


def decode_levels(spec: ActQuantSpec, codes: Array, alpha: float) -> Array:
    """Integer level codes -> real activation values."""
    lv = spec.levels(alpha)
    return lv[codes]


# ---------------------------------------------------------------------------
# Folded batch-norm (inference view used during truth-table extraction)
# ---------------------------------------------------------------------------

def fold_bn(w: Array, b: Array, gamma: Array, beta: Array, mean: Array,
            var: Array, eps: float = 1e-5):
    """Fold BN(gamma,beta,mean,var) following y = xW^T + b into (w', b').

    Returns weights/bias such that BN(xW^T + b) == x w'^T + b'.
    w: (out, in). This is the 'BN disappears into the Boolean function'
    step of the paper.
    """
    inv = gamma / jnp.sqrt(var + eps)
    w2 = w * inv[:, None]
    b2 = (b - mean) * inv + beta
    return w2, b2
