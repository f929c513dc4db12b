"""Plain reference of the quantized sparse MLP (JSC-S, JSC-M).

The model as the configuration states it, row by row, in numpy: each
neuron takes its ``fanin`` masked inputs, computes ``w . v + b``,
batch-norm with the given statistics, and a signed uniform quantizer of
``act_bits`` bits over ``[-a, a]`` with ``a = |alpha| + 1e-3`` (the
parameter is float32). Inputs are quantized the same way with
``in_bits`` and ``alpha``. The label is the first index of the largest
output-layer score. It imports nothing of the program and uses nothing
the program made: no tables, no netlist.

``dtype`` sets the arithmetic: float64 is the reference; bfloat16 (from
``ml_dtypes``) is the control, the nearest precision below the float32
the configuration states.
"""
from __future__ import annotations

import numpy as np

BN_EPS = 1e-5


def _half(bits: int) -> int:
    if bits < 2:
        raise ValueError(f"signed quantizer of {bits} bit(s) not modelled")
    return (1 << (bits - 1)) - 1


def _codes(z: np.ndarray, bits: int, a, dtype) -> np.ndarray:
    """Signed uniform quantizer: real values -> codes 0..2*half."""
    h = _half(bits)
    s = (dtype(h) / dtype(a)).astype(dtype)
    q = np.round((np.clip(z, -dtype(a), dtype(a)) * s).astype(dtype)
                 .astype(np.float64))
    return (q + h).astype(np.int64)


def _values(codes: np.ndarray, bits: int, a, dtype) -> np.ndarray:
    """Codes -> the quantizer's level values."""
    h = _half(bits)
    step = (dtype(a) / dtype(h)).astype(dtype)
    return ((codes - h).astype(dtype) * step).astype(dtype)


def layer_alpha(alpha) -> np.float32:
    """The model's quantizer range: |alpha| + 1e-3 in float32."""
    return np.float32(np.abs(np.float32(alpha)) + np.float32(1e-3))


def output_codes(cfg: dict, weights: dict, x: np.ndarray,
                 dtype=np.float64) -> np.ndarray:
    """(n, 16) float32 features -> (n, n_out) output-layer codes."""
    dt = np.dtype(dtype).type
    a_prev = float(cfg["alpha"])
    b_prev = int(cfg["in_bits"])
    codes = _codes(np.asarray(x).astype(dt), b_prev, a_prev, dt)
    for lp, bits in zip(weights["layers"], cfg["act_bits"]):
        v = _values(codes, b_prev, a_prev, dt)
        mask = np.asarray(lp["mask"], bool)
        k = int(mask.sum(1).max())
        idx = np.stack([np.nonzero(r)[0] for r in mask])     # (N, K)
        if idx.shape[1] != k:
            raise ValueError("every neuron must keep the same fanin")
        w = np.take_along_axis(np.asarray(lp["w"]), idx, 1).astype(dt)
        y = np.zeros((v.shape[0], idx.shape[0]), dt)
        for j in range(k):              # sequential sum over the fanin
            y = (y + (v[:, idx[:, j]] * w[:, j]).astype(dt)).astype(dt)
        y = (y + np.asarray(lp["b"]).astype(dt)).astype(dt)
        inv = (dt(1.0) / np.sqrt((np.asarray(lp["bn_var"]).astype(dt)
                                  + dt(BN_EPS)).astype(dt))).astype(dt)
        z = ((y - np.asarray(lp["bn_mean"]).astype(dt)).astype(dt)
             * inv).astype(dt)
        z = (z * np.asarray(lp["bn_gamma"]).astype(dt)
             + np.asarray(lp["bn_beta"]).astype(dt)).astype(dt)
        a = float(layer_alpha(lp["alpha"]))
        codes = _codes(z, int(bits), a, dt)
        a_prev, b_prev = a, int(bits)
    return codes


def labels(cfg: dict, weights: dict, x: np.ndarray, dtype=np.float64,
           block: int = 1 << 16) -> np.ndarray:
    """(n,) int32 labels: first index of the largest output score over
    the first ``n_classes`` outputs, in blocks of ``block`` rows."""
    n_cls = int(cfg["n_classes"])
    out = np.empty(x.shape[0], np.int32)
    for s in range(0, x.shape[0], block):
        c = output_codes(cfg, weights, x[s: s + block], dtype)
        out[s: s + block] = np.argmax(c[:, :n_cls], axis=1)
    return out
