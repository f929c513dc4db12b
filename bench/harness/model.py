"""The model the benchmark serves: seeded weights and the served path.

The weights are the benchmark's own: drawn from the configuration's
fixed ``model_seed`` on the device in one jitted call, then handed as
host arrays both to the program (which compiles them to logic,
synthesizes the LUT netlist and serves it) and to the plain reference.
Batch-norm statistics come from a calibration pass of the same call, so
every neuron spans its quantizer's range as a trained one does.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

CAL_ROWS = 8192     # calibration rows for the batch-norm statistics
BN_EPS = 1e-5


def signed_half(bits: int) -> int:
    """Largest code magnitude of a signed ``bits``-bit quantizer."""
    if bits < 2:
        raise ValueError("the served models use signed quantizers of "
                         f"2 bits or more, not {bits}")
    return (1 << (bits - 1)) - 1


def make_weights(cfg: dict, device) -> Dict:
    """Weights of ``cfg`` from its ``model_seed``: one jitted call on
    ``device``, returned as numpy arrays.

    Returns ``{"layers": [{"w", "b", "alpha", "bn_gamma", "bn_beta",
    "bn_mean", "bn_var", "mask"}...]}``; ``mask`` keeps ``fanins[i]``
    inputs of every neuron."""
    import jax
    import jax.numpy as jnp

    feats = list(cfg["features"])
    fanins = list(cfg["fanins"])
    bits = list(cfg["act_bits"])
    n_in = int(cfg["n_inputs"])

    def quant(z, b, a):
        h = signed_half(b)
        return jnp.round(jnp.clip(z, -a, a) * (h / a)) / (h / a)

    def gen(key):
        key, kx = jax.random.split(key)
        h = quant(jax.random.normal(kx, (CAL_ROWS, n_in)),
                  int(cfg["in_bits"]), float(cfg["alpha"]))
        layers = []
        d_in = n_in
        for d_out, k, b in zip(feats, fanins, bits):
            key, *ks = jax.random.split(key, 7)
            score = jax.random.uniform(ks[0], (d_out, d_in))
            kth = jnp.sort(score, axis=1)[:, d_in - k][:, None]
            mask = score >= kth
            w = jax.random.normal(ks[1], (d_out, d_in))
            bias = 0.1 * jax.random.normal(ks[2], (d_out,))
            y = jnp.dot(h, jnp.where(mask, w, 0.0).T,
                        precision=jax.lax.Precision.HIGHEST) + bias
            mean, var = y.mean(0), y.var(0)
            gamma = jax.random.uniform(ks[3], (d_out,), minval=0.5,
                                       maxval=1.5)
            beta = 0.2 * jax.random.normal(ks[4], (d_out,))
            alpha = jax.random.uniform(ks[5], (), minval=0.75, maxval=1.25)
            z = (y - mean) * jax.lax.rsqrt(var + BN_EPS) * gamma + beta
            h = quant(z, b, jnp.abs(alpha) + 1e-3)
            layers.append(dict(w=w, b=bias, alpha=alpha, bn_gamma=gamma,
                               bn_beta=beta, bn_mean=mean, bn_var=var,
                               mask=mask))
            d_in = d_out
        return layers

    key = jax.device_put(jax.random.PRNGKey(int(cfg["model_seed"])), device)
    layers = jax.jit(gen)(key)
    return {"layers": [{k: np.asarray(v) for k, v in lp.items()}
                       for lp in layers]}


def mlp_config(cfg: dict):
    """The program's ``MLPConfig`` for a configuration file."""
    from repro.models.mlp import MLPConfig
    return MLPConfig(name=cfg["name"], n_inputs=int(cfg["n_inputs"]),
                     features=tuple(cfg["features"]),
                     fanins=tuple(cfg["fanins"]),
                     act_bits=tuple(cfg["act_bits"]),
                     in_bits=int(cfg["in_bits"]),
                     n_classes=int(cfg["n_classes"]),
                     alpha=float(cfg["alpha"]))


def to_logic(cfg: dict, weights: Dict):
    """The program's compile step: weights -> ``LogicNetwork``."""
    from repro.models.mlp import to_logic as program_to_logic
    params = {"layers": [{k: lp[k] for k in
                          ("w", "b", "alpha", "bn_gamma", "bn_beta")}
                         for lp in weights["layers"]]}
    bn_state = {"mean": [lp["bn_mean"] for lp in weights["layers"]],
                "var": [lp["bn_var"] for lp in weights["layers"]]}
    masks = [lp["mask"] for lp in weights["layers"]]
    return program_to_logic(mlp_config(cfg), params, masks, bn_state)


def build_executor(cfg: dict, net):
    """The scheduler's executor for ``cfg["serve"]``: one
    ``LogicEngine`` aggregator on the default chip, or
    ``build_logic_replicas`` over ``replicas`` chips. Returns
    ``(executor, [BitplaneNetwork per replica])``."""
    s = cfg["serve"]
    n_rep = int(s["replicas"])
    max_batch = int(s["sched"]["max_batch"])
    if n_rep == 1:
        from repro.serving.engine import LogicEngine
        eng = LogicEngine(net, int(cfg["n_classes"]),
                          max_batch=max_batch, backend=s["backend"],
                          engine=s["engine"])
        return eng.scheduler_executor(), [eng.bitnet]
    from repro.serve import build_logic_replicas
    rs = build_logic_replicas(net, int(cfg["n_classes"]), n_replicas=n_rep,
                              backend=s["backend"], engine=s["engine"],
                              max_batch=max_batch, policy=s["policy"])
    return rs, [r.fn.bitnet for r in rs.replicas]


def netlist_stats(bitnet) -> dict:
    """Sizes of the served netlist, counted from the mapped network."""
    m = bitnet.mapped
    return {"n_luts": int(m.n_luts), "depth": int(m.depth),
            "k": int(m.k), "n_pi_wires": int(m.n_pis),
            "n_out_wires": int(len(m.outputs))}


def build(cfg: dict, log) -> tuple:
    """Weights, compile, synthesis and warm-up of the served path.

    Returns ``(executor, bitnets, weights, stats)``."""
    import jax

    t = time.perf_counter()
    weights = make_weights(cfg, jax.devices()[0])
    t_w = time.perf_counter()
    net = to_logic(cfg, weights)
    t_l = time.perf_counter()
    executor, bitnets = build_executor(cfg, net)
    t_e = time.perf_counter()
    stats = netlist_stats(bitnets[0])
    log(f"weights {t_w - t:.2f}s, to_logic {t_l - t_w:.2f}s, synthesis "
        f"and warm-up {t_e - t_l:.2f}s: {stats['n_luts']} LUTs, depth "
        f"{stats['depth']}, {len(bitnets)} replica(s) on "
        f"{[str(b.device) for b in bitnets]}")
    return executor, bitnets, weights, stats
