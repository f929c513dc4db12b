"""Netlists synthesized ahead of time, keyed by what they implement.

Synthesis of a large network (LogicNets' JSC-M: about 60,000 LUTs) takes
longer than a server may spend starting up, so it runs offline, as the
FPGA flow runs its place and route, and the served path loads the
mapped netlist:

    save(mapped, net, effort, k)        # offline, once per network
    mapped = load(net, effort, k)       # at start-up

A netlist's file name is the ``fingerprint`` of the ``LogicNetwork`` it
implements, the synthesis settings and the file format. A network whose
tables changed by one entry asks for another file, so a stale netlist is
never served: ``load`` raises ``NetlistNotFound`` (it never falls back
to synthesis). Before it returns, ``load`` also checks the netlist
against the tables on seeded random input codes.
"""
from __future__ import annotations

import hashlib
import os
import time
from typing import Optional

import numpy as np

from .lutmap import MappedLUT, MappedNetwork

FORMAT_VERSION = 1
CHECK_ROWS = 4096       # random input-code rows of the load-time check
CHECK_SEED = 0

# <repo>/netlists, the root as repro.launch.cache finds it
DEFAULT_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "netlists")


class NetlistNotFound(FileNotFoundError):
    """The store holds no netlist for this network and these settings."""

    def __init__(self, fingerprint: str, path: str):
        self.fingerprint = fingerprint
        self.path = path
        super().__init__(
            f"no prebuilt netlist {fingerprint} at {path}; build it with "
            f"`python3 bench/make_netlist.py --config <configuration "
            f"file>` (the network's tables, effort or k differ from "
            f"every netlist in the store)")


class NetlistMismatch(ValueError):
    """A stored netlist is not the one its name promises."""


def _spec_key(spec) -> str:
    return f"{spec.kind}:{int(spec.bits)}"


def _f32(x) -> bytes:
    return np.asarray(x, np.float32).tobytes()


def _i64(a) -> bytes:
    return np.ascontiguousarray(np.asarray(a), np.int64).tobytes()


def fingerprint(net, effort: int, k: int) -> str:
    """sha256 (hex) over what the netlist must implement: every layer's
    fan-in indices, tables, quantizer specs and alphas, the network's
    input spec, alpha and sizes, and the synthesis settings."""
    h = hashlib.sha256()

    def put(tag: str, data: bytes = b"") -> None:
        h.update(f"{tag}:{len(data)};".encode())
        h.update(data)

    put(f"format={FORMAT_VERSION};effort={int(effort)};k={int(k)}")
    put(f"in_spec={_spec_key(net.in_spec)};n_inputs={int(net.n_inputs)};"
        f"n_outputs={int(net.n_outputs)}", _f32(net.in_alpha))
    for i, lt in enumerate(net.layers):
        idx, tab = np.asarray(lt.fanin_idx), np.asarray(lt.tables)
        put(f"layer{i};in={_spec_key(lt.in_spec)};"
            f"out={_spec_key(lt.out_spec)};idx={idx.shape};tab={tab.shape}",
            _f32(lt.in_alpha) + _f32(lt.out_alpha))
        put("fanin_idx", _i64(idx))
        put("tables", _i64(tab))
    return h.hexdigest()


def path_of(fp: str, root: Optional[str] = None) -> str:
    """Where the store keeps the netlist of fingerprint ``fp``."""
    return os.path.join(DEFAULT_ROOT if root is None else root, fp + ".npz")


def save(mapped: MappedNetwork, net, effort: int, k: int,
         root: Optional[str] = None) -> str:
    """Write ``mapped`` (the netlist of ``net`` at ``effort``/``k``) to
    ``<root>/<fingerprint>.npz``; returns the path."""
    if mapped.k != k or not 1 <= k <= 6:
        raise ValueError(f"netlist k={mapped.k}, asked k={k}: the store "
                         f"keeps INIT words of up to 64 bits (k <= 6)")
    fp = fingerprint(net, effort, k)
    n = mapped.n_luts
    leaves = np.full((n, k), -1, np.int64)
    n_leaves = np.zeros((n,), np.int8)
    init = np.zeros((n,), np.uint64)
    for i, lut in enumerate(mapped.luts):
        leaves[i, :len(lut.leaves)] = lut.leaves
        n_leaves[i] = len(lut.leaves)
        init[i] = lut.tt
    path = path_of(fp, root)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez_compressed(
        tmp, fingerprint=np.array(fp), n_pis=np.int64(mapped.n_pis),
        k=np.int64(k), roots=np.array([l.root for l in mapped.luts],
                                      np.int64),
        leaves=leaves, n_leaves=n_leaves, init=init,
        outputs=np.asarray(mapped.outputs, np.int64))
    os.replace(tmp, path)
    return path


def read(path: str):
    """``(fingerprint, MappedNetwork)`` of a netlist file, unchecked."""
    with np.load(path) as z:
        leaves, n_leaves = z["leaves"], z["n_leaves"]
        luts = [MappedLUT(int(r), tuple(int(x) for x in lv[:m]), int(tt))
                for r, lv, m, tt in zip(z["roots"], leaves, n_leaves,
                                        z["init"])]
        return str(z["fingerprint"]), MappedNetwork(
            int(z["n_pis"]), int(z["k"]), luts,
            [int(o) for o in z["outputs"]])


def check_against_tables(mapped: MappedNetwork, net) -> None:
    """The numpy fold of ``mapped`` against ``net.apply_codes`` on
    ``CHECK_ROWS`` seeded random input-code rows; raises
    ``NetlistMismatch`` on the first row that disagrees."""
    import jax.numpy as jnp

    from .executor import BitplaneNetwork
    rows = CHECK_ROWS
    rng = np.random.default_rng(CHECK_SEED)
    codes = rng.integers(0, net.in_spec.n_levels, (rows, net.n_inputs),
                         dtype=np.int32)
    want = np.asarray(net.apply_codes(jnp.asarray(codes))).astype(np.int64)
    got = BitplaneNetwork(net, mapped, engine="numpy").apply_codes(codes)
    bad = np.nonzero((got != want).any(axis=1))[0]
    if bad.size:
        r = int(bad[0])
        raise NetlistMismatch(
            f"netlist disagrees with the tables on {bad.size} of {rows} "
            f"rows; first: input codes {codes[r].tolist()} give "
            f"{got[r].tolist()}, the tables {want[r].tolist()}")


def load(net, effort: int, k: int, root: Optional[str] = None,
         tracer=None) -> MappedNetwork:
    """The stored netlist of ``net`` at ``effort``/``k``, checked
    against the tables. Raises ``NetlistNotFound`` when the store has
    none (never synthesizes) and ``NetlistMismatch`` when the file is
    not what its name says or disagrees with the tables. The load is a
    ``netlist_load`` span (``cat="setup"``) on ``tracer``."""
    if tracer is None:
        from repro.obs.trace import NULL_TRACER
        tracer = NULL_TRACER
    fp = fingerprint(net, effort, k)
    path = path_of(fp, root)
    if not os.path.exists(path):
        raise NetlistNotFound(fp, path)
    args: dict = {}
    with tracer.span("netlist_load", cat="setup", args=args):
        stored, mapped = read(path)
        if stored != fp:
            raise NetlistMismatch(
                f"{path}: the file holds the netlist of {stored}, not of "
                f"{fp}; rebuild it with bench/make_netlist.py")
        t = time.perf_counter()
        check_against_tables(mapped, net)
        args.update(luts=mapped.n_luts, bytes=os.path.getsize(path),
                    check_s=time.perf_counter() - t)
    return mapped
