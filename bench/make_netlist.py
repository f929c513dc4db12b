#!/usr/bin/env python3
"""Synthesize a configuration's netlist ahead of time into netlists/.

    python3 bench/make_netlist.py --config bench/configs/jsc-m.json

Makes the configuration's weights exactly as a benchmark run does (on
JAX's first device, from ``model_seed``), compiles them to logic,
synthesizes the mapped netlist at ``LogicEngine``'s effort and k, and
saves it where ``LogicEngine(backend="bitplane-prebuilt")`` loads it
(``repro.synth.store``). Prints the fingerprint, LUTs, depth and
seconds as one JSON line.

The chip and the CPU may round the batch-norm statistics and the table
thresholds differently, and then the tables, and with them the
fingerprint, differ. Synthesis is plain host code, so it can run on
another machine than the one that serves:

    # where the netlist will be served: compile to logic, no synthesis
    python3 bench/make_netlist.py --config bench/configs/jsc-m.json \
        --net-out chiprun_out/jsc-m.logic.pkl
    # anywhere: synthesize that network
    python3 bench/make_netlist.py --config bench/configs/jsc-m.json \
        --net chiprun_out/jsc-m.logic.pkl
"""
import argparse
import json
import os
import pickle
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import model  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--net-out", help="write the compiled LogicNetwork "
                    "(pickle) here and stop before the synthesis")
    ap.add_argument("--net", help="synthesize the LogicNetwork that "
                    "--net-out wrote, instead of making the weights here")
    ap.add_argument("--root", help="the store's directory (default: "
                    "netlists/ at the root of the repository)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    import jax

    from repro.core.lutcost import LUT_K
    from repro.serving.engine import LogicEngine
    from repro.synth import network_to_aig, store, synthesize

    effort = LogicEngine.synth_effort
    t0 = time.perf_counter()
    if args.net:
        with open(args.net, "rb") as f:
            net = pickle.load(f)
        made_on = "read from " + args.net
    else:
        dev = jax.devices()[0]
        net = model.to_logic(cfg, model.make_weights(cfg, dev))
        made_on = dev.device_kind
    out = {"config": cfg["name"], "net": made_on,
           "fingerprint": store.fingerprint(net, effort, LUT_K),
           "effort": effort, "k": LUT_K,
           "logic_s": round(time.perf_counter() - t0, 3)}
    if args.net_out:
        with open(args.net_out, "wb") as f:
            pickle.dump(net, f)
        out["net_out"] = args.net_out
    else:
        t = time.perf_counter()
        mapped = synthesize(network_to_aig(net), effort=effort, k=LUT_K)
        out["synthesis_s"] = round(time.perf_counter() - t, 3)
        path = store.save(mapped, net, effort, LUT_K, root=args.root)
        out.update(luts=mapped.n_luts, depth=mapped.depth,
                   path=os.path.relpath(path),
                   bytes=os.path.getsize(path))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
