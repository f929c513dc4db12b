"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    """The workload entry with its configuration and traffic loaded:
    ``{"name", "chips", "config", "traffic", "why", "cfg", "tr"}``."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(by_name)}")
    w = dict(by_name[workload])
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    w["cfg"] = load_json(conf["file"])
    w["tr"] = load_json(os.path.join("bench", "traffic",
                                     w["traffic"] + ".json"))
    return w


def metric_entries(bench: dict, w: dict, trace: bool) -> List[dict]:
    """The metrics a run of cell ``w`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced. A metric without a
    ``workloads`` list applies wherever its end-to-end metric (or, for
    a per-layer one, the metric it moves) is reported."""
    e2e = [m for m in bench["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    out = []
    for m in bench["per_layer"]:
        if ("workloads" in m and w["name"] in m["workloads"]
                or "workloads" not in m and m["moves"] in reported):
            out.append(m)
    return out


def load_module(path: str, name: str):
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """``bench/metrics/<metric>.py``: its ``read(ctx)`` gives the value,
    or None where the run has nothing to read."""
    mod = load_module(os.path.join(BENCH_DIR, "metrics", metric + ".py"),
                      "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def reference(cfg: dict):
    """The configuration's plain reference module, beside its file."""
    name = cfg["reference"]
    return load_module(os.path.join(BENCH_DIR, "configs", name + ".py"),
                       "bench_reference_" + name)


def peaks() -> Dict[str, dict]:
    return load_json(os.path.join("bench", "peaks.json"))
