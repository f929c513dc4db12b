"""Bitplane request aggregation: concurrent requests fill uint32 lanes.

``repro.synth``'s executor packs 32 *samples* per uint32 word and
evaluates the whole mapped 6-LUT netlist once per pack. Here the lanes
are filled with 32 concurrent *requests* instead: the scheduler's batch
(row-concatenated request payloads) is quantized to input codes, each
code bit scattered into its wire's bitplane with request r in bit r%32
of word r//32, and one netlist evaluation over the precompiled plan
serves the entire pack — the paper's bit-level parallelism turned
into a request-throughput mechanism. Per-request argmaxes are sliced
back out of the output planes, bit-identical to ``classify`` on the
gather and Pallas paths.

The netlist executor is whatever engine the ``BitplaneNetwork`` was
built with (``repro.synth.ENGINES``): under the device engine
(``"pallas-streamed"``) the packed words are handed straight to the
kernel and only the scattered argmax labels come back — pack → all
levels → complement → argmax is one fused jit, so between enqueue and
verdict nothing touches the host. The call returns once that program is
launched: its labels are ``PendingLabels``, which ``np.asarray`` waits
for, so the scheduler can pack and launch the next batch meanwhile. The
numpy engine keeps the host fold (``execute_packed``) + decode and
returns an ndarray. Aggregation itself is engine-agnostic;
``classify_packed`` dispatches.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.obs.trace import NULL_TRACER
from repro.synth.executor import BitplaneNetwork
from repro.synth.simulate import WORD_BITS, pack_bits


class BitplaneAggregator:
    """Scheduler executor: one netlist evaluation per request pack.

    Satisfies the ``MicroBatchScheduler`` executor contract
    ``(B, n_features) -> (B,)``; every 32 rows of the batch share one
    uint32 lane-word through the whole netlist.

    Not thread-safe by design — the scheduler serializes executor calls
    on one dispatch thread (its completion thread only awaits the
    returned labels), so the ``n_*`` counters need no lock and
    carry no ``_GUARDED_BY`` annotation. Wrap in ``ReplicaSet`` for
    concurrent dispatch.
    """

    def __init__(self, bitnet: BitplaneNetwork, n_classes: int,
                 pad_rows: Optional[int] = None):
        self.bitnet = bitnet
        self.n_classes = n_classes
        self.lanes_per_word = WORD_BITS
        self.pad_rows = pad_rows
        self.tracer = NULL_TRACER
        self.n_features = bitnet.net.n_inputs   # admission width check
        self.n_evals = 0            # lane-words carrying >= 1 real request
        self.n_rows = 0             # request rows served
        self.n_pad_rows = 0         # shape-stability padding rows added
        self.n_partial_packs = 0    # flushes whose last lane-word is partial
        if pad_rows:                # warm the single device-program shape
            np.asarray(self(np.zeros((1, bitnet.net.n_inputs), np.float32)))
            self.n_evals = self.n_rows = 0
            self.n_pad_rows = self.n_partial_packs = 0

    def pack_requests(self, x: np.ndarray) -> np.ndarray:
        """(B, n_features) real inputs -> (n_pi_wires, ceil(B/32)) words.

        The input quantizer runs on the host (float32 numpy), so the
        pack makes no device transfer. With ``pad_rows`` set, short
        batches are zero-padded to that row count first: one word count
        for the device program, so its kernel and argmax compile once
        instead of once per distinct flush size.
        """
        bn = self.bitnet
        tr = self.tracer
        if self.pad_rows and x.shape[0] < self.pad_rows:
            x = np.concatenate(
                [x, np.zeros((self.pad_rows - x.shape[0], x.shape[1]),
                             x.dtype)])
        with tr.span("quantize", cat="pack"):
            codes = bn.quantize_codes(x)
        with tr.span("bitpack", cat="pack"):
            planes = np.empty((codes.shape[1] * bn.in_bits, codes.shape[0]),
                              np.uint8)
            for b in range(bn.in_bits):  # wire i*in_bits+b = bit b of code i
                planes[b::bn.in_bits] = ((codes >> b) & 1).T
            return pack_bits(planes)

    def __call__(self, x: np.ndarray,
                 deadline_us: Optional[float] = None):
        """Evaluate one request pack; returns its labels, pending on the
        device engine (``np.asarray`` waits for them). ``deadline_us``
        (the tightest absolute SLO deadline in the batch, forwarded by
        the scheduler) is what triggers partial-pack flushes upstream:
        the scheduler dispatches before the lane-word is full whenever
        that deadline cannot absorb further fill-wait, and
        ``n_partial_packs`` counts how often the pack went out with idle
        lanes as a result."""
        x = np.asarray(x)
        true_rows = x.shape[0]
        tr = self.tracer
        pack_args = exec_args = None
        if tr.enabled:
            pack_args = {"rows": true_rows, "batch": tr.batch,
                         "lane_words": -(-true_rows // self.lanes_per_word)}
            exec_args = {"rows": true_rows, "batch": tr.batch,
                         "engine": self.bitnet.engine}
        with tr.span("aggregate_pack", cat="pack", args=pack_args):
            pi_words = self.pack_requests(x)
        # engine dispatch happens inside classify_packed: the device
        # engine ships the words to the device and launches the program
        # (h2d + launch: the labels stay pending); numpy is the host
        # fold + decode.
        with tr.span("device_exec", cat="exec", args=exec_args):
            labels = self.bitnet.classify_packed(pi_words, true_rows,
                                                 self.n_classes)
        # occupancy is accounted against *real* request rows: lane-words
        # that exist only because of pad_rows shape-stability padding
        # are tracked separately, not counted as served capacity.
        self.n_evals += -(-true_rows // self.lanes_per_word)
        self.n_rows += true_rows
        if self.pad_rows and true_rows < self.pad_rows:
            self.n_pad_rows += self.pad_rows - true_rows
        if true_rows % self.lanes_per_word:
            self.n_partial_packs += 1
        return labels

    def set_tracer(self, tracer) -> None:
        """Adopt ``tracer`` (propagated through the underlying network
        to its engine, so the ``h2d`` span nests inside ``device_exec``
        and the labels' ``fetch`` is recorded where they are awaited);
        the scheduler calls this automatically when constructed with
        one."""
        self.tracer = tracer
        self.bitnet.tracer = tracer

    def stats(self) -> dict:
        occ = self.mean_lane_occupancy
        return {"n_evals": self.n_evals, "n_rows": self.n_rows,
                "n_pad_rows": self.n_pad_rows,
                "n_partial_packs": self.n_partial_packs,
                "engine": self.bitnet.engine,
                "mean_lane_occupancy": occ}

    def publish(self, registry, name: str = "aggregate") -> None:
        """Expose the occupancy counters through a
        ``repro.obs.MetricsRegistry`` snapshot provider."""
        registry.register(name, self.stats)

    @property
    def mean_lane_occupancy(self) -> Optional[float]:
        """Fraction of uint32 lanes (in lane-words carrying at least one
        real request) filled by a real request; shape-stability pad rows
        are excluded (see ``n_pad_rows``)."""
        if self.n_evals == 0:
            return None
        return self.n_rows / (self.n_evals * self.lanes_per_word)
