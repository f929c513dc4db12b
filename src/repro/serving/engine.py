"""Batched serving engine.

Two request kinds, matching the paper's deployment story:
  * LogicEngine — ultra-low-latency classification through the compiled
    fixed-function logic network (the paper's product); requests are
    micro-batched with a latency deadline, executed via the Pallas
    lut_layer path (oracle path selectable);
  * LMEngine    — autoregressive decode with a shared KV cache pool:
    continuous batching over slots (admit on free slot, retire on EOS /
    max tokens). On-pod deployment shards slots over ("pod","data") and
    heads over "model" exactly like the dry-run's decode cells.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.logic_infer import LogicNetwork
from repro.core.lutcost import LUT_K
from repro.models import lm


# ---------------------------------------------------------------------------
# Logic-network serving (the paper's inference product)
# ---------------------------------------------------------------------------

BITPLANE_BACKENDS = ("bitplane", "bitplane-prebuilt")


@dataclasses.dataclass
class LogicEngine:
    """Micro-batching frontend over a compiled LogicNetwork.

    ``backend`` selects the inference representation:
      * ``"gather"``   — per-neuron truth-table gathers (pure jnp oracle);
      * ``"pallas"``   — same tables through the lut_layer Pallas kernel;
      * ``"bitplane"`` — the ``repro.synth`` mapped 6-LUT netlist run as
        packed bitplane ops (32 samples per uint32 lane) — no per-neuron
        gathers at all; synthesized at construction;
      * ``"bitplane-prebuilt"`` — the same, from the netlist synthesized
        ahead of time into ``repro.synth.store`` (a store miss raises
        ``NetlistNotFound``; it never synthesizes).
    Argmax outputs are identical across backends.

    For the bitplane backend, ``engine`` names a netlist executor in
    the ``repro.synth.executors`` registry: ``"numpy"`` folds levels on
    the host; ``"pallas"`` runs the whole levelized netlist through the
    monolithic ``kernels.lut_eval`` device pipeline;
    ``"pallas-streamed"`` through the streamed/tiled kernel (pack →
    levels → complement → argmax in one jit either way). Custom engines
    registered via ``executors.register`` work here unchanged.

    ``device`` (a ``jax.Device``) pins this engine's arrays and jitted
    calls to one device, so replicas can each own a chip; ``None`` uses
    JAX's default device.
    """

    net: LogicNetwork
    n_classes: int
    max_batch: int = 256
    max_wait_ms: float = 0.2
    use_pallas: bool = False            # legacy alias for backend="pallas"
    backend: str = "gather"
    engine: str = "numpy"               # bitplane netlist executor
    synth_effort: int = 1
    device: Any = None

    def __post_init__(self):
        if self.use_pallas and self.backend == "gather":
            self.backend = "pallas"
        if self.backend in BITPLANE_BACKENDS:
            from repro.serve.aggregate import BitplaneAggregator
            from repro.synth import BitplaneNetwork
            build = (BitplaneNetwork.from_logic_network
                     if self.backend == "bitplane"
                     else BitplaneNetwork.from_store)
            self.bitnet = build(self.net, effort=self.synth_effort, k=LUT_K,
                                engine=self.engine, device=self.device)
            # padded aggregator: one device-program shape for every flush size
            self._fn = BitplaneAggregator(self.bitnet, self.n_classes,
                                          pad_rows=self.max_batch)
            return
        if self.backend not in ("gather", "pallas"):
            raise ValueError(f"unknown LogicEngine backend {self.backend!r}")
        use_pallas = self.backend == "pallas"
        self._fn = jax.jit(
            lambda x: jnp.argmax(
                self.net(x, use_pallas=use_pallas)
                [..., : self.n_classes], axis=-1))
        # warm the jit cache at the serving batch size
        self._fn(jax.device_put(
            np.zeros((self.max_batch, self.net.n_inputs), np.float32),
            self.device))

    def exec_batch(self, x: np.ndarray) -> np.ndarray:
        """One evaluation: (B <= max_batch, F) -> (B,) int32 argmax.

        The jit backends pad to the warmed ``max_batch`` shape; the
        bitplane backend packs exactly the rows it is given.
        """
        x = np.asarray(x)
        n = x.shape[0]
        assert n <= self.max_batch, (n, self.max_batch)
        if self.backend in BITPLANE_BACKENDS:
            return np.asarray(self._fn(x))
        pad = self.max_batch - n
        if pad:
            x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
        return np.asarray(self._fn(jax.device_put(x, self.device)))[:n]

    def classify(self, x: np.ndarray) -> np.ndarray:
        """Synchronous batched classification."""
        n = x.shape[0]
        out = np.empty((n,), np.int32)
        for i in range(0, n, self.max_batch):
            xb = x[i: i + self.max_batch]
            out[i: i + xb.shape[0]] = self.exec_batch(xb)
        return out

    def scheduler_executor(self) -> Callable[[np.ndarray], np.ndarray]:
        """Executor callable for ``repro.serve`` schedulers.

        The bitplane backend aggregates the batch's requests into uint32
        lanes and evaluates the mapped netlist once per pack
        (``repro.serve.aggregate``); the jit backends run one padded
        evaluation. All three return identical argmaxes. The executor
        advertises ``n_features`` so the scheduler rejects wrong-width
        payloads at admission (typed ``BAD_SHAPE``) instead of letting
        one malformed request poison a whole batch.
        """
        if self.backend in BITPLANE_BACKENDS:
            return self._fn             # BitplaneAggregator: has n_features

        def ex(x: np.ndarray) -> np.ndarray:
            return self.exec_batch(x)

        ex.n_features = self.net.n_inputs
        return ex

    def serve_queue(self, requests: List[np.ndarray], clock=None,
                    deadline_us: Optional[float] = None,
                    lane_slo_us: Optional[Tuple[float, ...]] = None,
                    tracer=None
                    ) -> Tuple[List[np.ndarray], Dict[str, float]]:
        """Micro-batched serving of a request list; returns per-request
        results + latency stats (p50/p95/p99/mean, µs).

        Thin compatibility wrapper over ``repro.serve``'s micro-batch
        scheduler: all requests are admitted up front and drained, so
        the reported latencies are true enqueue→complete times — a
        request stuck behind earlier batches shows its head-of-line
        wait, which the old per-call timing loop hid.

        ``deadline_us`` gives every request that latency budget (µs from
        enqueue); ``lane_slo_us`` installs the per-lane SLO table
        instead. With either set, requests past their budget at flush
        time are shed with a typed ``RequestRejected(DEADLINE_EXCEEDED)``
        (a ``None`` in the results list) and the stats gain
        ``deadline_miss_rate`` / ``shed``.
        """
        from repro.serve import (MicroBatchScheduler, RequestRejected,
                                 SchedConfig)

        cfg = SchedConfig(max_batch=self.max_batch,
                          max_wait_us=self.max_wait_ms * 1e3,
                          max_queue=max(2 * len(requests), 1),
                          n_priorities=1, lane_slo_us=lane_slo_us)
        sched = MicroBatchScheduler(self.scheduler_executor(), cfg,
                                    clock=clock, tracer=tracer)
        futs: List[Any] = []
        for r in requests:
            r = np.asarray(r)
            if r.ndim > 1 and r.shape[0] > self.max_batch:
                futs.append([sched.submit(r[i: i + self.max_batch],
                                          deadline_us=deadline_us)
                             for i in range(0, r.shape[0], self.max_batch)])
            else:
                futs.append(sched.submit(r, deadline_us=deadline_us))
        sched.drain()

        def _res(f):
            try:
                return np.asarray(f.result())
            except RequestRejected:
                return None                 # shed past its deadline

        results = []
        for f in futs:
            if isinstance(f, list):
                parts = [_res(p) for p in f]
                results.append(None if any(p is None for p in parts)
                               else np.concatenate(parts))
            else:
                results.append(_res(f))
        snap = sched.metrics.snapshot()
        stats = {k: snap[k] for k in
                 ("p50_us", "p95_us", "p99_us", "mean_us", "qps",
                  "mean_batch_occupancy", "n_batches",
                  "deadline_miss_rate", "shed")}
        return results, stats


# ---------------------------------------------------------------------------
# LM serving (continuous batching decode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LMRequest:
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1            # -1 = never
    out_tokens: Optional[List[int]] = None


_LM_CACHE_LEAVES = ("k", "v", "positions", "ssm", "conv", "enc_out")


class LMEngine:
    """Continuous-batching decode over a fixed slot pool.

    Slots admit requests as they free up; one jitted decode_step advances
    every active slot each tick (inactive slots carry a pad token, their
    outputs are discarded) — the standard TPU serving shape where the
    decode batch is static and occupancy varies.

    Admission sits behind the ``repro.serve`` bounded priority queue:
    ``submit`` enqueues with a priority lane and raises a typed
    ``RequestRejected`` when ``max_pending`` is hit (backpressure),
    and freed slots always admit the highest-priority waiter first.
    """

    def __init__(self, cfg: ArchConfig, params, n_slots: int = 4,
                 max_seq: int = 512, max_pending: Optional[int] = None,
                 n_priorities: int = 2, clock=None):
        from repro.serve.clock import SystemClock
        from repro.serve.sched import BoundedPriorityQueue

        self.cfg = cfg
        self.params = params
        self.clock = clock or SystemClock()
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.cache = lm.init_cache(cfg, n_slots, max_seq)
        self.positions = np.zeros((n_slots,), np.int32)
        self.active: List[Optional[LMRequest]] = [None] * n_slots
        self.last_tok = np.zeros((n_slots, 1), np.int32)
        self._decode = jax.jit(
            lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos))
        self._prefill_cache = {}
        self._splice = jax.jit(self._splice_slot, donate_argnums=(0,))
        self.admission = BoundedPriorityQueue(
            max_pending if max_pending is not None else (1 << 30),
            n_priorities)

    def submit(self, req: LMRequest, priority: int = 0,
               deadline_us: Optional[float] = None):
        """Admit into the priority queue (typed reject when full).

        ``deadline_us`` is a queueing budget (µs from enqueue): a
        request still waiting for a decode slot past its budget is shed
        with a typed ``RequestRejected(DEADLINE_EXCEEDED)`` on its
        future instead of being admitted late.

        Returns the request's ``ServeFuture``: resolved with the
        finished ``LMRequest`` by ``run``, with enqueue→complete
        latency on ``fut.latency_us``.
        """
        import math

        from repro.serve.sched import ServeFuture, ServeRequest

        fut = ServeFuture()
        fut.t_enqueue_us = self.clock.now_us()
        self.admission.push(ServeRequest(
            x=req, rows=1, priority=priority,
            t_enqueue_us=fut.t_enqueue_us, future=fut,
            deadline_us=(fut.t_enqueue_us + deadline_us
                         if deadline_us is not None else math.inf)))
        return fut

    @staticmethod
    def _splice_slot(cache, single, slot):
        """Write ONE admitted slot into the pooled cache.

        Runs jitted with the pool donated, so every leaf updates in
        place — O(layers × window) writes for the admitted slot only,
        where the old two-step ``.at[...].set`` path materialised two
        full-pool copies per leaf (O(layers × slots) device traffic per
        admission).
        """
        out = {}
        for key, pool in cache.items():
            s = single[key]
            if key in ("k", "v"):            # (L, B, W, KV, dh)
                w = min(s.shape[2], pool.shape[2])
                row = jnp.zeros(pool.shape[:1] + pool.shape[2:], pool.dtype)
                row = row.at[:, :w].set(s[:, 0, :w])
                out[key] = pool.at[:, slot].set(row)
            elif key == "positions":          # (B, W)
                w = min(s.shape[1], pool.shape[1])
                row = jnp.full(pool.shape[1:], -1, pool.dtype)
                row = row.at[:w].set(s[0, :w])
                out[key] = pool.at[slot].set(row)
            elif key in ("ssm", "conv"):      # (L, B, ...)
                out[key] = pool.at[:, slot].set(s[:, 0])
            else:                             # enc_out (B, F, D)
                out[key] = pool.at[slot].set(s[0])
        return out

    def _admit(self, req: LMRequest, slot: int):
        # per-request prefill at its prompt length (compile cache per len)
        s = len(req.prompt)
        toks = jnp.asarray(req.prompt[None, :])
        if s not in self._prefill_cache:
            self._prefill_cache[s] = jax.jit(
                lambda p, t: lm.prefill(self.cfg, p, tokens=t,
                                        max_seq=self.max_seq))
        logits, cache1 = self._prefill_cache[s](self.params, toks)

        for key in cache1:
            if key not in _LM_CACHE_LEAVES:
                raise KeyError(f"unknown cache leaf {key}")
        # splice only the admitted slot (ring slot layouts agree because
        # prompt_len <= pool window here)
        self.cache = self._splice(self.cache, cache1,
                                  jnp.asarray(slot, jnp.int32))
        req.out_tokens = []
        self.active[slot] = req
        self.positions[slot] = s
        self.last_tok[slot, 0] = int(jnp.argmax(logits[0]))
        req.out_tokens.append(int(self.last_tok[slot, 0]))

    def run(self, requests: Sequence[LMRequest] = ()) -> List[LMRequest]:
        """Decode until the admission queue and all slots are empty.

        ``requests`` (back-compat) are submitted at priority 0 before
        the loop; callers using ``submit`` directly can pass nothing.
        """
        for r in requests:
            self.submit(r)
        from repro.serve.sched import RejectReason, RequestRejected

        done: List[LMRequest] = []
        sreqs: List[Optional[Any]] = [None] * self.n_slots
        while len(self.admission) or any(a is not None for a in self.active):
            # shed waiters whose queueing budget expired before a slot
            # freed up — a typed reject beats a silently late admission
            now_us = self.clock.now_us()
            for expired in self.admission.shed_expired(now_us):
                expired.future.t_done_us = now_us
                expired.future.set_exception(RequestRejected(
                    RejectReason.DEADLINE_EXCEEDED,
                    f"expired {now_us - expired.deadline_us:.0f} µs before "
                    f"a decode slot freed"))
            # admit, highest priority lane first
            for i in range(self.n_slots):
                if self.active[i] is None and len(self.admission):
                    (sreq,) = self.admission.pop_batch(1)
                    sreqs[i] = sreq
                    self._admit(sreq.x, i)
            # decode tick
            logits, self.cache = self._decode(
                self.params, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.positions))
            nxt = np.asarray(jnp.argmax(logits, -1))
            for i in range(self.n_slots):
                req = self.active[i]
                if req is None:
                    continue
                tok = int(nxt[i])
                req.out_tokens.append(tok)
                self.positions[i] += 1
                self.last_tok[i, 0] = tok
                if (tok == req.eos_id
                        or len(req.out_tokens) >= req.max_new_tokens
                        or self.positions[i] >= self.max_seq - 1):
                    done.append(req)
                    self.active[i] = None
                    if sreqs[i] is not None:
                        sreqs[i].future.t_done_us = self.clock.now_us()
                        sreqs[i].future.set_result(req)
                        sreqs[i] = None
        return done
