"""Pass 2 — miter-style functional equivalence between pipeline stages.

Each adjacency of the synth pipeline gets a check:

    SOP cover      <->  AIG built from it       (``equiv_cover_aig``)
    AIG            <->  rewritten/balanced AIG  (``equiv_aigs``)
    AIG            <->  mapped k-LUT netlist    (``equiv_aig_mapped``)
    mapped netlist <->  DevicePlan tensors      (``equiv_mapped_plan``)
    LogicNetwork   <->  mapped netlist          (``equiv_network_mapped``)

Cones with <= ``exhaustive_limit`` primary inputs are *proved* by
exhaustive packed simulation (chunked so a 2^20-minterm sweep never
materializes the whole plane); beyond that, corner vectors (all-zeros,
all-ones, one-hot, one-cold) plus packed random words give the standard
random-simulation filter. Either way a mismatch yields the concrete
counterexample input pattern in the report.

The DevicePlan side is evaluated by ``execute_plan_host`` — an
independent slot-by-slot interpreter of the plan tensors, deliberately
*not* sharing code with ``synth.executor.execute_packed`` so a bug in
the plan compiler cannot hide behind shared evaluation code.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

from repro.synth.aig import AIG
from repro.synth.executor import DevicePlan, MappedNetwork, execute_packed
from repro.synth.simulate import (WORD_BITS, pack_bits, simulate,
                                  unpack_bits)

from .report import CheckReport, Counterexample

PASS = "equiv"
FORMAL_PASS = "formal"

# beyond this many PIs exhaustive enumeration (2^n patterns) is skipped
EXHAUSTIVE_LIMIT = 20
# words simulated per chunk: bounds peak memory at
# n_nodes * CHUNK_WORDS * 4 bytes during exhaustive sweeps
CHUNK_WORDS = 2048

_LOW_VAR_WORDS = (0xAAAAAAAA, 0xCCCCCCCC, 0xF0F0F0F0, 0xFF00FF00,
                  0xFFFF0000)


def exhaustive_chunk(n_pis: int, word0: int, n_words: int) -> np.ndarray:
    """Packed exhaustive patterns for minterms [32*word0, 32*(word0 +
    n_words)): row v is variable v. Bit b of word w is minterm
    32*(word0+w)+b, so variable v < 5 is a fixed bit pattern and
    variable v >= 5 selects on word index."""
    out = np.empty((n_pis, n_words), np.uint32)
    w = np.arange(word0, word0 + n_words, dtype=np.uint64)
    for v in range(n_pis):
        if v < 5:
            out[v] = _LOW_VAR_WORDS[v]
        else:
            out[v] = np.where((w >> np.uint64(v - 5)) & np.uint64(1),
                              np.uint32(0xFFFFFFFF), np.uint32(0))
    return out


def corner_words(n_pis: int) -> np.ndarray:
    """Packed corner patterns: all-zeros, all-ones, every one-hot and
    every one-cold input — the boundary cases random sampling is least
    likely to hit on wide cones."""
    pats = [np.zeros(n_pis, np.uint8), np.ones(n_pis, np.uint8)]
    for i in range(n_pis):
        hot = np.zeros(n_pis, np.uint8)
        hot[i] = 1
        pats.append(hot)
        pats.append(1 - hot)
    return pack_bits(np.stack(pats, axis=1))


def random_pi_words(n_pis: int, n_words: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << WORD_BITS, (n_pis, n_words),
                        dtype=np.uint32)


def _first_mismatch(a: np.ndarray, b: np.ndarray,
                    n_valid_lanes: Optional[int] = None
                    ) -> Optional[Tuple[int, int, int]]:
    """(output_row, word, bit) of the first differing packed bit."""
    diff = a ^ b
    if n_valid_lanes is not None:
        nw = diff.shape[1]
        valid = (np.arange(nw * WORD_BITS) < n_valid_lanes).astype(np.uint8)
        mask = pack_bits(valid[None, :])[0]
        diff = diff & mask[None, :]
    rows, words = np.nonzero(diff)
    if rows.size == 0:
        return None
    i = int(np.lexsort((rows, words))[0])   # earliest input pattern first
    r, w = int(rows[i]), int(words[i])
    d = int(diff[r, w])
    bit = (d & -d).bit_length() - 1
    return r, w, bit


def _lane_bits(pi_words: np.ndarray, word: int, bit: int) -> Tuple[int, ...]:
    return tuple(int((pi_words[v, word] >> bit) & 1)
                 for v in range(pi_words.shape[0]))


EvalFn = Callable[[np.ndarray], np.ndarray]


def miter(eval_ref: EvalFn, eval_dut: EvalFn, n_pis: int,
          rep: CheckReport, stage: str,
          exhaustive_limit: int = EXHAUSTIVE_LIMIT,
          n_random_words: int = 64, seed: int = 0,
          pass_name: str = PASS) -> bool:
    """Compare two (n_pis, W) -> (n_out, W) evaluators; on mismatch,
    record the first counterexample on ``rep``. Returns equivalence."""
    if n_pis == 0:      # constant network: a single empty pattern
        empty = np.zeros((0, 1), np.uint32)
        a, b = np.asarray(eval_ref(empty)), np.asarray(eval_dut(empty))
        rep.checked += 1
        hit = _first_mismatch(a, b, n_valid_lanes=1)
        if hit is None:
            return True
        r, w, bit = hit
        cex = Counterexample((), r, int((b[r, w] >> bit) & 1),
                             int((a[r, w] >> bit) & 1), exhaustive=True)
        rep.error(pass_name, stage, "stages disagree on the constant network",
                  counterexample=cex)
        return False
    if n_pis <= exhaustive_limit:
        total_words = max(1, (1 << n_pis) // WORD_BITS)
        valid = (1 << n_pis) if n_pis < 5 else None
        for w0 in range(0, total_words, CHUNK_WORDS):
            nw = min(CHUNK_WORDS, total_words - w0)
            words = exhaustive_chunk(n_pis, w0, nw)
            a = np.asarray(eval_ref(words))
            b = np.asarray(eval_dut(words))
            rep.checked += nw * WORD_BITS if valid is None else valid
            hit = _first_mismatch(a, b, n_valid_lanes=valid)
            if hit is not None:
                r, w, bit = hit
                cex = Counterexample(_lane_bits(words, w, bit), r,
                                     int((b[r, w] >> bit) & 1),
                                     int((a[r, w] >> bit) & 1),
                                     exhaustive=True)
                rep.error(pass_name, stage,
                          f"exhaustive miter found a mismatch "
                          f"(minterm {(w0 + w) * WORD_BITS + bit})",
                          counterexample=cex)
                return False
        return True
    # wide cone: corners + random words (mismatch = proof; agreement =
    # strong evidence, 32 patterns per word)
    batches = [("corner", corner_words(n_pis))]
    if n_random_words > 0:
        batches.append(("random", random_pi_words(n_pis, n_random_words,
                                                  seed)))
    for kind, words in batches:
        a = np.asarray(eval_ref(words))
        b = np.asarray(eval_dut(words))
        n_valid = (2 * n_pis + 2 if kind == "corner"
                   else words.shape[1] * WORD_BITS)
        rep.checked += n_valid
        hit = _first_mismatch(a, b,
                              n_valid_lanes=(n_valid if kind == "corner"
                                             else None))
        if hit is not None:
            r, w, bit = hit
            cex = Counterexample(_lane_bits(words, w, bit), r,
                                 int((b[r, w] >> bit) & 1),
                                 int((a[r, w] >> bit) & 1))
            rep.error(pass_name, stage,
                      f"{kind}-vector miter found a mismatch "
                      f"({n_pis} PIs, exhaustive skipped)",
                      counterexample=cex)
            return False
    return True


# ---------------------------------------------------------------------------
# Formal (SAT) escalation
# ---------------------------------------------------------------------------

def _report_formal(rep: CheckReport, stage: str, res, eval_ref: EvalFn,
                   eval_dut: EvalFn, n_pis: int) -> bool:
    """Fold a ``FormalResult`` into the report.

    Returns True when the formal engine settled the question (UNSAT
    proof or SAT counterexample) — the caller then skips sampling.
    UNPROVEN records a warning and returns False: the caller *must*
    fall back to the sampled miter, loudly, never silently pass.
    """
    from .sat import SAT, UNSAT

    stat_keys = ("nodes", "queries", "merged_struct", "merged_sat",
                 "refuted", "query_unknown", "conflicts", "decisions",
                 "propagations", "outputs", "outputs_merged")
    rep.info[f"formal[{stage}]"] = {
        "verdict": res.verdict,
        **{k: res.stats[k] for k in stat_keys if k in res.stats}}
    if res.verdict == UNSAT:
        rep.checked += res.stats.get("outputs", 0)
        return True
    if res.verdict == SAT:
        words = pack_bits(np.array(res.cex, np.uint8)[:, None])
        a, b = np.asarray(eval_ref(words)), np.asarray(eval_dut(words))
        hit = _first_mismatch(a, b, n_valid_lanes=1)
        if hit is None:       # engine said SAT but the sim disagrees
            rep.error(FORMAL_PASS, stage,
                      "SAT counterexample failed bitplane replay — "
                      "formal engine bug, treat the stage as unverified")
            return True
        r, w, bit = hit
        cex = Counterexample(res.cex, r, int((b[r, w] >> bit) & 1),
                             int((a[r, w] >> bit) & 1), formal=True)
        rep.error(FORMAL_PASS, stage,
                  f"SAT miter proved inequivalence ({n_pis} PIs, "
                  f"{res.stats['conflicts']} conflicts); counterexample "
                  f"replayed through the bitplane sim",
                  counterexample=cex)
        return True
    rep.warn(FORMAL_PASS, stage,
             f"UNPROVEN: conflict budget exhausted "
             f"({res.stats['conflicts']} conflicts, "
             f"{res.stats['queries']} queries) — falling back to the "
             f"sampled miter, which is a filter, not a proof")
    return False


def _formal_kwargs(conflict_budget, seed):
    kw = {"seed": seed}
    if conflict_budget is not None:
        kw["conflict_budget"] = conflict_budget
    return kw


# ---------------------------------------------------------------------------
# Stage adjacencies
# ---------------------------------------------------------------------------

def equiv_aigs(ref: AIG, dut: AIG, name: str = "aig-rewrite",
               formal: bool = False, conflict_budget: Optional[int] = None,
               **kw) -> CheckReport:
    """AIG <-> transformed AIG (balance / rewrite must preserve the
    function on *every* input — no don't-cares at this stage).

    ``formal=True`` escalates cones wider than the exhaustive limit to
    the SAT engine: UNSAT is a proof at any width, SAT yields a
    replayed counterexample, UNPROVEN falls back to sampling."""
    rep = CheckReport(name)
    if ref.n_pis != dut.n_pis or len(ref.outputs) != len(dut.outputs):
        rep.error(PASS, "aig-rewrite",
                  f"interface mismatch: {ref.n_pis} PIs/"
                  f"{len(ref.outputs)} POs vs {dut.n_pis}/"
                  f"{len(dut.outputs)}")
        return rep
    e_ref = lambda w: simulate(ref, w)
    e_dut = lambda w: simulate(dut, w)
    limit = kw.get("exhaustive_limit", EXHAUSTIVE_LIMIT)
    if formal and ref.n_pis > limit:
        from .sat import prove_aig_equiv
        res = prove_aig_equiv(ref, dut,
                              **_formal_kwargs(conflict_budget,
                                               kw.get("seed", 0)))
        if _report_formal(rep, "aig-rewrite", res, e_ref, e_dut, ref.n_pis):
            return rep
        kw.setdefault("pass_name", FORMAL_PASS)
    miter(e_ref, e_dut, ref.n_pis, rep, "aig-rewrite", **kw)
    return rep


def equiv_aig_mapped(aig: AIG, mapped: MappedNetwork,
                     name: str = "aig-mapped", formal: bool = False,
                     conflict_budget: Optional[int] = None,
                     **kw) -> CheckReport:
    """AIG <-> its k-LUT cover (mapping covers exact cone functions, so
    this too must hold on every input); ``formal=True`` as in
    :func:`equiv_aigs`."""
    rep = CheckReport(name)
    if aig.n_pis != mapped.n_pis or len(aig.outputs) != len(mapped.outputs):
        rep.error(PASS, "aig-mapped",
                  f"interface mismatch: {aig.n_pis} PIs/"
                  f"{len(aig.outputs)} POs vs {mapped.n_pis}/"
                  f"{len(mapped.outputs)}")
        return rep
    e_ref = lambda w: simulate(aig, w)
    e_dut = lambda w: execute_packed(mapped, w)
    limit = kw.get("exhaustive_limit", EXHAUSTIVE_LIMIT)
    if formal and aig.n_pis > limit:
        from .sat import prove_aig_mapped
        res = prove_aig_mapped(aig, mapped,
                               **_formal_kwargs(conflict_budget,
                                                kw.get("seed", 0)))
        if _report_formal(rep, "aig-mapped", res, e_ref, e_dut, aig.n_pis):
            return rep
        kw.setdefault("pass_name", FORMAL_PASS)
    miter(e_ref, e_dut, aig.n_pis, rep, "aig-mapped", **kw)
    return rep


def execute_plan_host(dplan: DevicePlan, pi_words: np.ndarray) -> np.ndarray:
    """Slot-by-slot host interpreter of the DevicePlan tensors — the
    reference semantics of a slot walk over the netlist, sharing no code
    with ``execute_packed``'s level-vectorized fold."""
    pi_words = np.asarray(pi_words, np.uint32)
    w = pi_words.shape[1]
    wires = np.zeros((dplan.n_wires + 1, w), np.uint32)   # +1 = dump row
    wires[1: dplan.n_pis + 1] = pi_words
    n_levels, lw, k = dplan.leaf_idx.shape
    for lvl in range(n_levels):
        for s in range(lw):
            ins = wires[dplan.leaf_idx[lvl, s]]            # (k, W)
            state = np.repeat(dplan.tt_bits[lvl, s][:, None], w, axis=1)
            half = state.shape[0] // 2
            for j in range(k - 1, -1, -1):
                sel = ins[j]
                state = (state[:half] & ~sel) | (state[half:] & sel)
                half //= 2
            wires[dplan.out_wires[lvl, s]] = state[0]
    out = wires[dplan.out_idx]
    out[dplan.out_neg] = ~out[dplan.out_neg]
    return out


def equiv_mapped_plan(mapped: MappedNetwork, dplan: DevicePlan,
                      name: str = "mapped-plan", formal: bool = False,
                      conflict_budget: Optional[int] = None,
                      **kw) -> CheckReport:
    """Mapped netlist <-> its stacked/padded DevicePlan tensors;
    ``formal=True`` as in :func:`equiv_aigs`."""
    rep = CheckReport(name)
    if mapped.n_pis != dplan.n_pis or \
            len(mapped.outputs) != dplan.out_idx.shape[0]:
        rep.error(PASS, "mapped-plan",
                  f"interface mismatch: {mapped.n_pis} PIs/"
                  f"{len(mapped.outputs)} POs vs {dplan.n_pis}/"
                  f"{dplan.out_idx.shape[0]}")
        return rep
    e_ref = lambda w: execute_packed(mapped, w)
    e_dut = lambda w: execute_plan_host(dplan, w)
    limit = kw.get("exhaustive_limit", EXHAUSTIVE_LIMIT)
    if formal and mapped.n_pis > limit:
        from .sat import prove_mapped_plan
        res = prove_mapped_plan(mapped, dplan,
                                **_formal_kwargs(conflict_budget,
                                                 kw.get("seed", 0)))
        if _report_formal(rep, "mapped-plan", res, e_ref, e_dut,
                          mapped.n_pis):
            return rep
        kw.setdefault("pass_name", FORMAL_PASS)
    miter(e_ref, e_dut, mapped.n_pis, rep, "mapped-plan", **kw)
    return rep


def eval_cover_words(cover, pi_words: np.ndarray) -> np.ndarray:
    """Evaluate an espresso ``Cover`` (SOP) on packed words: OR over
    cubes of AND over literals. (1, W) output."""
    from repro.core.espresso import FREE

    w = pi_words.shape[1]
    acc = np.zeros(w, np.uint32)
    for cube in cover.cubes:
        term = np.full(w, 0xFFFFFFFF, np.uint32)
        for v in range(cover.n_vars):
            if cube[v] == FREE:
                continue
            pv = pi_words[v]
            term &= pv if cube[v] == 1 else ~pv
        acc |= term
    return acc[None, :]


def equiv_cover_aig(cover, aig: AIG, dc_mask=None,
                    name: str = "sop-aig", **kw) -> CheckReport:
    """SOP cover <-> single-output AIG built from it. ``dc_mask`` is an
    optional dense bool array over minterms: rows where the function is
    a don't-care are excluded (the AIG is free to differ there)."""
    rep = CheckReport(name)
    n = cover.n_vars
    if aig.n_pis != n or len(aig.outputs) != 1:
        rep.error(PASS, "sop-aig",
                  f"interface mismatch: cover has {n} vars, AIG has "
                  f"{aig.n_pis} PIs / {len(aig.outputs)} POs")
        return rep
    if dc_mask is None:
        miter(lambda w: eval_cover_words(cover, w),
              lambda w: simulate(aig, w), n, rep, "sop-aig", **kw)
        return rep
    dc_mask = np.asarray(dc_mask, bool)

    def masked(fn):
        def run(words):
            # zero the DC lanes on both sides so they always agree
            nw = words.shape[1]
            mint = np.arange(nw * WORD_BITS) % dc_mask.shape[0]
            care = pack_bits((~dc_mask[mint])[None, :].astype(np.uint8))
            return np.asarray(fn(words)) & care
        return run

    miter(masked(lambda w: eval_cover_words(cover, w)),
          masked(lambda w: simulate(aig, w)), n, rep, "sop-aig", **kw)
    return rep


def _net_mapped_eval(net, mapped: MappedNetwork, codes: np.ndarray):
    """(got, want) output codes of the mapped net vs the LogicNetwork
    oracle on a (n, n_inputs) batch of input codes."""
    n = codes.shape[0]
    want = np.asarray(net.apply_codes(codes))
    in_bits = net.in_spec.code_bits
    planes = np.empty((codes.shape[1] * in_bits, n), np.uint8)
    for b in range(in_bits):
        planes[b::in_bits] = ((codes >> b) & 1).T
    out_words = execute_packed(mapped, pack_bits(planes))
    out_bits_arr = unpack_bits(out_words, n)
    out_bits = net.layers[-1].out_spec.code_bits
    got = np.zeros((n, out_bits_arr.shape[0] // out_bits), np.int64)
    for b in range(out_bits):
        got |= out_bits_arr[b::out_bits].T.astype(np.int64) << b
    return got, want


def equiv_network_mapped(net, mapped: MappedNetwork,
                         n_samples: int = 1024, seed: int = 0,
                         formal: bool = False,
                         conflict_budget: Optional[int] = None,
                         name: str = "network-mapped") -> CheckReport:
    """LogicNetwork truth-table oracle <-> mapped netlist on *valid*
    input codes.

    The SOP extraction feeds espresso unreachable codes as don't-cares,
    so the mapped net only promises equality on codes the quantizer can
    produce — arbitrary bit patterns would yield false counterexamples.
    The counterexample here is therefore reported as an input *code*
    row, not a PI bit pattern.

    ``formal=True`` first runs the SAT engine with the quantizer care
    set encoded as CNF blocking clauses: UNSAT proves equality on every
    reachable code (any width), SAT yields a code-row counterexample
    replayed through the bitplane sim, UNPROVEN falls back to the
    sampled code check below.
    """
    rep = CheckReport(name)
    in_bits = net.in_spec.code_bits
    if formal:
        from .sat import SAT, UNSAT, prove_network_mapped
        res = prove_network_mapped(
            net, mapped, **_formal_kwargs(conflict_budget, seed))
        stage = "network-mapped"
        rep.info[f"formal[{stage}]"] = {
            "verdict": res.verdict,
            **{k: res.stats[k] for k in
               ("nodes", "queries", "merged_struct", "merged_sat",
                "refuted", "query_unknown", "conflicts", "outputs",
                "outputs_merged") if k in res.stats}}
        if res.verdict == UNSAT:
            rep.checked += res.stats.get("outputs", 0)
            return rep
        if res.verdict == SAT:
            bits = np.array(res.cex, np.int64)
            codes = np.zeros((1, net.n_inputs), np.int64)
            for b in range(in_bits):
                codes[0] |= bits[b::in_bits] << b
            got, want = _net_mapped_eval(net, mapped, codes)
            jbad = np.nonzero(got[0] != want[0])[0]
            if jbad.size == 0:
                rep.error(FORMAL_PASS, stage,
                          "SAT counterexample failed bitplane replay — "
                          "formal engine bug, treat the stage as "
                          "unverified")
                return rep
            j = int(jbad[0])
            cex = Counterexample(tuple(int(c) for c in codes[0]), j,
                                 int(got[0, j]), int(want[0, j]),
                                 formal=True)
            rep.error(FORMAL_PASS, stage,
                      f"SAT miter proved inequivalence on a reachable "
                      f"code row ({res.stats['conflicts']} conflicts; "
                      f"inputs below are quantizer *codes*, not PI "
                      f"bits); replayed through the bitplane sim",
                      counterexample=cex)
            return rep
        rep.warn(FORMAL_PASS, stage,
                 f"UNPROVEN: conflict budget exhausted "
                 f"({res.stats['conflicts']} conflicts) — falling back "
                 f"to sampled code rows, which is a filter, not a proof")
    rng = np.random.default_rng(seed)
    n_levels = net.in_spec.n_levels
    codes = rng.integers(0, n_levels, (n_samples, net.n_inputs),
                         dtype=np.int64)
    got, want = _net_mapped_eval(net, mapped, codes)
    rep.checked += n_samples
    bad = np.nonzero(np.any(got != want, axis=1))[0]
    if bad.size:
        r = int(bad[0])
        j = int(np.nonzero(got[r] != want[r])[0][0])
        cex = Counterexample(tuple(int(c) for c in codes[r]), j,
                             int(got[r, j]), int(want[r, j]))
        rep.error(PASS, "network-mapped",
                  f"mapped netlist disagrees with the truth-table oracle "
                  f"on {bad.size}/{n_samples} sampled code rows (inputs "
                  f"below are quantizer *codes*, not PI bits)",
                  counterexample=cex)
    return rep
