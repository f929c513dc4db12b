"""repro.synth — multi-level logic synthesis and k-LUT technology mapping.

The offline replacement for the Vivado step of NullaNet Tiny's flow:

    SOP covers (core.espresso)
      -> AIG with structural hashing          (synth.aig / synth.from_sop)
      -> balance + DAG-aware rewriting        (synth.rewrite)
      -> depth-optimal 6-LUT mapping + area   (synth.lutmap)
      -> measured LUTs/depth, Verilog,        (synth.executor)
         and bit-parallel TPU/CPU execution   (synth.simulate,
                                               kernels.aig_sim)

``compile_logic_network(net)`` is the one-call pipeline from a compiled
``LogicNetwork`` to its executable mapped netlist, a ``BitplaneNetwork``
that runs on one of ``ENGINES``: the numpy host fold (the reference) or
the device engine, ``"pallas-streamed"``.
"""
from .aig import AIG, CONST0, CONST1, lit, lit_compl, lit_not, lit_var
from .cuts import Cut, enumerate_cuts
from .executor import (ENGINES, BitplaneNetwork, DevicePlan, TilePlan,
                       UnknownEngineError, compile_device_plan,
                       compile_tile_plan, emit_verilog, execute_packed,
                       execute_packed_streamed)
from .from_sop import cover_to_aig, layer_to_aig, network_to_aig, table_to_aig
from .lutmap import MappedLUT, MappedNetwork, map_aig
from .rewrite import balance, optimize, rewrite
from .simulate import (exhaustive_equiv, input_patterns, pack_bits,
                       random_equiv, random_words, simulate, unpack_bits)


def synthesize(aig: AIG, effort: int = 1, k: int = 6,
               verify=False) -> MappedNetwork:
    """balance/rewrite rounds (``effort``; 0 = map the raw AIG) followed
    by k-LUT mapping with area recovery.

    ``verify=True`` miters every transform against its input (rewrite
    must preserve the function everywhere, the LUT cover must match the
    optimized AIG everywhere) and raises ``repro.check.CheckFailure``
    with a counterexample on any disagreement. Cones wider than the
    20-PI exhaustive limit are only *sampled*; ``verify="formal"``
    escalates them to the ``repro.check.sat`` engine, which proves the
    miter UNSAT at any width (or fails with a replayed SAT
    counterexample / an explicit UNPROVEN warning)."""
    raw = aig
    if effort > 0:
        aig = optimize(aig, rounds=effort)
    mapped = map_aig(aig, k=k)
    if verify:
        from repro.check.pipeline import verify_synthesis
        verify_synthesis(raw, aig, mapped, formal=(verify == "formal"))
    return mapped


def compile_logic_network(net, effort: int = 1, k: int = 6,
                          engine: str = "numpy",
                          interpret=None,
                          verify: bool = False,
                          device=None) -> BitplaneNetwork:
    """LogicNetwork -> optimized mapped netlist, ready to execute.

    ``engine`` is one of ``ENGINES``: ``"pallas-streamed"`` runs the
    netlist through the fused ``kernels.lut_eval`` device pipeline
    instead of the host fold (``"numpy"``). ``verify=True``
    additionally runs the ``repro.check`` lint + equivalence passes
    over every synthesis stage (CheckFailure on the first
    counterexample). ``device`` pins the device engine to one
    ``jax.Device``."""
    return BitplaneNetwork.from_logic_network(net, effort=effort, k=k,
                                              engine=engine,
                                              interpret=interpret,
                                              verify=verify, device=device)
