"""Pallas TPU kernels for the compute hot-spots of the NullaNet Tiny flow.

  lut_layer     — truth-table-lookup layer (the TPU analogue of the FPGA
                  LUT fabric): bit-pack fanin codes + VMEM table gather.
  xnor_popcount — bit-packed bipolar (±1) matmul via XNOR + popcount,
                  the binary-QAT inference/training forward primitive.
  fanin_matmul  — fanin-K gather-matmul for FCP-sparse linear layers.
  aig_sim       — bit-parallel AIG simulation: the node walk of the
                  synthesis-time equivalence checker run on-chip.
  lut_eval      — whole mapped-netlist execution: the level-major tile
                  plan evaluated as Shannon-cofactor folds over a wire
                  plane in VMEM, or in HBM where it does not fit (the
                  serving path of
                  ``BitplaneNetwork(engine="pallas-streamed")``).
  flash_attention — online-softmax attention (VMEM-tiled), the LM-side
                  hot-spot at 32k+ contexts (GQA via grouped heads).

Each kernel directory holds <name>.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper with shape plumbing) and ref.py (pure-jnp
oracle used by the allclose test sweeps).

All kernels are written against TPU VMEM tiling (blocks aligned to
(8, 128) lanes where applicable) and validated on CPU with
``interpret=True``.
"""
