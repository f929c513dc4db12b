"""One launch-configuration surface for every kernel in ``repro.kernels``.

Each kernel directory used to grow its own ad-hoc launch kwargs
(``block_w=...``, ``interpret=...``, per-kernel VMEM assumptions), so
every caller had to know six different call conventions. ``KernelSpec``
is the single object they pass instead:

  * ``TileConfig`` — the geometry knobs: word/lane tile (``block_w``),
    row tile for blocked kernels (``block_rows``), slot tile for the
    streamed netlist walks (``tile_rows``), and the per-core VMEM
    budget the tiling must respect;
  * ``KernelSpec`` — ties a ``TileConfig`` to the interpret decision
    (``interpret=None`` auto-resolves to "interpret everywhere but a
    real TPU", the contract every ops.py wrapper already used).

ops.py wrappers accept ``spec=`` and fall back to their historical
keyword arguments when it is omitted, so existing call sites keep
working unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

DEFAULT_BLOCK_W = 128       # lane-aligned word tile (last axis)
DEFAULT_TILE_ROWS = 32      # slot tile for streamed netlist walks
DEFAULT_VMEM_BUDGET = 16 << 20   # one TPU core's VMEM


def default_interpret() -> bool:
    """Interpret on anything but a real TPU: CPU CI runs kernels through
    the Pallas interpreter, a TPU runs the compiled Mosaic kernel."""
    import jax
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Geometry of one kernel launch.

    ``block_w`` tiles the packed-word / lane axis (the grid axis of the
    bitplane kernels), ``block_rows`` tiles row-blocked kernels, and
    ``tile_rows`` is the slot-tile of the streamed netlist walk (how
    many LUT slots one double-buffered step evaluates). All three are
    upper bounds: wrappers clamp to the actual problem size.
    """

    block_w: int = DEFAULT_BLOCK_W
    block_rows: int = 0                  # 0 = kernel default / unblocked
    tile_rows: int = DEFAULT_TILE_ROWS
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET

    def clamp_block_w(self, w: int) -> int:
        """Effective word tile for a ``w``-word problem."""
        return min(self.block_w, max(1, w))

    def clamp_tile_rows(self, rows: int) -> int:
        """Effective slot tile for a ``rows``-slot level walk."""
        return min(self.tile_rows, max(1, rows))


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A named launch configuration for one kernel."""

    name: str = ""
    interpret: Optional[bool] = None     # None = auto (not on a TPU)
    tile: TileConfig = dataclasses.field(default_factory=TileConfig)

    def resolve_interpret(self, override: Optional[bool] = None) -> bool:
        """Explicit per-call override > spec pin > backend auto-detect."""
        if override is not None:
            return override
        if self.interpret is not None:
            return self.interpret
        return default_interpret()


DEFAULT_SPEC = KernelSpec()
