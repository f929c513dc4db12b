"""serve_mfu: the whole served path's share of the chip's bf16 peak, in
%: rows labelled per second (before the device trace began) times the
FLOPs of the sparse MLP the netlist replaces, over chips times the
peak. The weights are real (only activations are quantized), so bf16
matrix units are the form of the same model the chip would otherwise
run."""
from harness.measure import rows_per_s_in


def flops_per_event(cfg: dict) -> int:
    """2 x sum over layers of neurons x fanin."""
    return 2 * sum(int(n) * int(k)
                   for n, k in zip(cfg["features"], cfg["fanins"]))


def read(ctx):
    rate = rows_per_s_in(ctx.served, ctx.host_window)
    chips = int(ctx.cell["chips"])
    return 100.0 * rate * flops_per_event(ctx.cfg) / (
        chips * ctx.peak["bf16_flops_per_s"])
