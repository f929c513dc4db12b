"""lut_eval_roofline: the streamed ``lut_eval`` kernel's share of its
HBM roofline, in %: the least time the chip's HBM bandwidth allows for
the bytes one call must move, over the measured device time per call.

Bytes per call are counted from the netlist, not from the
implementation: the packed input words, the output words, and one read
of the LUT table (2^k/8 INIT bytes and k 4-byte leaf indices per LUT).
The chip's integer (VPU) peak is not published, so there is no
compute-side bound."""
from harness import spec

WORD_BYTES = 4
LANE_BITS = 32


def bytes_per_call(netlist: dict, rows: int) -> int:
    """HBM bytes one kernel call needs for ``rows`` packed rows."""
    words = -(-rows // LANE_BITS)
    k = netlist["k"]
    table = netlist["n_luts"] * ((1 << k) // 8 + k * WORD_BYTES)
    io = (netlist["n_pi_wires"] + netlist["n_out_wires"]) * words
    return io * WORD_BYTES + table


def read(ctx):
    dev_us = spec.reader("lut_eval_device_us")(ctx)
    if dev_us is None or dev_us <= 0:
        return None
    rows = int(ctx.cfg["serve"]["sched"]["max_batch"])
    least_s = bytes_per_call(ctx.netlist, rows) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (dev_us * 1e-6)
