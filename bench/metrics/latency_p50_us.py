"""latency_p50_us: median over every request due in the window of its
done time minus its due time (open loop, host clock). The sample count
and the 99th percentile go to standard error."""
import sys

import numpy as np

from harness.measure import latencies_us


def read(ctx):
    if ctx.tr["loop"] != "open":
        return None
    lat = latencies_us(ctx.served)
    if not lat.size:
        return None
    print(f"[bench] latency samples: {lat.size}, p99 "
          f"{np.percentile(lat, 99):.1f} us", file=sys.stderr)
    return float(np.percentile(lat, 50))
