"""Share of its memory roofline that the ``bitunpack`` kernel reached:
the bytes its launches read and wrote (from their shapes,
``bench/cost.py``) over the chip's HBM bandwidth, divided by the summed
device time of its events in the trace.  The kernel does a few integer
ops per byte, so bandwidth and not compute bounds it."""

import math

from bench.trace_reduce import op_seconds


def read(obs: dict):
    nbytes = obs["counters"].get("bitunpack_bytes")
    peaks = obs.get("peaks")
    if not nbytes or not peaks or math.isnan(nbytes):
        return None
    secs, count = op_seconds(obs["trace"], "bitunpack")
    if count == 0 or secs <= 0:
        return None
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / secs
