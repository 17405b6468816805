"""The decode step's share of its roofline: the least time its work needs
(the larger of its FLOPs over peak and its least HBM bytes over peak
bandwidth, both from the configuration's count module; for a dense
decoder every weight once, the KV cache read up to the position, the new
KV entry written) over the device time of its programs in the trace."""

from bench import peaks
from bench.metrics import _decode


def read(run):
    if run.trace is None:
        return None
    secs, n = _decode.step_device(run)
    steps = _decode.traced_steps(run)
    if not n or not steps:
        return None
    pk = run.peak
    least = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                for f, b in steps) / len(steps)
    return peaks.share(least * n, secs, "decode_roofline.serve")
