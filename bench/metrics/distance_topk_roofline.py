"""distance_topk_roofline: the exact-scan kernel's share of its roofline.
Work per call: every real query against every row, 2*B*N*D operations
and the rows, scales and queries read once (bench/work.py), against the
published peaks (bench/peaks.py); time is the summed device time of the
kernel's events."""
from bench import peaks, work

KERNEL = "distance_topk"
# the Pallas call returns per-tile partials (f32[T, B, k], s32[T, B, k])
SIGNATURE = (r'^%\S+ = \(f32\[\d+,\d+,\d+\](\{[^}]*\})?, '
             r's32\[\d+,\d+,\d+\](\{[^}]*\})?\) '
             r'custom-call\(.*custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    t = ctx.trace.kernel_ns(SIGNATURE, lo, hi) / 1e9
    if t <= 0 or not ctx.calls:
        return None
    c = ctx.config
    dtype = ctx.config["index"]["params"].get("dtype", "fp32")
    flops = nbytes = 0.0
    for _, _, _, real in ctx.calls:
        f, b = work.distance_topk(real, c["rows"], c["dim"], dtype)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * peaks.least_seconds(ctx.device_kind, flops, nbytes) / t
