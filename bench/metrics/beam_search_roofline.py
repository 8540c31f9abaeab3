"""beam_search_roofline: the fused layer-0 beam kernel's share of its
roofline. Work comes from the cell's parameters: per real query searched
in the traced window, ef x 2M candidate rows of D elements read and
scored (bench/work.py), against the published peaks (bench/peaks.py);
time is the summed device time of the kernel's events."""
from bench import peaks, work

KERNEL = "beam_search"
# the Pallas call returns (ids s32[B, W], dists f32[B, W])
SIGNATURE = (r'^%\S+ = \(s32\[\d+,\d+\](\{[^}]*\})?, '
             r'f32\[\d+,\d+\](\{[^}]*\})?\) '
             r'custom-call\(.*custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    t = ctx.trace.kernel_ns(SIGNATURE, lo, hi) / 1e9
    real = sum(c[3] for c in ctx.calls)
    if t <= 0 or real <= 0:
        return None
    p = ctx.config["index"]["params"]
    flops, nbytes = work.beam_search(
        real, p["ef_search"], p["M"], ctx.config["dim"],
        p.get("dtype", "fp32"))
    return 100.0 * peaks.least_seconds(ctx.device_kind, flops, nbytes) / t
