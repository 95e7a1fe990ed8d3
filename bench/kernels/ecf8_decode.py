"""ECF8 weight decode (``kernels/ecf8_decode.py``): bytes per kernel call.

Every model call decodes every compressed weight once (a stacked leaf
once per layer, under the layer scan).  Each call reads the container
(lane payload, sign/mantissa rows, 40 table scalars) and writes one fp8
byte per element; it does no floating-point work.  The cost returned is
the mean over one model call's kernel calls, since the trace does not say
which weight a call decoded.
"""
# the device trace names the kernel's op after the jitted function that
# wraps its pallas_call (``kernels/ecf8_decode.decode_pallas``)
PATTERN = "%decode_pallas."
TABLE_BYTES = 40 * 4
LANES = 128


def cost(ctx):
    """(FLOPs, bytes) of the mean kernel call, or None."""
    total = calls = 0
    for arrays, meta, layers in ctx.weight_leaves:
        C, stride = arrays["payload"][-3], arrays["payload"][-2]
        R = arrays["signmant"][-2]
        per = (C * stride * LANES + C * R * LANES + TABLE_BYTES
               + C * meta.sym_per_lane * LANES)
        total += per * layers
        calls += layers
    return (0.0, total / calls) if calls else None
