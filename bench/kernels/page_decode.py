"""Cold-page decode (``kvcache/kernels.py``): bytes per kernel call.

A decode or chunk step that sees cold pages decodes the whole cold pool
of each layer's K and V in one call each: it reads every cold slot's lane
payload (the uniform stride budget) and its (3, max_code_len) int32
tables, and writes one int32 canonical index per element.  No
floating-point work.
"""
# the device trace names the kernel's op after the jitted function that
# wraps its pallas_call (``kvcache/kernels.decode_page_indices_pallas``)
PATTERN = "%decode_page_indices_pallas."
LANES = 128


def cost(ctx):
    p = ctx.paged
    if p is None or not p.n_cold:
        return None
    n = p.n_cold
    S = -(-p.page_elems // LANES)
    return (0.0, n * p.stride_budget * LANES + n * 3 * p.max_code_len * 4
            + n * S * LANES * 4)
