"""Roofline share of the Pallas ``topk_mask`` kernel, %: the least time
its calls could take on the chip (bytes read and written, from each
call's shapes, over peak HBM bandwidth; the kernel does one compare and
select per element, so bandwidth bounds it) over their measured time."""


def read(ctx):
    secs, nbytes = ctx.reduced.kernel_calls(r"jit\(topk_mask\)/.*pallas_call")
    if secs <= 0.0:
        return None
    return 100.0 * (nbytes / ctx.peaks["hbm_bytes_per_s"]) / secs
