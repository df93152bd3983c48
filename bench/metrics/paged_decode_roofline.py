"""Kernels: the Pallas paged decode kernel's share of its roofline in the
traced window: the bytes of the live K/V tokens of the decoding slots,
plus q and the output of every row, at HBM bandwidth
(``bench.flops.paged_decode_bound_s``), over the device time of the
kernel's events.  Dead pages and ghost lanes are not work, so a kernel
that skips them raises the share."""
from bench import flops

KERNEL = "_paged_pallas"


def read(run):
    if run.trace is None or "attn" not in (run.pallas_sites or ()):
        return None
    t_kernel, _n = run.trace.kernel_time(KERNEL)
    if t_kernel <= 0:
        return None
    a = run.arch
    bound = sum(a.layers * flops.paged_decode_bound_s(
        a, [ctx for _rid, ctx in t.decode], t.slots, run.peak["hbm_bytes_s"])
        for t in run.window_ticks if t.decode)
    return 100.0 * bound / t_kernel
