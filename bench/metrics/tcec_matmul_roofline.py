"""Kernels: the fused TCEC matmul's share of its roofline in the traced
window: the sum over the window's matmuls of their least time
(``bench.flops.matmul_bound_s``: 2mnk at the bf16 peak, or operands and
result at their stored width over HBM bandwidth, whichever is larger)
over the device time of the kernel's events.  Only the sites the planner
put on the kernel count, with the rows each call really carries: decoding
slots for a decode step, real prompt tokens for a chunk."""
from bench import flops

KERNEL = "_tcec_matmul_fused"


def read(run):
    if run.trace is None or not run.pallas_sites:
        return None
    t_kernel, _n = run.trace.kernel_time(KERNEL)
    if t_kernel <= 0:
        return None
    a, pk = run.arch, run.peak
    shapes = [(k, n) for site, k, n in flops.matmul_shapes(a)
              if site in run.pallas_sites]
    bound = 0.0
    for t in run.window_ticks:
        rows = [n for _rid, n, _s, _last in t.prefill]
        if t.decode:
            rows.append(len(t.decode))
        for m in rows:
            bound += a.layers * sum(
                flops.matmul_bound_s(m, k, n, a.itemsize, pk["bf16_flops"],
                                     pk["hbm_bytes_s"]) for k, n in shapes)
    return 100.0 * bound / t_kernel
