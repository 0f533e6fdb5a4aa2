"""Share of the roofline reached by the fused matmul kernels (the Pallas
calls ``qmatmul`` and ``qmatmul_packed``): the summed per-call bound
``max(ops / int8 peak, bytes / HBM bandwidth)`` of ``bench/work.py``'s
true-shape counts, over the kernels' device time in the trace."""


def read(run):
    t = run.trace["kernel_s"].get("qmatmul", 0.0)
    return 100.0 * run.work.kernels["qmatmul"].roofline_s / t if t > 0 else None
