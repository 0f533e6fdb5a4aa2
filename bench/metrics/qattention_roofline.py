"""Share of the roofline reached by the fused attention kernel (the Pallas
call ``qattention``): the summed per-call bound of ``bench/work.py``'s
causal, true-context counts, over the kernel's device time in the trace."""


def read(run):
    t = run.trace["kernel_s"].get("qattention", 0.0)
    return 100.0 * run.work.kernels["qattention"].roofline_s / t if t > 0 else None
