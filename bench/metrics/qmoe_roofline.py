"""Share of the roofline reached by the grouped routed-expert kernel (the
Pallas call ``qmoe``): the summed per-call bound ``max(ops / int8 peak,
bytes / HBM bandwidth)`` of the path's true-shape counts (operations for the
routed rows only, bytes for the weights of the experts they hit plus the
rows), over the kernel's device time in the trace."""


def read(run):
    t = run.trace["kernel_s"].get("qmoe", 0.0)
    qmoe = getattr(run.work, "kernels", {}).get("qmoe")
    return 100.0 * qmoe.roofline_s / t if t > 0 and qmoe is not None else None
