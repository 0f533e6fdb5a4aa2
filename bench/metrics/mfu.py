"""The whole step's share of the chip's int8 peak: the operations the model
needs for the window's tokens (``bench/work.py``: every prompt token through
every layer, the LM head at the last prompt position and at each decoded
token, causal attention at the true context), over the traced window times
the peak."""


def read(run):
    if run.work.needed_ops <= 0:
        return None
    return 100.0 * run.work.needed_ops / (run.trace["window_s"] * run.peaks["int8_ops_per_s"])
