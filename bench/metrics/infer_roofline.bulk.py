"""Kernels: least time of the window's inference batches over the device's
busy time (%).  Every device operation of a cell without learning serves
inference, so the busy time is the kernels' time."""

from bench.readers import least_infer_s


def read(run):
    least = least_infer_s(run)
    red = run.reduced
    if least is None or red is None or red.busy_s <= 0:
        return None
    return 100.0 * least / red.busy_s
