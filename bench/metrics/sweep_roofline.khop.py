"""Share of the HBM roofline the k-hop sweeps reach: the bytes any
implementation must read (4 B per traversed edge, the out-edges of every
vertex within k - 1 hops of each distinct answered source, counted on the
reference CSR) over the chip's peak HBM bandwidth, divided by the device
time of the sweep program (``_batched_khop``) in the window."""


def read(run):
    if run.trace_summary is None or run.checked is None:
        return None
    _, seconds = run.trace_summary.program_seconds("_batched_khop")
    edges = sum(run.checked.traversed.values())
    if seconds <= 0 or not edges:
        return None
    return 100.0 * (4.0 * edges / run.peaks["hbm_bytes_per_s"]) / seconds
