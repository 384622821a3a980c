"""The served lane steps' share of their HBM roofline: the least time
the chip could take for every lane-step the window's batches executed
(idle lanes and frozen tails included, the service's
`lane_steps_executed`), serve_counts.lane_step_bytes each at the peak
HBM rate (peaks.json), over the device time of every compute op in the
traced window (benchmark/layers.py), averaged over the chips. %, from
the device trace; nothing to read from a service without the counter."""

from benchmark import layers, serve_counts


def read(run):
    tr = run.trace
    executed = (getattr(run.window, "pipeline", None) or {}).get(
        "lane_steps_executed")
    if tr is None or not executed:
        return None
    compute_s = layers.seconds(tr, lambda op: True)
    if compute_s <= 0.0:
        return None
    least_s = (serve_counts.lane_step_bytes(run.config) * executed
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / compute_s
