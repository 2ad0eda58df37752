"""The coordinate-median kernel's share of its roofline, in %: the least time
to read n rows of d values and write one (`harness.kernel_cost`, bound by HBM
bytes) over the summed device time per step of the Mosaic kernel's events in
the trace. None where the trace holds no such event."""

from harness import kernel_cost, peaks, reduce_trace

def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    config = facts["config"]
    seconds = reduce_trace.kernel_seconds_per_step(
        trace["fullest"], reduce_trace.is_mosaic)
    if seconds is None:
        return None
    least = kernel_cost.coordinate_rule_least_seconds(
        config["num_workers"], config["num_params"], config["gar_dtype"],
        peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s"))
    return 100.0 * least / seconds
