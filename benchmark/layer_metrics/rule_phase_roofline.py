"""The aggregation rule's share of its roofline, in %, whatever implements
it: the least time to read n rows of d values and write one
(`harness.kernel_cost`, bound by HBM bytes) over the device time per step of
every operation that holds a ``phase.rule`` instruction — the mixed ones
included, so that time left out can never push the share over 100. None
where there is no map or no such operation (`harness.phase_map`)."""

from harness import kernel_cost, peaks, phase_map


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    seconds = phase_map.holding_seconds(trace, facts, "rule")
    if seconds is None:
        return None
    config = facts["config"]
    least = kernel_cost.coordinate_rule_least_seconds(
        config["num_workers"], config["num_params"], config["gar_dtype"],
        peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s"))
    return 100.0 * least / seconds
