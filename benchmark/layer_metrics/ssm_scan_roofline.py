"""The selective scans' share of the chip's bandwidth, in %: the bytes their
equations must move in one step (`harness.ssm_cost`) over the device time
per step of every operation that holds an instruction of ``model.ssm_scan``
— the mixed ones included, so that time left out can never push the share
over 100 — over the peak HBM bytes/s. None where there is no map, no such
operation, or a configuration without Mamba layers (`harness.model_map`)."""

from harness import model_map, peaks, ssm_cost


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    if "mamba" not in facts["config"]["model"].get("layer_types", ()):
        return None
    seconds = model_map.holding_seconds(trace, facts, "ssm_scan")
    if not seconds:
        return None
    peak = peaks.peak(facts["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * ssm_cost.bytes_per_step(facts["config"]) / (
        seconds * facts["chips"] * peak)
