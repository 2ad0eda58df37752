"""The expert matmuls' share of the chip's peak, in %: the FLOPs the held
experts' pairs need in one step at the uniform expectation
(`harness.moe_cost`: bound by operations) over the device time per step of
every operation that holds a ``model.moe_experts`` instruction — the mixed
ones included, so that time left out can never push the share over 100 —
over the peak bf16 FLOP/s. None where there is no map or no such operation
(`harness.model_map`)."""

from harness import model_map, moe_cost, peaks


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    seconds = model_map.holding_seconds(trace, facts, "moe_experts")
    if seconds is None:
        return None
    peak = peaks.peak(facts["device"]["kind"], "bf16_flops")
    return 100.0 * moe_cost.expert_flops_per_step(facts["config"]) / (
        seconds * facts["chips"] * peak)
