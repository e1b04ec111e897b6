"""Share of the roofline that the held experts' grouped products reach, in
percent: the least time their executed work needs (the larger of its FLOPs
over the bf16 peak and its least bytes over HBM bandwidth) over the device
time of the ops under the ``moe.held`` scope.  Work counts the routed
choices that landed on held experts, recomputation included
(``chipbench.train_counts``)."""


def read(out):
    c = out.counters
    t = c.get("scope_s.moe.held", 0.0)
    if out.peaks is None or "experts.flops" not in c or t <= 0:
        return None
    least = max(c["experts.flops"] / out.peaks.bf16_flops, c["experts.bytes"] / out.peaks.hbm_bytes_per_s)
    return 100.0 * least / t
