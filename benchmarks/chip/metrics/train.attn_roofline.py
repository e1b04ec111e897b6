"""Share of the roofline that the attention ops of the grad step reach, in
percent: the least time their executed work needs (the larger of its FLOPs
over the bf16 peak and its least bytes over HBM bandwidth) over their
device time, windowed and full layers together.  Work counts what the
program runs, recomputation included (``chipbench.train_counts``); time
sums the ops under the ``attn.window`` and ``attn.full`` scopes."""


def read(out):
    c = out.counters
    t = c.get("scope_s.attn.window", 0.0) + c.get("scope_s.attn.full", 0.0)
    if out.peaks is None or "attn.flops" not in c or t <= 0:
        return None
    least = max(c["attn.flops"] / out.peaks.bf16_flops, c["attn.bytes"] / out.peaks.hbm_bytes_per_s)
    return 100.0 * least / t
