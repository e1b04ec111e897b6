"""The least chip time of the window's step bodies over the chips' busy
time, in percent.  The least time of a step is the larger of its FLOPs over
the bf16 peak and its least bytes over the HBM bandwidth (the
configuration's ``step_costs``); COMM copies are busy time but not in the
numerator, so the share stays under 100."""


def read(out):
    if out.reduction is None or "step_costs" not in out.cost:
        return None
    busy = sum(out.reduction.busy_by_device.values())
    if busy <= 0:
        return None
    p = out.peaks
    least = sum(
        max(flops / p.bf16_flops, nbytes / p.hbm_bytes_per_s)
        for flops, nbytes in out.cost["step_costs"]
    )
    return 100.0 * out.units * least / busy
