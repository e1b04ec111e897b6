"""The busiest held expert's routed choices over the held experts' mean, in
the busiest MoE layer, averaged over the window's iterations: the grad
step's ``moe_load_max`` counter, read with the loss."""


def read(out):
    return out.counters.get("moe_load_max")
