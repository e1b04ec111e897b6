"""Model FLOPs per token times the window's tokens per second, over the
chips' bf16 peak, in percent.  FLOPs count forward and backward with no
recomputation (``chipbench.flops``)."""


def read(out):
    if out.peaks is None or "train_tokens_per_s" not in out.e2e:
        return None
    rate = out.cost["flops_per_token"] * out.e2e["train_tokens_per_s"]
    return 100.0 * rate / (out.peaks.bf16_flops * out.chips)
