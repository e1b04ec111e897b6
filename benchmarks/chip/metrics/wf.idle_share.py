"""Share of the traced window in which no operation ran on a chip, in
percent: 1 - (union of device-op intervals / window), averaged over the
chips the cell uses."""


def read(out):
    if out.reduction is None:
        return None
    return 100.0 * out.reduction.idle_share
