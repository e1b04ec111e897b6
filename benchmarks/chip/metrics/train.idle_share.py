"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - (union of device-op intervals / window)."""


def read(out):
    if out.reduction is None:
        return None
    return 100.0 * out.reduction.idle_share
