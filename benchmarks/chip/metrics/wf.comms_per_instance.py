"""COMMs the jax backend fired per workflow instance (``stats["comms"]``)."""


def read(out):
    if not out.units or "comms" not in out.counters:
        return None
    return out.counters["comms"] / out.units
