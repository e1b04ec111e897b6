"""Host milliseconds of the trainer's per-iteration ``lowered.compile(fns)``
(attaching the iteration's step bodies to the SWIRL plan), averaged over
the window's iterations."""


def read(out):
    return out.host.get("plan_compile_ms")
