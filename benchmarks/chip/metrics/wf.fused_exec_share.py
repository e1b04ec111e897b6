"""Share of step executions that ran inside a fused segment, in percent
(``stats["fused"]["fused_execs"] / stats["execs"]``)."""


def read(out):
    if not out.counters.get("execs"):
        return None
    return 100.0 * out.counters["fused_execs"] / out.counters["execs"]
