"""layer: sparse experts. Device trace, device 0: time of every operation
whose `jax.named_scope` is under `olmoe/moe` (router, dispatch, grouped
matmuls, combine, forward and backward), per traced step."""

MOE_SCOPES = ("olmoe/moe", "olmoe/moe/router", "olmoe/moe/dispatch",
              "olmoe/moe/experts", "olmoe/moe/combine")


def scope_ms(run, scopes):
    """ms per traced step under `scopes`, or None where the run has no trace
    reduced by scope (a program without the scopes, a run without a trace)."""
    trace = run.get("trace")
    if not trace or not trace.get("scope_s") or not trace.get("steps"):
        return None
    found = [trace["scope_s"][s] for s in scopes if s in trace["scope_s"]]
    if not found:
        return None
    return 1e3 * sum(found) / trace["steps"]


def read(run):
    return scope_ms(run, MOE_SCOPES)
