"""layer: embedding engine. Device trace, device 0: time of the placement
kernel of the embedding backward (`place_sorted_grads`, by its own name: a
program may hold other Mosaic kernels, as xDeepFM's CIN has since PR 40), per
traced step."""

KERNEL = "place_sorted_grads"


def traced_kernel(run):
    """(the kernel's seconds in the traced window, the traced steps), or None
    where the run has no trace, no count of its steps or no such kernel."""
    trace = run.get("trace") or {}
    seconds = (trace.get("mosaic_kernel_s") or {}).get(KERNEL)
    if not seconds or not trace.get("steps"):
        return None
    return seconds, trace["steps"]


def read(run):
    traced = traced_kernel(run)
    if traced is None:
        return None
    seconds, steps = traced
    return 1e3 * seconds / steps
