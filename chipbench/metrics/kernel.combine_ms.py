"""Device time of the intersect kernels (every Pallas call on the served
path), summed over the trace, per batch served in it."""


def read(rec):
    if rec.trace is None or not rec.spans:
        return None
    calls = [e for e in rec.trace.device_events if e.is_kernel]
    if not calls:
        return None
    return sum(e.dur_ns for e in calls) * 1e-6 / len(rec.spans)
