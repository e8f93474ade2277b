"""Wall time of the longest batch call into the service layer in the
window, timed by the benchmark's adapter around it: the batch that every
request queued behind it waits for."""


def read(rec):
    if not rec.spans:
        return None
    return max(s.end - s.start for s in rec.spans) * 1e3
