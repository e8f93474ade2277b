"""Process start to window start, less the reference's scan: corpus,
index build, open, warm-up and every compile they cause (host clock)."""


def read(rec):
    return rec.setup_s
