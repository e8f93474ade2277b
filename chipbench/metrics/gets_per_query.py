"""Object-store range reads issued from the window's open until its last
answer, per request answered."""


def read(rec):
    return rec.gets / rec.completed if rec.completed else None
