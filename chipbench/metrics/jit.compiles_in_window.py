"""Backend compilations between the window's open and its last answer;
the persistent cache is off in the window, so each is a full compile."""


def read(rec):
    return rec.compiles
