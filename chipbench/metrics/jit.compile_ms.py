"""Backend compile time in the window, from the program's `jit.compile`
records (JAX's own duration of each compile), summed."""

from chipbench import program


def read(rec):
    if not program.named(rec, program.BATCH):
        return None
    return sum(s.attrs["seconds"] for s in program.named(
        rec, "jit.compile")) * 1e3
