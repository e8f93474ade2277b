"""Mean host time per batch in the combine kernels' calls
(`combine.call`), through the copy of the result to the host: dispatch,
compiles, transfers and device time."""

from chipbench import program


def read(rec):
    return program.per_batch_ms(rec, "combine.call")
