"""Mean host time per batch building the combine kernels' operands
(`combine.build`): per-query universes, `searchsorted`, bitmaps and
program packing."""

from chipbench import program


def read(rec):
    return program.per_batch_ms(rec, "combine.build")
