"""Mean host time per batch turning the kernels' result bitmaps back
into candidate keys and lengths (`combine.unpack`)."""

from chipbench import program


def read(rec):
    return program.per_batch_ms(rec, "combine.unpack")
