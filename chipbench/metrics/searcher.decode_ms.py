"""Mean host time per batch in round-1 decoding (`searcher.decode`):
each fetched superpost decoded once, layers intersected per word."""

from chipbench import program


def read(rec):
    return program.per_batch_ms(rec, "searcher.decode")
