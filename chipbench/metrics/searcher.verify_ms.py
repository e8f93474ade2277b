"""Mean host time per batch decoding and verifying fetched documents
(every `searcher.verify`, the round-2 fallback's included)."""

from chipbench import program


def read(rec):
    return program.per_batch_ms(rec, "searcher.verify")
