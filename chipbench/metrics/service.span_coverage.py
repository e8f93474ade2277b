"""Share of the service's batch time (`service.search_batch`) that its
child spans cover: how much of a batch the breakdown names."""

from chipbench import program


def read(rec):
    batches = program.named(rec, program.BATCH)
    if not batches:
        return None
    ids = {b.id for b in batches}
    children = [s for s in program.spans(rec) if s.parent in ids]
    return 100.0 * program.ms(children) / program.ms(batches)
