"""Mean host time per batch in the cluster-fused combine: the
`combine.build`, `combine.call` and `combine.unpack` spans of the
`combine_cluster` kernel (bitmaps and programs for every (shard, query),
the one kernel call through the copy of its result to the host, and the
unpack of every candidate set). A program whose combine spans do not
name that kernel reads nothing."""

from chipbench import program

PHASES = ("combine.build", "combine.call", "combine.unpack")


def read(rec):
    found = [s for name in PHASES for s in program.named(rec, name)
             if s.attrs.get("kernel") == "combine_cluster"]
    batches = len(program.named(rec, program.BATCH))
    if not found or not batches:
        return None
    return program.ms(found) / batches
