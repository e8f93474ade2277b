"""Mean host time per batch in the cluster-fused gather's round-2
document fetches (`cluster.round2`, one a fetch round: the quota round
and each completion round).
A program without the span reads nothing."""

from chipbench import program


def read(rec):
    if not program.named(rec, "cluster.round2"):
        return None
    return program.per_batch_ms(rec, "cluster.round2")
