"""Mean host time per batch in the cluster-fused gather's phase 1
(`cluster.round1`): every shard's round-1 superpost fetch and decode, run
concurrently, up to the last shard's answer.
A program without the span reads nothing."""

from chipbench import program


def read(rec):
    if not program.named(rec, "cluster.round1"):
        return None
    return program.per_batch_ms(rec, "cluster.round1")
