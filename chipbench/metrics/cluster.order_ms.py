"""Mean host time per batch in the cluster-fused gather's phase 3
(`cluster.order`): per-shard candidate permutations, the Eq. 6 quotas
and each job's canonical order.
A program without the span reads nothing."""

from chipbench import program


def read(rec):
    if not program.named(rec, "cluster.order"):
        return None
    return program.per_batch_ms(rec, "cluster.order")
