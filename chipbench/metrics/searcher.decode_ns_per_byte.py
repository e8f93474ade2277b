"""The round-1 decoder's rate: time in `searcher.decode` spans over the
superpost payload bytes they decoded (their `bytes` attributes), summed
over the window, in ns a byte. Each span also holds the per-word layer
intersection, so this is the decode phase's cost per byte it read.

On the 16-shard cluster the shards' round-1 legs run concurrently under
one interpreter lock and their decode spans overlap, so there the
reading is the contended rate: each leg's span counts the time it
waited for the others too. A program whose decode spans carry no
`bytes` reads nothing."""

from chipbench import program


def read(rec):
    found = program.named(rec, "searcher.decode")
    if not program.named(rec, program.BATCH) \
            or not any("bytes" in s.attrs for s in found):
        return None
    n_bytes = sum(s.attrs.get("bytes", 0) for s in found)
    return program.ms(found) * 1e6 / n_bytes if n_bytes else None
