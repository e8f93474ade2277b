"""Log lines shaped like Loghub's HDFS_v1, generated from a seed.

HDFS_v1 (Xu et al., SOSP 2009; Loghub, He et al., arXiv:2008.06448) is
the console log of an HDFS cluster of about 200 nodes: 11,175,629 lines
about 575,061 blocks, 19.4 lines a block, of which 16,838 blocks are
labelled anomalous. Its lines are

    081109 203518 143 INFO dfs.DataNode$DataXceiver: Receiving block blk_-1608999687919862906 src: /10.250.19.102:54106 dest: /10.250.19.102:50010

date, time, thread id, level, component, and one of the templates below
(the wording of Loghub's HDFS templates). A block's life gives 19 of
them: the namenode allocates it, each of its three replicas is received
(Receiving, PacketResponder terminating, Received) and reported to the
namenode (addStoredBlock), and later each replica is invalidated and
deleted. The 0.4 further lines a block that HDFS_v1 has beyond those 19
are reads (Served block, a failed serve) and scanner verifications, and
on the anomalous blocks the exceptions of a failed write pipeline.

Every count is apportioned, not drawn: every seed gives the same number
of blocks, of lines of each template and of anomalous blocks, so the
seed changes the ids, nodes, times and order, not the amount of text.
`params` is the configuration's `corpus` entry.
"""

from __future__ import annotations

import numpy as np

from chipbench.corpus import apportion

# (component, text); {b} block id, {n} node, {m} peer node, {port} a
# source port, {r} responder index, {size} block size, {sub} data subdir,
# {path} the file the block belongs to
NAMENODE = "dfs.FSNamesystem"
LIFE = (
    (NAMENODE, "BLOCK* NameSystem.allocateBlock: {path}. blk_{b}", 1),
    ("dfs.DataNode$DataXceiver",
     "Receiving block blk_{b} src: /{m}:{port} dest: /{n}:50010", 3),
    ("dfs.DataNode$PacketResponder",
     "PacketResponder {r} for block blk_{b} terminating", 3),
    ("dfs.DataNode$PacketResponder",
     "Received block blk_{b} of size {size} from /{m}", 3),
    (NAMENODE, "BLOCK* NameSystem.addStoredBlock: blockMap updated: "
     "{n}:50010 is added to blk_{b} size {size}", 3),
    (NAMENODE, "BLOCK* NameSystem.delete: blk_{b} is added to invalidSet "
     "of {n}:50010", 3),
    ("dfs.FSDataset", "Deleting block blk_{b} file "
     "/mnt/hadoop/dfs/data/current/subdir{sub}/blk_{b}", 3),
)
EXTRAS = {
    "served": ("dfs.DataNode$DataXceiver",
               "{n}:50010 Served block blk_{b} to /{m}"),
    "verified": ("dfs.DataBlockScanner", "Verification succeeded for blk_{b}"),
    "serve_exception": ("dfs.DataNode$DataXceiver",
                        "{n}:50010:Got exception while serving blk_{b} to /{m}:"),
}
ANOMALIES = (
    ("dfs.DataNode$DataXceiver", "writeBlock blk_{b} received exception "
     "java.io.IOException: Could not read from stream"),
    ("dfs.DataNode$DataXceiver", "Exception in receiveBlock for block blk_{b} "
     "java.io.IOException: Connection reset by peer"),
    ("dfs.DataNode$PacketResponder",
     "PacketResponder {r} for block blk_{b} Interrupted."),
    ("dfs.DataNode$DataXceiver", "Receiving empty packet for block blk_{b}"),
    ("dfs.DataNode$PacketResponder", "PacketResponder blk_{b} {r} Exception "
     "java.io.IOException: Broken pipe"),
)
WRITE = 5                  # LIFE's first five templates write the block
ANOMALY_LINES = 2          # exception lines a failed block logs
FULL_BLOCK = 67108864      # HDFS's 64 MiB block size of the time
JOB_USER = "root"          # the source's jobs wrote under /user/<this>


def make_lines(n_docs: int, rng: np.random.Generator, params: dict,
               ) -> list[str]:
    life = sum(c for _comp, _text, c in LIFE)
    n_blocks = min(max(1, round(n_docs * params["blocks_per_line"])),
                   n_docs // life)
    n_extra = n_docs - life * n_blocks
    n_bad = min(round(n_blocks * params["anomalous_block_share"]),
                n_extra // ANOMALY_LINES)
    extra_kinds = list(params["extras"])
    extra_counts = apportion([params["extras"][k] for k in extra_kinds],
                              n_extra - ANOMALY_LINES * n_bad)

    # the blocks: id, three distinct nodes, size, when written and deleted
    span = params["hours"] * 3600.0
    ids = rng.integers(-(1 << 63), (1 << 63) - 1, n_blocks, dtype=np.int64)
    n_nodes = params["nodes"]
    nodes = _addresses(rng, n_nodes)
    first = rng.integers(0, n_nodes, n_blocks)
    off1 = rng.integers(1, n_nodes, n_blocks)
    off2 = rng.integers(1, n_nodes - 1, n_blocks)
    off2 = off2 + (off2 >= off1)
    replica = np.stack([first, (first + off1) % n_nodes,
                        (first + off2) % n_nodes], axis=1)
    full = np.zeros(n_blocks, dtype=bool)
    full[rng.permutation(n_blocks)[:round(params["full_block_share"]
                                          * n_blocks)]] = True
    size = np.where(full, FULL_BLOCK, rng.integers(1, FULL_BLOCK, n_blocks))
    written = np.sort(rng.random(n_blocks)) * span * 0.9
    deleted = written + rng.random(n_blocks) * (span - written)
    job = 1 + (written // 1800).astype(np.int64)
    task = rng.integers(0, 2000, n_blocks)

    # one row a line: template, block, replica, seconds since the start
    rows_t, rows_b, rows_r, rows_s = [], [], [], []

    def add(template, blocks, reps, secs):
        rows_t.append(np.full(len(blocks), template))
        rows_b.append(blocks)
        rows_r.append(reps)
        rows_s.append(secs)

    every = np.arange(n_blocks)
    for t, (_comp, _text, count) in enumerate(LIFE):
        for r in range(count):
            if t < WRITE:   # the write pipeline, within seconds
                secs = written + t * 0.3 + rng.random(n_blocks) * 2.0
            else:           # invalidation and deletion, later
                secs = deleted + (t - WRITE) + rng.random(n_blocks) * 3.0
            add(t, every, np.full(n_blocks, r if count > 1 else 0), secs)
    n_life = len(LIFE)
    for name, count in zip(extra_kinds, extra_counts):
        b = rng.integers(0, n_blocks, count)
        add(n_life + list(EXTRAS).index(name), b,
            rng.integers(0, 3, count),
            written[b] + rng.random(count) * (deleted[b] - written[b]))
    bad = rng.permutation(n_blocks)[:n_bad]
    for j in range(ANOMALY_LINES):
        add(n_life + len(EXTRAS) + (np.arange(n_bad) + j) % len(ANOMALIES),
            bad, rng.integers(0, 3, n_bad),
            written[bad] + 0.5 + j * 0.5 + rng.random(n_bad))
    kind = np.concatenate(rows_t)
    blk = np.concatenate(rows_b)
    rep = np.concatenate(rows_r)
    secs = np.concatenate(rows_s)
    order = np.argsort(secs, kind="stable")
    kind, blk, rep, secs = kind[order], blk[order], rep[order], secs[order]

    templates = [(c, x) for c, x, _n in LIFE] + list(EXTRAS.values()) \
        + list(ANOMALIES)
    n = len(kind)
    node = replica[blk, rep]
    peer = replica[blk, (rep + 1 + rng.integers(0, 2, n)) % 3]
    namenode = np.array([c == NAMENODE for c, _x in templates])[kind]
    pid = np.where(namenode, rng.integers(18, 36, n),
                   rng.integers(100, 8000, n))
    port = rng.integers(32768, 61000, n)
    sub = rng.integers(0, 64, n)
    d0, t0 = params["start"].split()
    start = (int(d0[4:]) * 86400 + int(t0[:2]) * 3600 + int(t0[2:4]) * 60
             + int(t0[4:]) + secs.astype(np.int64))
    day, sec = np.divmod(start, 86400)
    hms = (sec // 3600) * 10000 + (sec // 60 % 60) * 100 + sec % 60
    dates = [f"{d0[:4]}{d:02d}" for d in range(int(day.max()) + 1)]
    warn = n_life + list(EXTRAS).index("serve_exception")
    fmt = [f"{{date}} {{hms:06d}} {{pid}} "
           f"{'WARN' if i == warn else 'INFO'} {c}: {x}"
           for i, (c, x) in enumerate(templates)]
    bid, bsize = ids.tolist(), size.tolist()
    path = [f"/user/{JOB_USER}/rand/_temporary/_task_200811092030_{j:04d}_m_"
            f"{k:06d}_0/part-{k:05d}"
            for j, k in zip(job.tolist(), task.tolist())]
    out = []
    for (k, b, r, d, h, p, nd, pr, po, sb) in zip(
            kind.tolist(), blk.tolist(), rep.tolist(), day.tolist(),
            hms.tolist(), pid.tolist(), node.tolist(), peer.tolist(),
            port.tolist(), sub.tolist()):
        out.append(fmt[k].format(
            date=dates[d], hms=h, pid=p, b=bid[b], n=nodes[nd], m=nodes[pr],
            port=po, r=r, size=bsize[b], sub=sb, path=path[b]))
    return out


def _addresses(rng: np.random.Generator, n: int) -> list[str]:
    """`n` distinct private addresses in 10.250.0.0/15, as the source's
    EC2 nodes had."""
    codes = rng.choice(2 * 256 * 256, n, replace=False)
    return [f"10.{250 + c // 65536}.{c // 256 % 256}.{c % 256}"
            for c in codes.tolist()]
