"""The benchmark's plain reference.

`Reference` scans every line of the generated corpus (`corpora/`) with
a copy of the program's word rule and answers queries by set algebra
over that scan: it shares no code and no data with the index under
test. `Reference.matches(..., verified=False)` is the control: the same
scan with the round-2 verification skipped (phrases answered as the AND
of their words, negations ignored), which breaks the guarantee that
every returned document matches its query.

Queries are the generator's own trees (`traffic/boolean_mix.py`):
`("term", word)`, `("phrase", words, slop)`, `("and", items)`,
`("or", items)` and `("not", item)`; the program sees only their text.
"""

from __future__ import annotations

import re

import numpy as np

# the program's word rule (repro/data/tokenizer.py), applied to lowercased
# text, plus the line break that separates documents in a scan
_TOKEN_RE = re.compile(r"[a-z0-9_\-./]+|\n")
_SCAN_CHUNK = 100_000


def apportion(shares: list[float], n: int) -> np.ndarray:
    """Largest-remainder split of `n` by `shares`."""
    exact = np.asarray(shares, dtype=float) * n / sum(shares)
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


class Reference:
    """Inverted file of a corpus built by scanning every line.

    Tokens are kept in corpus order (`ids`, `doc`), and `order` sorts
    them by word, so a word's occurrences are one slice of it: their
    global positions, ascending, and the documents that hold them."""

    def __init__(self, docs: list[str]) -> None:
        self.n_docs = len(docs)
        vocab: dict[str, int] = {}
        parts = []
        for lo in range(0, len(docs), _SCAN_CHUNK):
            toks = _TOKEN_RE.findall(
                "\n".join(docs[lo:lo + _SCAN_CHUNK]).lower() + "\n")
            parts.append(np.fromiter(
                (vocab.setdefault(t, len(vocab)) for t in toks),
                dtype=np.int32, count=len(toks)))
        ids = np.concatenate(parts) if parts else np.empty(0, np.int32)
        newline = vocab.setdefault("\n", len(vocab))
        is_nl = ids == newline
        doc = np.cumsum(is_nl) - is_nl
        self.ids = ids[~is_nl]
        self.doc = doc[~is_nl].astype(np.int64)
        self.vocab = vocab
        self.words = np.array(list(vocab), dtype=object)
        self.order = np.argsort(self.ids, kind="stable")
        bounds = np.searchsorted(self.ids[self.order],
                                 np.arange(len(vocab) + 1))
        self._lo, self._hi = bounds[:-1], bounds[1:]
        pairs = np.unique(self.ids.astype(np.int64) * max(self.n_docs, 1)
                          + self.doc)
        self.df = np.bincount(pairs // max(self.n_docs, 1),
                              minlength=len(vocab))
        self.df[newline] = 0

    # ----------------------------------------------------------- scans
    def positions(self, word: str) -> np.ndarray:
        """Global token positions of `word`, ascending."""
        wid = self.vocab.get(word)
        if wid is None:
            return np.empty(0, np.int64)
        return self.order[self._lo[wid]:self._hi[wid]]

    def docs_with(self, word: str) -> np.ndarray:
        """Sorted ids of the documents that contain `word`."""
        return np.unique(self.doc[self.positions(word)])

    def phrase_docs(self, words: tuple[str, ...], slop: int) -> np.ndarray:
        """Documents where `words` occur in order, each the earliest next
        occurrence of its word, spanning at most `slop` extra tokens."""
        starts = self.positions(words[0])
        cur = starts.copy()
        ok = np.ones(len(starts), dtype=bool)
        for w in words[1:]:
            pos = self.positions(w)
            if not len(pos):
                return np.empty(0, np.int64)
            i = np.searchsorted(pos, cur, side="right")
            nxt = pos[np.minimum(i, len(pos) - 1)]
            ok &= (i < len(pos)) & (self.doc[nxt] == self.doc[starts])
            cur = np.where(ok, nxt, cur)
        ok &= (cur - starts + 1) - len(words) <= slop
        return np.unique(self.doc[starts[ok]])

    # --------------------------------------------------------- answers
    def matches(self, q: tuple, verified: bool = True) -> np.ndarray:
        """Sorted ids of the documents that match the tree `q`. With
        `verified=False` phrases lose their order and negations are
        dropped: the candidate superset an unverified answer serves."""
        kind = q[0]
        if kind == "term":
            return self.docs_with(q[1])
        if kind == "phrase":
            if verified:
                return self.phrase_docs(tuple(q[1]), q[2])
            return self.matches(("and", [("term", w) for w in q[1]]), False)
        if kind == "or":
            return np.unique(np.concatenate(
                [self.matches(s, verified) for s in q[1]]))
        if kind == "and":
            pos = [self.matches(s, verified) for s in q[1] if s[0] != "not"]
            out = pos[0]
            for p in pos[1:]:
                out = np.intersect1d(out, p, assume_unique=True)
            if verified:
                for s in q[1]:
                    if s[0] == "not":
                        out = np.setdiff1d(out, self.matches(s[1]),
                                           assume_unique=True)
            return out
        raise ValueError(f"the reference has no rule for {q!r}")
