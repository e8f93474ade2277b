"""Open-loop boolean search traffic over the benchmark's log corpus.

A mix file (`traffic/<mix>.json`) names this generator and sets:

  rate_qps   offered load (the gaps of Poisson arrivals)
  warmup_s   length of the warm-up stream, at the same rate
  top_k      results per request (null: every match)
  zipf_s     popularity exponent over a pool's ranks
  pools      name -> {"pattern": p}: the corpus words that `p` matches
             whole, ranked by document frequency, most frequent first;
             "top": n keeps the first n; "pairs": true makes the pool's
             items pairs of such words adjacent in a line, ranked by how
             many lines hold them
  shapes     [{"share": n, "tree": T}], where T is a pool name (one word),
             ["and", T, ...], ["or", T, ...], ["not", T], or
             ["phrase", slop, pool] (a pair from a pairs pool)

Every seed gets the same requests, rank by rank and in the same order;
the seed changes the arrival times and the corpus (its block ids and
addresses), not the amount of work. A shape with c requests draws, for
each word it holds, the pool's ranks at the c Zipf quantiles of that
pool, each slot's in a fixed order of its own; words of equal frequency
are ranked by their text. The gaps between arrivals are the quantiles of
the exponential distribution of the rate (a Poisson process's), scaled
to fill the window.

The requests are dealt in a low-discrepancy order of their size, the
lines that their words select before verification, from a fixed start
(`ORDER_U`); the gaps in a low-discrepancy order of their length, from a
seeded start (`spread`). Requests of one size, and gaps of one length,
lie evenly over the window instead of anywhere in it: a request that
costs seconds (a phrase whose words share many lines that the phrase is
not in, which the reader verifies one by one) comes at the same place on
every seed, about a sixth of the window or more from the few nearest to
it in size, and has about as many requests arrive behind it. From a
seeded start it could fall in the window's last seconds, where the
window closes before the queue behind it forms. The gaps are Poisson's,
but their short and long ones do not bunch as a random order would
bunch them.
"""

from __future__ import annotations

import re

import numpy as np

from chipbench.corpus import apportion
from chipbench.openloop import Request

_PLAIN_WORD = re.compile(r"[a-z0-9_./][a-z0-9_\-./]*\Z")
_KEYWORDS = {"and", "or", "not"}


def schedule(mix: dict, ref, rng: np.random.Generator,
             seconds: float) -> list[Request]:
    """The requests due in a window of `seconds`, in order."""
    rate = float(mix["rate_qps"])
    n = max(1, round(rate * seconds))
    gaps = spread(-np.log1p(-(np.arange(n) + 0.5) / n) / rate, rng.random())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
        * (seconds / gaps.sum())
    shapes = mix["shapes"]
    counts = apportion([s["share"] for s in shapes], n)
    words = _Words(mix, ref)
    trees = []
    for shape, c in zip(shapes, counts.tolist()):
        slots = _pools_of(shape["tree"])
        columns = [words.ranks(pool, c)[_slot_order(c, j)]
                   for j, pool in enumerate(slots)]
        for i in range(c):
            trees.append(words.tree(shape["tree"],
                                    iter([(pool, col[i]) for pool, col
                                          in zip(slots, columns)])))
    texts = [to_text(t) for t in trees]
    size = [len(ref.matches(t, verified=False)) for t in trees]
    by_size = sorted(range(n), key=lambda i: (-size[i], texts[i]))
    return [Request(t, trees[by_size[i]], texts[by_size[i]], mix["top_k"])
            for t, i in zip(due.tolist(), spread(np.arange(n), ORDER_U).tolist())]


def _slot_order(c: int, slot: int) -> np.ndarray:
    """The fixed order in which slot `slot` of a shape pairs its c draws
    with the first slot's: the same on every seed."""
    if slot == 0:
        return np.arange(c)
    return spread(np.arange(c), np.random.default_rng(slot).random())


_GOLDEN = (5 ** 0.5 - 1) / 2
# where the requests' order by size starts, the same on every seed
ORDER_U = 0.0


def spread(values: np.ndarray, u: float) -> np.ndarray:
    """`values` reordered so that equal values lie far apart: position i
    takes the value whose rank is the rank of (u + i * golden ratio) mod 1
    among all positions. Equal values are neighbours in rank, and
    neighbours on that circle are about a quarter of the positions apart
    or more (the three-gap theorem)."""
    values = np.sort(values, kind="stable")
    key = (u + np.arange(len(values)) * _GOLDEN) % 1.0
    return values[np.argsort(np.argsort(key, kind="stable"), kind="stable")]


def _pools_of(spec) -> list[str]:
    """The pool of every word a tree of this shape draws, with repeats."""
    if isinstance(spec, str):
        return [spec]
    if spec[0] == "phrase":
        return [spec[2]]
    return [p for s in spec[1:] for p in _pools_of(s)]


class _Words:
    """The mix's pools, each ranked by document frequency, most frequent
    first, ties by text; Zipf-distributed draws over the ranks."""

    def __init__(self, mix: dict, ref) -> None:
        self.ref = ref
        self.zipf_s = float(mix["zipf_s"])
        self.pools = {}
        self.pairs = {n for n, spec in mix["pools"].items()
                      if spec.get("pairs")}
        for name, spec in mix["pools"].items():
            ok = _matching(ref, spec["pattern"])
            if spec.get("pairs"):
                items, df = _pairs(ref, ok)
            else:
                items = np.flatnonzero(ok)
                df = ref.df[items]
            text = np.unique([self._text(name, i) for i in items.tolist()],
                             return_inverse=True)[1]
            order = np.lexsort((text, -df))
            items = items[order[:spec.get("top", len(order))]]
            if not len(items):
                raise ValueError(f"pool {name!r} holds no word of this corpus")
            self.pools[name] = items

    def _text(self, pool: str, item: int) -> str:
        words = self.ref.words
        if pool not in self.pairs:
            return str(words[item])
        first, second = divmod(item, len(words))
        return f"{words[first]} {words[second]}"

    def ranks(self, pool: str, m: int) -> np.ndarray:
        """The pool's ranks at the m Zipf quantiles (i + 0.5) / m."""
        n = len(self.pools[pool])
        cdf = np.cumsum(np.arange(1, n + 1) ** -self.zipf_s)
        return np.searchsorted(cdf / cdf[-1], (np.arange(m) + 0.5) / max(m, 1))

    def word(self, pool: str, rank: int):
        item = int(self.pools[pool][rank])
        if pool not in self.pairs:
            return self._text(pool, item)
        return tuple(self._text(pool, item).split(" "))

    def tree(self, spec, items) -> tuple:
        """The tree of shape `spec`, its words the (pool, rank) of `items`
        in the order `_pools_of` lists them."""
        if isinstance(spec, str):
            return ("term", self.word(*next(items)))
        head, *rest = spec
        if head == "phrase":
            return ("phrase", self.word(*next(items)), int(rest[0]))
        if head == "not":
            return ("not", self.tree(rest[0], items))
        if head in ("and", "or"):
            return (head, tuple(self.tree(s, items) for s in rest))
        raise ValueError(f"unknown shape {spec!r}")


def _matching(ref, pattern: str) -> np.ndarray:
    """Which of the reference's words `pattern` matches whole and the
    query language reads as a plain word."""
    pat = re.compile(pattern)
    return np.fromiter((bool(pat.fullmatch(w)) and bool(_PLAIN_WORD.match(w))
                        and w not in _KEYWORDS for w in ref.words),
                       dtype=bool, count=len(ref.words))


def _pairs(ref, ok: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Pairs of `ok` words adjacent in a line, each as
    first * len(vocabulary) + second, and how many lines hold each."""
    ids, doc = ref.ids, ref.doc
    words = np.flatnonzero(ok)
    compact = np.cumsum(ok) - 1
    both = ok[ids[:-1]] & ok[ids[1:]] & (doc[:-1] == doc[1:])
    at = np.flatnonzero(both)
    pair = compact[ids[at]] * len(words) + compact[ids[at + 1]]
    per_line = np.unique(pair * max(ref.n_docs, 1) + doc[at])
    pair, lines = np.unique(per_line // max(ref.n_docs, 1),
                            return_counts=True)
    first, second = np.divmod(pair, len(words))
    return words[first] * len(ref.words) + words[second], lines


def to_text(tree: tuple) -> str:
    """The query language's text for a tree."""
    kind = tree[0]
    if kind == "term":
        return tree[1]
    if kind == "phrase":
        return '"' + " ".join(tree[1]) + '"' + (f"~{tree[2]}" if tree[2] else "")
    if kind == "not":
        sub = to_text(tree[1])
        return f"NOT ({sub})" if tree[1][0] in ("and", "or") else f"NOT {sub}"
    parts = [f"({to_text(s)})" if kind == "and" and s[0] == "or"
             else to_text(s) for s in tree[1]]
    return (" AND " if kind == "and" else " OR ").join(parts)
