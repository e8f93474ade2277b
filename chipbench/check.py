"""The comparison that decides `correct`.

Every request answered in the window is compared with the reference
(`corpus.Reference`): the whole answer, not a sample. The configurations
guarantee that every returned document matches its query and that a
top-K answer holds min(K, matches) documents, all distinct. Three counts
follow, each exact, so each has the limit 0:

  wrong_docs     returned documents that do not match their query, are
                 returned twice, or are not a document of the corpus
                 with the bytes the corpus holds there
  short_results  documents missing from answers that hold fewer than
                 min(K, matches) matching documents
  unanswered     requests the program failed, or did not answer within
                 the drain limit past the window's close

Requests that the frontend refused at the door (`Overloaded`) are not
answers; they count as failed against `attempted`, not here.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"wrong_docs": 0, "short_results": 0, "unanswered": 0}


def compare(outcomes, ref, layout: dict, docs: list[str],
            ) -> dict[str, dict]:
    """`layout` maps (blob, offset) to a document's index in `docs`."""
    wrong = short = unanswered = 0
    cache: dict[tuple, np.ndarray] = {}
    for o in outcomes:
        if o.refused:
            continue
        if not o.answered:
            unanswered += 1
            continue
        tree, k = o.request.tree, o.request.top_k
        if tree not in cache:
            cache[tree] = ref.matches(tree)
        match = cache[tree]
        seen: set[int] = set()
        for r, text in zip(o.result.refs, o.result.texts):
            i = layout.get((r.blob, r.offset))
            if i is None or i in seen or text != docs[i] \
                    or r.length != len(docs[i].encode()):
                wrong += 1
                continue
            seen.add(i)
            pos = np.searchsorted(match, i)
            if pos == len(match) or match[pos] != i:
                wrong += 1
        found = int(np.isin(np.fromiter(seen, np.int64, len(seen)),
                            match).sum())
        want = len(match) if k is None else min(k, len(match))
        short += max(0, want - found)
    values = {"wrong_docs": wrong, "short_results": short,
              "unanswered": unanswered}
    return {name: {"value": v, "limit": LIMITS[name]}
            for name, v in values.items()}


def passed(checks: dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
