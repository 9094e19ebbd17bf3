"""What decides ``correct``: every answer the timed path gave, compared with
the plain reference (``reference.py``).

Numbers compared, each against the limit that the configuration states:

* ``failed``: requests that were refused, got no answer by the end of the
  drain, or were answered degraded or off the full path (a request that
  expired in the queue is answered empty and degraded);
* ``<kind>_wrong``: full-path answers of ``count``, ``list`` or ``topk``
  that differ from the reference's in any way;
* ``tfidf_wrong``: tf-idf answers whose documents are not the reference's
  top k: a document the reference does not score, a count other than
  ``min(k, candidates)``, or an order that the reference's scores
  contradict by more than ``tfidf_score_err``'s limit;
* ``tfidf_score_err``: the largest gap between a served tf-idf score and
  the reference's score of that document, relative to the reference's
  score where that is above 1 (absolute below).

The answers of failed requests are not compared: ``failed`` itself has
the limit 0, so a run that drops, refuses or degrades any request is not
correct.  The configurations set no deadline, so a request that waits out a
stall of the host is answered in full, late: its latency counts the wait,
and only an answer that never comes or says the wrong thing fails the run.
"""

from __future__ import annotations


def _score_gap(served: float, ref: float) -> float:
    return abs(served - ref) / max(abs(ref), 1.0)


def tfidf_verdict(answer, scores: dict, k: int, tol: float) -> tuple[bool, float]:
    """(wrong, largest score gap) of one served tf-idf answer."""
    gap = 0.0
    docs = [doc for doc, _ in answer]
    if any(doc not in scores for doc in docs) or len(set(docs)) != len(docs):
        return True, float("inf")
    for doc, s in answer:
        gap = max(gap, _score_gap(s, scores[doc]))
    wrong = len(docs) != min(k, len(scores))
    ranked = [scores[doc] for doc in docs]
    slack = lambda r: tol * max(abs(r), 1.0)
    for a, b in zip(ranked, ranked[1:]):
        wrong |= a < b - slack(b)
    if ranked:
        served = set(docs)
        rest = max((s for doc, s in scores.items() if doc not in served), default=None)
        wrong |= rest is not None and ranked[-1] < rest - slack(rest)
    return wrong, gap


def compare(records, ref, *, k: int, conjunctive: bool, limits: dict,
            kinds) -> dict:
    """``{name: {"value": v, "limit": l}}`` over ``records`` (each with
    ``kind``, raw ``payload``, ``answer`` or None, and ``failed``)."""
    wrong = {kind: 0 for kind in kinds}
    gap = 0.0
    failed = 0
    tol = limits.get("tfidf_score_err", 0.0)
    for rec in records:
        if rec.failed:
            failed += 1
            continue
        got = rec.answer
        if rec.kind == "count":
            wrong["count"] += got != ref.count(rec.payload)
        elif rec.kind == "list":
            wrong["list"] += got != ref.list(rec.payload)
        elif rec.kind == "topk":
            wrong["topk"] += got != ref.topk(rec.payload, k)
        else:
            bad, g = tfidf_verdict(got, ref.tfidf_scores(rec.payload, conjunctive),
                                   k, tol)
            wrong["tfidf"] += bad
            gap = max(gap, g)
    out = {"failed": failed}
    out.update({f"{kind}_wrong": n for kind, n in wrong.items()})
    if "tfidf" in kinds:
        out["tfidf_score_err"] = gap
    return {name: {"value": v, "limit": limits[name]} for name, v in out.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
