"""Closed loop: questions over a few long shared documents.

Each request is one document followed by a short question; every slot
stays busy because ``batch_slots`` requests are outstanding at all
times (a slot freed in a step is refilled at the next).  The documents are prefilled at set-up, into
the prefix cache, as a deployment that serves them would have done.

Parameters (the mix's file): ``docs``; ``doc_len`` and ``question_len``,
each ``[min, max]``; ``output`` as ``{"median", "sigma", "min",
"max"}``; ``cycle``, the number of distinct
(document, question length, output length) triples that every seed
shares, in its own order.  Document lengths are odd, so a document never
ends on a page boundary and attaching it copies the boundary page.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import sampling
from benchmarks.chip.traffic.mix import Mix, Req

#: output tokens of the set-up requests: one prefill and one decode step
SETUP_NEW_TOKENS = 2


def make(traffic, *, seed: int, vocab: int, seconds: float,
         cell_params, engine) -> Mix:
    n_docs = int(traffic["docs"])
    lo, hi = traffic["doc_len"]
    doc_lens = [sampling.odd(int(x))
                for x in sampling.uniform_int_set(n_docs, lo, hi)]
    doc_lens = sampling.shuffled(np.array(doc_lens), seed, 1)
    docs = [sampling.rng(seed, 2, i).integers(0, vocab, int(n))
            .astype(np.int32) for i, n in enumerate(doc_lens)]
    m = int(traffic["cycle"])
    qlo, qhi = traffic["question_len"]
    o = traffic["output"]
    which = sampling.shuffled(np.arange(m) % n_docs, seed, 3)
    qlens = sampling.shuffled(sampling.uniform_int_set(m, qlo, qhi), seed, 4)
    olens = sampling.shuffled(sampling.lognormal_set(
        m, o["median"], o["sigma"], o["min"], o["max"]), seed, 5)

    def question(tag: int, k: int, n: int) -> np.ndarray:
        return sampling.rng(seed, tag, k).integers(0, vocab, n).astype(
            np.int32)

    def request(k: int) -> Req:
        j = k % m
        doc = docs[int(which[j])]
        return Req(prompt=np.concatenate([doc, question(6, k, int(qlens[j]))]),
                   max_new_tokens=int(olens[j]),
                   shared_prefix=len(doc))

    # one request per document puts it in the prefix cache (its boundary
    # page too); one more attaches a document, as every window request
    # will, so the copy of the boundary page is compiled at set-up
    setup = [Req(prompt=np.concatenate([d, question(7, i, qlo)]),
                 max_new_tokens=SETUP_NEW_TOKENS)
             for i, d in enumerate(docs)]
    setup.append(Req(prompt=np.concatenate(
        [docs[0], question(7, n_docs, qlo)]),
        max_new_tokens=SETUP_NEW_TOKENS, shared_prefix=len(docs[0])))
    return Mix(loop="closed", request=request,
               backlog=int(engine["batch_slots"]), setup=setup)
