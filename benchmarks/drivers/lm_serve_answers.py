"""Driver ``lm_serve_answers``: ``lm_serve`` for a cell whose answers are
longer than 256 tokens (``sc2-serve-short``: outputs 128-512).

Everything is ``lm_serve``'s, by import: the model, the server, the warm-up,
the open loop, the request times, the counters and the result. One number
differs. ``lm_serve.check_against_reference`` asks the reference for the
logits of the last 256 positions of a sequence and then judges every
generated token, so an answer of more than 256 tokens indexes past them
(``IndexError``, my chip run, PR 29, call 3, on the parent commit and on the
change alike). Here the tail is as long as the cell's longest answer
(``traffic.output_tokens.max``), one length for every sequence so that the
reference compiles once a pad length. ``lm_serve.run`` looks its check up in
its own module, so this driver puts its check there for the run and takes it
out again: the accepted file is not edited (a ``benchmark`` PR would give
``lm_serve``'s check the tail as an argument and this file could go).

Workload file keys: those of ``lm_serve``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.drivers import lm_serve
from benchmarks.lib import reference_lm
from benchmarks.lib.outcome import Outcome

build_model = lm_serve.build_model          # what tools/find_knee*.py ask for
build_server = lm_serve.build_server


def check_against_reference(lm, cfg, finished, check, rng, *, n_tail):
    """``lm_serve.check_against_reference`` with the reference's tail
    ``n_tail`` positions long: each generated token of a seeded sample of
    finished requests must be the argmax of the reference's teacher-forced
    logits, or within ``near_tie`` x max|logit| of it."""
    short = [o for o in finished
             if len(o.arrival.prompt) <= check["short_max_prompt"]]
    picks = [short[j] for j in rng.permutation(len(short))
             [:check["sample_short"]]]
    longest = max(finished, key=lambda o: len(o.arrival.prompt))
    if longest not in picks:
        picks.append(longest)
    notes, ok = [], True
    for o in picks:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        n = len(toks)
        pad_to = max(n_tail, 1 << int(np.ceil(np.log2(len(seq)))))
        logits = np.asarray(reference_lm.tail_logits(
            lm.params, seq, cfg, min(len(seq), n_tail), pad_to=pad_to))[-n:]
        best = logits.max(axis=-1)
        gap = (best - logits[np.arange(n), toks]) / np.abs(logits).max(-1)
        bad = int(np.sum(gap > check["near_tie"]))
        ok &= bad == 0
        notes.append(f"check: prompt={len(o.arrival.prompt)} new={n} "
                     f"judged={n} off_argmax={int(np.sum(gap > 0))} "
                     f"worst_gap={float(gap.max()):.5f} "
                     f"beyond_near_tie={bad}")
    return ok, notes


def run(ctx) -> Outcome:
    n_tail = int(ctx.cell["traffic"]["output_tokens"]["max"])
    accepted = lm_serve.check_against_reference

    def check(lm, cfg, finished, limits, rng):
        return check_against_reference(lm, cfg, finished, limits, rng,
                                       n_tail=n_tail)

    lm_serve.check_against_reference = check
    try:
        return lm_serve.run(ctx)
    finally:
        lm_serve.check_against_reference = accepted
