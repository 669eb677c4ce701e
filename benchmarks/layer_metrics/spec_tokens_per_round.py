"""Tokens a speculative round yields a live slot: ``emitted`` over ``rounds``
of the window's ``serve.decode`` spans, as the server booked them from the
blocks it read (the driver's counters ``spec_emitted`` and ``spec_rounds``).
1 + the share of drafts accepted: 1.0 on seeded weights, whose module meets the
target's argmax once in a vocabulary, and up to 2.0 with one draft a round; a
deployment's trained module reads 1.85-1.9 (DeepSeek-V3 section 5.4.3).
``None`` where the program counted no rounds."""

NAME, UNIT, LAYER, MOVES = ("spec_tokens_per_round", "count",
                            "multi-token prediction", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    rounds = counters.get("spec_rounds")
    if not rounds:
        return None
    return counters["spec_emitted"] / rounds
