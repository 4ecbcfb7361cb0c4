"""Step dispatch: how many ids the streaming detokeniser hands to
`tokenizer.decode` for a token it emits. Every step record whose `emit`
span detokenised carries `detok_ids`, the ids decoded inside that span
since the record before (`serve/engine._incremental_text`: the few ids
since the text last sent, decoded twice: up to where that text ended,
and with the new token), so their sum over the window's records over
the tokens those records emitted is the window a token is decoded in.
It does not grow with the output: 3 on a vocabulary of whole words (the
last word alone, then it and the new one), a little more where a
character is split across tokens and the held ones widen the window. A
detokeniser that starts from the output's first token reads about half
the output's length. The emit of a step follows its record, so the two
sums are one step apart at each end of the window. A program whose
records have no such field reports nothing."""

DISPATCH = "step dispatch"

METRICS = [{"name": "detok_ids_per_token", "unit": "ids",
            "layer": DISPATCH, "moves": "out_tok_s",
            "source": "program_counter"}]


def read(run):
    steps = run.get("steps", [])
    tokens = sum(s.get("tokens", 0) for s in steps)
    if not tokens or not any("detok_ids" in s for s in steps):
        return {}
    return {"detok_ids_per_token":
            sum(s.get("detok_ids", 0) for s in steps) / tokens}
