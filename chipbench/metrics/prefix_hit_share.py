"""Share of the window's prompt tokens served from the prefix cache:
the engine's ``prefix_hit_tokens`` over ``prompt_tokens``, both taken
as their change across the window."""


def read(rec):
    w = rec.window
    prompt = w.stats1["prompt_tokens"] - w.stats0["prompt_tokens"]
    if prompt <= 0:
        return None
    hit = w.stats1["prefix_hit_tokens"] - w.stats0["prefix_hit_tokens"]
    return 100.0 * hit / prompt
