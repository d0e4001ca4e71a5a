"""Share of the prompt tokens admitted in the window that the prefix
cache served (``EngineStats.prefix_hit_tokens``) instead of prefilling
(``EngineStats.prompt_tokens``).  0 where prompts share nothing."""


def read(r):
    hit = r.counters["prefix_hit_tokens"]
    total = hit + r.counters["prompt_tokens"]
    return hit / total if total else None
