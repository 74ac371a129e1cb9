"""Share of the prompt tokens of calls admitted in the window that the
engine served from its prefix cache (engine.stats cached tokens)."""


def read(run):
    c = run.counters
    return 100.0 * c.cached_tokens / c.prompt_tokens if c.prompt_tokens \
        else None
