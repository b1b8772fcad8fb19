"""Share of the prompt tokens the entry stage served from its prefix
cache in the window: cached / (cached + computed), from the program's
``AREngine.prefix_stats``, in %."""


def read(run):
    cached = run.delta("prefix", "cached_tokens")
    total = cached + run.delta("prefix", "computed_tokens")
    return 100.0 * cached / total if total else None
