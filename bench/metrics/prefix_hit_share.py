"""prefix_hit_share: prompt tokens served from pool-resident prefix blocks
over all prompt tokens admitted in the window, in percent. From the
scheduler's own counters (`pool_stats()` prefix_hit_tokens and
prompt_tokens), read as the window opens and as it closes."""


def read(run, name):
    prompt = run.counters.get("prompt_tokens", 0.0)
    if prompt <= 0:
        return None
    return 100.0 * run.counters["prefix_hit_tokens"] / prompt
