"""Prefix cache: prompt tokens served from cached blocks over all prompt
tokens admitted (hit + computed), summed over replicas (``prefix_stats``)."""


def read(obs):
    hit = sum(s["prefix"]["prefix_hit_tokens"] for s in obs.window.stats)
    comp = sum(s["prefix"]["prefill_tokens"] for s in obs.window.stats)
    return hit / (hit + comp) if hit + comp else None
