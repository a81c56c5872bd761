"""A count of jax.monitoring events in one phase of the run. params:
`event` (compiles, cache_hits, cache_misses), `phase` (setup, window)."""


def read(view, params):
    return view.events.count(params["event"], params["phase"])
