"""Device milliseconds per image of the operations whose scope path matches
``match`` (and not ``exclude``). Nothing matched: nothing returned."""

from benchmarks import trace


def reduce(reduced: dict, spec: dict):
    seconds = trace.scope_seconds(reduced, spec)
    if not seconds or not reduced["images"]:
        return None
    return 1e3 * seconds / reduced["images"]
