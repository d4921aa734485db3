"""Share of the reasoning decode step's device time in the cache gate (%)."""
from readers import DECODE
from spans import scope_share


def read(facts):
    return scope_share(facts, DECODE, "cache_gate")
