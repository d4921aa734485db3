"""Device idle share of the traced training window (%)."""
from readers import idle_share


def read(facts):
    return idle_share(facts)
