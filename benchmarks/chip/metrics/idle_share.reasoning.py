"""Device idle share of the traced reasoning window, while serving (%)."""
from readers import idle_share


def read(facts):
    return idle_share(facts)
