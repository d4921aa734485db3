"""Share of the reasoning window spent in prefill side steps (%)."""
from readers import prefill_share


def read(facts):
    return prefill_share(facts)
