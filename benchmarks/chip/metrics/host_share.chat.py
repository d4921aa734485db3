"""Share of the batcher's time outside prefill and decode spans (%)."""
from readers import host_share


def read(facts):
    return host_share(facts)
