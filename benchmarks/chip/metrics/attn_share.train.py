"""Share of the train step's device time in attention (%)."""
from readers import TRAIN
from spans import scope_share


def read(facts):
    return scope_share(facts, TRAIN, "attn")
