"""Share of the train step's device time in collective operations (%)."""
from readers import TRAIN, collective_share


def read(facts):
    return collective_share(facts, TRAIN)
