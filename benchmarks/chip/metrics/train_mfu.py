"""Training model FLOP utilisation (%)."""
from readers import train_mfu


def read(facts):
    return train_mfu(facts)
