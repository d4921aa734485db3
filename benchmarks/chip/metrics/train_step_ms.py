"""Device time per train-step program run (ms)."""
from readers import TRAIN, program_ms


def read(facts):
    return program_ms(facts, TRAIN)
