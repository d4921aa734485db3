"""Device time per decode-step program run (ms)."""
from readers import DECODE, program_ms


def read(facts):
    return program_ms(facts, DECODE)
