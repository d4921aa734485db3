"""Host time per chat decode step that the device cannot overlap (ms)."""
from spans import host_step_ms


def read(facts):
    return host_step_ms(facts)
