"""Serving operations over the chip's peak (%)."""
from readers import serve_mfu


def read(facts):
    return serve_mfu(facts)
