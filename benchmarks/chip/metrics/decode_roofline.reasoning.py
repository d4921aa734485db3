"""Decode steps' byte-bound roofline share (%)."""
from readers import decode_roofline


def read(facts):
    return decode_roofline(facts)
