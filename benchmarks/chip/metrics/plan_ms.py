"""Wall time of the serving plan fetch, a DSE on every start (ms)."""


def read(facts):
    return facts.get("plan_ms")
