"""The host's own milliseconds per engine iteration over the window: the
engine's ``engine.iteration`` span seconds, less the seconds its
``engine.harvest_wait`` spans spent blocked in ``jax.device_get``, over
the iterations (program span counters, ``stats["spans"]``).  None where
the program keeps no span counters."""


def _delta(a: dict, b: dict, name: str, field: str) -> float:
    return b.get(name, {}).get(field, 0) - a.get(name, {}).get(field, 0)


def read(res):
    if res["kind"] != "serve" or "spans" not in res["stats1"]:
        return None
    a, b = res["stats0"]["spans"], res["stats1"]["spans"]
    n = _delta(a, b, "engine.iteration", "count")
    if n <= 0:
        return None
    host = (_delta(a, b, "engine.iteration", "seconds")
            - _delta(a, b, "engine.harvest_wait", "seconds"))
    return 1e3 * host / n
