"""Window time per counted event, in ms: window / (counter increase x the
value named by ``times``, e.g. decode chunks x steps per chunk). Wall time
per event, host gaps included."""


def read(spec, obs):
    n = obs.counters.get(spec["counter"], 0.0)
    if "times" in spec:
        n *= obs.values.get(spec["times"], 0.0)
    if n <= 0:
        return None
    return obs.window_s * 1000.0 / n
