"""Mean milliseconds per call of the harness's ``bench.h2d`` span: the
host-to-device copy of the call's input (``jax.device_put``, waited
for)."""


def read(obs):
    s = obs.spans.get("bench.h2d")
    return sum(s) / len(s) * 1e3 if s else None
