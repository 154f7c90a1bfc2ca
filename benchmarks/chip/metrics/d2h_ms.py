"""Mean milliseconds per call of the harness's ``bench.d2h`` span:
reading the call's whole result back to the host (``jax.device_get``)."""


def read(obs):
    s = obs.spans.get("bench.d2h")
    return sum(s) / len(s) * 1e3 if s else None
