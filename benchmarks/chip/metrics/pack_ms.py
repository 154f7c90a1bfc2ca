"""Mean milliseconds per batch of the harness's ``bench.pack`` span:
host packing (``packing.pack_documents``, then padding to the budget)."""


def read(obs):
    s = obs.spans.get("bench.pack")
    return sum(s) / len(s) * 1e3 if s else None
