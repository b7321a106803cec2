"""Traces of jitted functions that the window's `train.run` call paid:
the sum of the program's `repro.trace.*` counts inside the call's root
span. Each counts once per trace of its function's Python body, never
per call. Read from `repro.obs` after the window. Moves
`train_tokens_per_s`."""
from bench import spans


def read(m):
    return spans.traces(m.info["attempted"])
