"""Seconds of the window's `train.run` call before its first step: the
program's `repro.train.entry` span (config, mesh, shardings, jit
construction, init, stacking, context, data), read from `repro.obs`
after the window. Moves `train_tokens_per_s`."""
from bench import spans


def read(m):
    return spans.entry_s(m.info["attempted"])
